"""Pallas kernels for the packed binary / k-bit GEMMs, the fused
quantize->pack prologue and fused decode attention.

Every kernel entry point takes ``interpret: bool | None``; ``None``
resolves through :func:`resolve_interpret`, the one place that decides
how a kernel executes."""

from __future__ import annotations

import jax

# Mosaic block rule on TPU: a block's minor dim is a multiple of LANES (or
# the array's whole dim), its second-minor dim a multiple of SUBLANES (or
# the whole dim).
LANES = 128
SUBLANES = 8


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Interpret mode follows the platform: the Pallas interpreter on the
    CPU backend, Mosaic-compiled kernels on TPU.  An explicit bool wins
    (tests pin the interpreter with ``interpret=True``).  Any other
    platform raises rather than silently timing the interpreter."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas execution mode for platform {backend!r}: kernels "
        "compile on 'tpu' and interpret on 'cpu'")
