"""Public jit'd wrappers around the binary-GEMM kernels.

Since the dispatch refactor this module is a thin compatibility surface over
``kernels/dispatch.py`` — the single place that owns backend selection, the
tile-size heuristic table, pad-correction arithmetic, and the fused
epilogue.  Benchmarks and tests keep calling these names; layer code should
use :mod:`repro.kernels.dispatch` directly.

Backend selection (see the dispatch registry):
  * "vpu"  — Pallas popcount kernel (the literal paper algorithm)
  * "mxu"  — Pallas unpack-to-int8 MXU kernel (TPU-native, beyond-paper)
  * "xla"  — pure-jnp reference (oracle / fallback; also what the multi-pod
             dry-run lowers, since pallas_call in interpret mode is not a
             meaningful target for cost analysis)

Pallas kernels compile on TPU and interpret on the CPU backend
(:func:`repro.kernels.resolve_interpret`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitpack
from repro.kernels import dispatch

WORD_BITS = bitpack.WORD_BITS


def pack_activations(
    x: jax.Array, *, backend: str = "pallas", interpret: bool | None = None
) -> jax.Array:
    """Binarize+pack (M, K) float -> (M, ceil(K/32)) uint32.

    Rows are NOT padded (output keeps M); K tail bits are 0.
    """
    return dispatch.pack_activations(
        x, use_pallas=backend != "xla", interpret=interpret
    )


def xnor_gemm(
    a_packed: jax.Array,  # (M, Kw) uint32
    b_packed: jax.Array,  # (N, Kw) uint32  (weights, transposed layout)
    *,
    k_true: int,
    backend: str = "vpu",
    bm: int | None = None,
    bn: int | None = None,
    bkw: int | None = None,
) -> jax.Array:
    """Exact ±1 dot product (M, N) int32 from packed operands."""
    cfg = dispatch.GemmConfig(backend=backend, bm=bm, bn=bn, bkw=bkw)
    return dispatch.packed_gemm(a_packed, b_packed, k_true=k_true, config=cfg)


def binary_dot(
    x: jax.Array,  # (..., K) float activations
    w_packed: jax.Array,  # (N, Kw) uint32 packed weights
    *,
    k_true: int,
    backend: str = "vpu",
    out_dtype=jnp.float32,
) -> jax.Array:
    """Full packed-serving matmul: binarize+pack x, xnor-GEMM with packed w.

    Returns (..., N) in ``out_dtype`` — numerically identical to
    ``sign(x) @ sign(W)`` computed in floats (paper §2.2.2 invariant).
    """
    return dispatch.quant_gemm(
        x,
        w_packed,
        k_true=k_true,
        config=dispatch.GemmConfig(backend=backend),
        epilogue=dispatch.EpilogueSpec(out_dtype=out_dtype),
    )
