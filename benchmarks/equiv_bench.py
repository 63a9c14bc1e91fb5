"""§2.2.2 equivalence spot-bench: the float-MXU path and the packed-xnor
path agree bit-for-bit, the Pallas kernels (interpret mode) match too, and
the k-bit (DoReFa) plane-packed path matches the fake-quant train path to
fp32 rounding.  Reports timing for context (interpret mode is slow on CPU
by design — the Pallas numbers are correctness evidence, not performance).

Every row carries ``exact_match`` — the CI bench-smoke job fails the build
if any row reports False (benchmarks/run.py --fail-on-mismatch)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack, quant
from repro.kernels import dispatch, ops, ref
from repro.kernels.dispatch import GemmConfig
from repro.launch.mesh import make_mesh


def rows(small: bool = False):
    rng = np.random.default_rng(0)
    m, k, n = (64, 512, 48) if small else (256, 4096, 256)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    oracle = np.asarray(ref.sign_gemm_ref(a, w)).astype(np.int32)
    ap, wp = bitpack.pack_sign(a), bitpack.pack_sign(w.T)

    for backend in ("xla", "vpu", "mxu"):
        t0 = time.perf_counter()
        got = np.asarray(ops.xnor_gemm(ap, wp, k_true=k, backend=backend))
        dt = (time.perf_counter() - t0) * 1e6
        exact = bool((got == oracle).all())
        yield {"backend": backend, "bits": 1, "M": m, "K": k, "N": n,
               "us_per_call_cold": round(dt, 1), "exact_match": exact}

    # tensor-parallel: every shard-* backend must be BIT-IDENTICAL to the
    # oracle at every split (Kw-partial int32 popcounts psum exactly; pad
    # correction applies once on the reduced sum).  Rows appear when the
    # process has multiple devices — CI forces 8 virtual host devices.
    n_dev = len(jax.devices())
    for ways in (2, 8):
        if ways > n_dev:
            continue
        mesh = make_mesh((ways,), ("model",))
        for backend in ("shard-vpu", "shard-mxu"):
            for layout in ("k", "n"):
                cfg = GemmConfig(backend=backend, mesh=mesh,
                                 shard_layout=layout)
                t0 = time.perf_counter()
                got = np.asarray(dispatch.packed_gemm(
                    ap, wp, k_true=k, config=cfg))
                dt = (time.perf_counter() - t0) * 1e6
                yield {"backend": f"{backend}/{layout}x{ways}", "bits": 1,
                       "M": m, "K": k, "N": n,
                       "us_per_call_cold": round(dt, 1),
                       "exact_match": bool((got == oracle).all())}

    # k-bit: plane-packed DoReFa GEMM vs the fake-quant oracle (allclose
    # at fp32 — the integer plane path differs from the float path only by
    # fp32 rounding of the quantized values)
    km, kk, kn = (32, 256, 24) if small else (64, 1024, 64)
    ak = jnp.asarray(rng.standard_normal((km, kk)), jnp.float32)
    wk = jnp.asarray(rng.standard_normal((kk, kn)), jnp.float32)
    for bits in (2, 4, 8):
        wk_planes = bitpack.pack_planes(
            quant.weight_codes(wk.T, bits), bits
        )
        want = np.asarray(ref.dorefa_gemm_ref(ak, wk, bits, bits))
        backends = ("xla", f"vpu-k{bits}", f"mxu-k{bits}")
        if bits == 4 and n_dev >= 2:  # sharded k-bit plane gate rows
            backends += (f"shard-vpu-k{bits}", f"shard-mxu-k{bits}")
        for backend in backends:
            cfg = GemmConfig(
                backend=backend,
                mesh=(make_mesh((2,), ("model",))
                      if backend.startswith("shard-") else None),
            )
            t0 = time.perf_counter()
            got = np.asarray(dispatch.quant_gemm(
                ak, wk_planes, k_true=kk, config=cfg,
                w_bits=bits, a_bits=bits,
            ))
            dt = (time.perf_counter() - t0) * 1e6
            exact = bool(np.allclose(got, want, rtol=1e-5, atol=1e-4))
            yield {"backend": backend, "bits": bits, "M": km, "K": kk,
                   "N": kn, "us_per_call_cold": round(dt, 1),
                   "exact_match": exact}
