"""Paper Figures 1-3: GEMM method comparison.

The paper benchmarks (on x86): naive C GEMM, Cblas/Atlas, xnor_32/64(+omp)
within a conv layer (M=filters, N=spatial*batch, K=k*k*Cin).  The TPU-
framework equivalents measured here on the host CPU via XLA:

  * ``dense_f32``    — XLA float GEMM (the Cblas stand-in)
  * ``xnor_packed``  — packed xnor GEMM, jnp/XLA reference path (popcount)
  * ``xnor_packed+binarize`` — same, including on-the-fly input packing
    (Fig. 1's "binarize input and xnor_64_omp" bar)
  * ``naive_loop``   — tiny python-loop GEMM on a SUBSAMPLE, extrapolated
    (the paper's naive baseline; only for the speedup denominator)

Axes swept exactly like the paper: Fig1 varies input channels, Fig2 varies
filter count, Fig3 varies kernel size.  Wall-times are host-CPU; the TPU
projection lives in the roofline analysis, not here.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack
from repro.kernels import ref
from repro.launch.mesh import make_mesh


def _time(fn, *args, warmup=2, iters=5) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


import functools


@jax.jit
def _dense(a, b):
    return a @ b


@functools.partial(jax.jit, static_argnums=(2,))
def _xnor_packed(ap, bp, k):
    return ref.xnor_gemm_ref(ap, bp, k)


@functools.partial(jax.jit, static_argnums=(2,))
def _xnor_with_binarize(a, bp, k):
    ap = bitpack.pack_sign(a)
    return ref.xnor_gemm_ref(ap, bp, k)


def _naive_us(m, n, k) -> float:
    """Extrapolated python/NumPy-loop GEMM time (paper's naive baseline)."""
    mm, nn = min(m, 16), min(n, 64)
    a = np.random.randn(mm, k).astype(np.float32)
    b = np.random.randn(k, nn).astype(np.float32)
    t0 = time.perf_counter()
    out = np.zeros((mm, nn), np.float32)
    for i in range(mm):
        for j in range(nn):
            out[i, j] = float(np.dot(a[i], b[:, j]))
    dt = (time.perf_counter() - t0) * 1e6
    return dt * (m / mm) * (n / nn)


def conv_gemm_row(filters=64, kernel=5, channels=256, batch=200, spatial=8):
    """One (M,N,K) point with the paper's conv-layer mapping."""
    m = filters
    k = kernel * kernel * channels
    n = batch * spatial * spatial
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    ap = bitpack.pack_sign(a)
    bp = bitpack.pack_sign(b.T)

    t_dense = _time(_dense, a, b)
    t_xnor = _time(_xnor_packed, ap, bp, k)
    t_xnor_bin = _time(_xnor_with_binarize, a, bp, k)
    t_naive = _naive_us(m, n, k)
    return {
        "M": m, "N": n, "K": k,
        "dense_f32_us": t_dense,
        "xnor_packed_us": t_xnor,
        "xnor_with_binarize_us": t_xnor_bin,
        "naive_us_extrapolated": t_naive,
        "speedup_vs_dense": t_dense / t_xnor,
        "speedup_vs_naive": t_naive / t_xnor,
    }


def fig1_rows(small: bool = False):
    """Fig 1: vary input channel size; filters=64, kernel=5, batch=200.
    ``small`` shrinks every axis for the CI bench-smoke job."""
    if small:
        for ch in (16, 32):
            yield {"sweep": "channels", "value": ch,
                   **conv_gemm_row(filters=16, kernel=3, channels=ch,
                                   batch=16, spatial=2)}
        return
    for ch in (64, 128, 256, 512):
        yield {"sweep": "channels", "value": ch,
               **conv_gemm_row(channels=ch, spatial=4)}


def fig2_rows(small: bool = False):
    """Fig 2: vary filter number; channels=256, kernel=5, batch=200."""
    for f in (8, 16) if small else (16, 32, 64, 128):
        yield {"sweep": "filters", "value": f,
               **(conv_gemm_row(filters=f, kernel=3, channels=32, batch=16,
                                spatial=2) if small
                  else conv_gemm_row(filters=f, spatial=4))}


def fig3_rows(small: bool = False):
    """Fig 3: vary kernel size; channels=256, batch=200, filters=64."""
    for ks in (1, 3) if small else (1, 3, 5, 7):
        yield {"sweep": "kernel", "value": ks,
               **(conv_gemm_row(filters=16, kernel=ks, channels=32,
                                batch=16, spatial=2) if small
                  else conv_gemm_row(kernel=ks, spatial=4))}


# ---------------------------------------------------------------------------
# Fig. 1's "binarize input" stage in isolation: the fused quantize->pack
# Pallas prologue (kernels/pack_bits.py, via dispatch.pack_activations /
# pack_act_planes) vs the jnp reference round trip (pack_sign /
# act_codes -> pack_planes).  Every row carries ``exact_match`` — the
# fused kernels must be BIT-IDENTICAL to the jnp oracle (code row-sums
# included), and the CI bench-smoke gate fails the build otherwise.  On
# this host-CPU rig the Pallas numbers run in interpret mode (correctness
# evidence, not performance).
# ---------------------------------------------------------------------------


def pack_rows(small: bool = False):
    from repro.core import quant
    from repro.kernels import dispatch

    m, k = (64, 512) if small else (512, 4096)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)

    def fused_sign(x):
        return dispatch.pack_activations(x, use_pallas=True)

    def jnp_sign(x):
        return dispatch.pack_activations(x, use_pallas=False)

    want = np.asarray(bitpack.pack_sign(a))
    got = np.asarray(fused_sign(a))
    yield {
        "stage": "pack_sign", "bits": 1, "M": m, "K": k,
        "jnp_us": round(_time(jnp_sign, a), 1),
        "fused_us": round(_time(fused_sign, a), 1),
        "exact_match": bool((got == want).all()),
    }

    for bits in (2, 4, 8) if not small else (2, 4):
        def fused_planes(x, b=bits):
            return dispatch.pack_act_planes(x, b, fused=True)

        def jnp_planes(x, b=bits):
            return dispatch.pack_act_planes(x, b, fused=False)

        codes = quant.act_codes(a, bits)
        want_p = np.asarray(bitpack.pack_planes(codes, bits))
        want_t = np.asarray(codes.astype(jnp.int32).sum(-1))
        got_p, got_t = fused_planes(a)
        exact = bool(
            (np.asarray(got_p) == want_p).all()
            and (np.asarray(got_t)[:, 0] == want_t).all()
        )
        yield {
            "stage": "quant_pack_planes", "bits": bits, "M": m, "K": k,
            "jnp_us": round(_time(jnp_planes, a), 1),
            "fused_us": round(_time(fused_planes, a), 1),
            "exact_match": exact,
        }


# ---------------------------------------------------------------------------
# Beyond-paper: the k-bit (DoReFa) sweep — how the bit-plane popcount GEMM
# scales with bit width.  Work grows as ka*kb plane pairs while packed HBM
# bytes grow as k/32 of fp32; the sweep reports both so the roofline can
# place w2/w4/w8 serving between the 1-bit xnor path and dense f32.
# ---------------------------------------------------------------------------


@jax.jit
def _plane_gemm(ap, wp):
    return ref.kbit_gemm_ref(ap, wp)


# ---------------------------------------------------------------------------
# Beyond-paper: the tensor-parallel (shard-*) sweep — the same packed GEMM
# partitioned across mesh devices (Kw-partial popcount + psum, or
# N-partitioned weights).  Every row carries ``exact_match`` against the
# single-device backend: the sharded path must be BIT-IDENTICAL, and the
# CI equivalence gate also covers it (benchmarks/equiv_bench.py).  On this
# host-CPU rig the timings measure collective/shard_map overhead, not TPU
# speedup — the correctness columns are the point.
# ---------------------------------------------------------------------------


def shard_rows(small: bool = False):
    """Sweep shard width (1/2/4/8-way) x backend over a fixed conv-mapped
    GEMM.  Multi-way rows need multiple devices — CI forces 8 virtual
    host devices via XLA_FLAGS.  In --smoke (CI gate) mode a single-device
    process emits an explicit ``exact_match=False`` row instead of
    silently skipping: otherwise a dropped/ignored XLA flag would turn
    the sharded-vs-single-device gate vacuously green."""
    from repro.kernels import dispatch
    from repro.kernels.dispatch import GemmConfig

    ndev = len(jax.devices())
    if small and ndev < 2:
        yield {
            "backend": "shard-*", "layout": "-", "ways": 0, "devices": ndev,
            "error": "smoke shard sweep needs >= 2 devices (set XLA_FLAGS="
                     "--xla_force_host_platform_device_count=8)",
            "exact_match": False,
        }
        return
    m, k, n = (32, 288, 16) if small else (128, 2304, 64)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    ap = bitpack.pack_sign(a)
    wp = bitpack.pack_sign(w.T)

    def run(cfg):
        return dispatch.packed_gemm(ap, wp, k_true=k, config=cfg)

    single = {}
    for inner in ("vpu", "mxu"):
        cfg = GemmConfig(backend=inner)
        # the correctness run doubles as the jit warm-up
        single[inner] = (np.asarray(run(cfg)),
                         _time(run, cfg, warmup=0, iters=2))

    for ways in (1, 2, 4, 8):
        if ways > ndev:
            continue
        mesh = make_mesh((ways,), ("model",))
        for inner in ("vpu", "mxu"):
            for layout in ("k", "n"):
                cfg = GemmConfig(backend=f"shard-{inner}", mesh=mesh,
                                 shard_layout=layout)
                got = np.asarray(run(cfg))  # also the jit warm-up
                t_us = _time(run, cfg, warmup=0, iters=2)
                want, t_single = single[inner]
                yield {
                    "backend": f"shard-{inner}", "layout": layout,
                    "ways": ways, "M": m, "N": n, "K": k,
                    "devices": ndev,
                    "single_device_us": round(t_single, 1),
                    "sharded_us": round(t_us, 1),
                    "exact_match": bool((got == want).all()),
                }


# ---------------------------------------------------------------------------
# Beyond-paper: decode-shape latency — the serving regime the mxu-k*
# backends target.  Autoregressive decode runs the quantized GEMM at tiny
# M (the in-flight batch) against fixed serving (N, K); the plane-popcount
# path pays ka*kb plane-pair passes regardless of M while the int8
# code-lane MXU path pays one dot, so the win should show exactly here.
# Rows time the full fused-prologue from-float path (dispatch.quant_gemm)
# for dense f32 vs vpu-k{bits} vs mxu-k{bits} at M in {1, 8, 32, 64}.
# Every row carries ``exact_match``: the mxu-k result must be BIT-identical
# to the vpu-k result (same raw (S, T) -> same fp32 dequant) and both must
# match the fake-quant oracle to fp32 rounding.  The overlap rows gate
# ``GemmConfig.overlap_collective`` — the chunked ppermute ring on the
# sharded "k" layout must be bit-identical to the sequential-psum default
# at every split, overlap on AND off.  All rows are covered by the CI
# bench-smoke --fail-on-mismatch gate.
# ---------------------------------------------------------------------------


def decode_rows(small: bool = False):
    from repro.core import quant
    from repro.kernels import dispatch, ref
    from repro.kernels.dispatch import GemmConfig

    n, k = (64, 512) if small else (1024, 4096)
    bits_sweep = (4, 8) if small else (2, 4, 8)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    def run(cfg, x, w_planes, bits):
        return dispatch.quant_gemm(x, w_planes, k_true=k, config=cfg,
                                   w_bits=bits, a_bits=bits)

    planes = {
        bits: bitpack.pack_planes(quant.weight_codes(w.T, bits), bits)
        for bits in bits_sweep
    }
    for bits in bits_sweep:
        w_planes = planes[bits]
        cfg_v = GemmConfig(backend=f"vpu-k{bits}")
        cfg_m = GemmConfig(backend=f"mxu-k{bits}")
        for m in (1, 8, 32, 64):
            x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
            want = np.asarray(ref.dorefa_gemm_ref(x, w, bits, bits))
            # the correctness runs double as the first jit warm-up; decode
            # calls are sub-ms here, so report the min over repeated
            # timing blocks (single-block means swing 2x on a shared host)
            got_v = np.asarray(run(cfg_v, x, w_planes, bits))
            got_m = np.asarray(run(cfg_m, x, w_planes, bits))
            t_dense = min(_time(_dense, x, w) for _ in range(3))
            t_v = min(_time(run, cfg_v, x, w_planes, bits, warmup=1,
                            iters=5) for _ in range(3))
            t_m = min(_time(run, cfg_m, x, w_planes, bits, warmup=1,
                            iters=5) for _ in range(3))
            exact = bool(
                (got_m == got_v).all()
                and np.allclose(got_m, want, rtol=1e-5, atol=1e-4)
            )
            yield {
                "M": m, "N": n, "K": k, "bits": bits,
                "plane_pairs": bits * bits,
                "dense_f32_us": round(t_dense, 1),
                "vpu_k_us": round(t_v, 1),
                "mxu_k_us": round(t_m, 1),
                "mxu_speedup_vs_vpu": round(t_v / t_m, 2),
                "exact_match": exact,
            }


def overlap_rows(small: bool = False):
    """overlap_collective gate: ring reduce-scatter == sequential psum ==
    single device on the sharded "k" layout (the decode serving layout),
    bit-identical for both k-bit families.  Split from ``decode_rows`` so
    the single-device decode latency sweep can run WITHOUT the virtual
    multi-device platform split (which divides the host thread pool and
    distorts single-device timings); this family needs the devices and
    runs alongside the other shard benches.  Like shard_rows, a smoke run
    without devices emits an explicit failing row instead of silently
    going vacuously green."""
    from repro.core import quant
    from repro.kernels import dispatch
    from repro.kernels.dispatch import GemmConfig

    n, k = (64, 512) if small else (1024, 4096)
    bits_sweep = (4, 8) if small else (2, 4, 8)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)

    def run(cfg, x, w_planes, bits):
        return dispatch.quant_gemm(x, w_planes, k_true=k, config=cfg,
                                   w_bits=bits, a_bits=bits)

    ndev = len(jax.devices())
    if ndev < 2:
        if small:
            yield {
                "backend": "shard-*-k8/overlap", "ways": 0, "devices": ndev,
                "error": "overlap gate needs >= 2 devices (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)",
                "exact_match": False,
            }
        return
    bits = max(bits_sweep)
    w_planes = bitpack.pack_planes(quant.weight_codes(w.T, bits), bits)
    m = 8
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    for ways in (2, 4):
        if ways > ndev:
            continue
        mesh = make_mesh((ways,), ("model",))
        for fam in ("vpu", "mxu"):
            base = np.asarray(
                run(GemmConfig(backend=f"{fam}-k{bits}"), x, w_planes, bits))
            for overlap in (False, True):
                cfg = GemmConfig(backend=f"shard-{fam}", mesh=mesh,
                                 shard_layout="k",
                                 overlap_collective=overlap)
                got = np.asarray(run(cfg, x, w_planes, bits))
                t_us = _time(run, cfg, x, w_planes, bits, warmup=0, iters=2)
                yield {
                    "backend": f"shard-{fam}-k{bits}/k", "ways": ways,
                    "overlap": overlap, "M": m, "N": n, "K": k,
                    "bits": bits, "devices": ndev,
                    "sharded_us": round(t_us, 1),
                    "exact_match": bool((got == base).all()),
                }

    # packed-operand and grouped/expert shard paths ride the same ring
    # now (PR 9) — gate each entry point at one split
    ways = 4 if ndev >= 4 else 2
    mesh = make_mesh((ways,), ("model",))
    ap = bitpack.pack_sign(jnp.where(x >= 0, 1.0, -1.0))
    wp1 = bitpack.pack_sign(jnp.where(w.T >= 0, 1.0, -1.0))
    base1 = np.asarray(dispatch.packed_gemm(
        ap, wp1, k_true=k, config=GemmConfig(backend="vpu")))

    def run_packed(cfg):
        return dispatch.packed_gemm(ap, wp1, k_true=k, config=cfg)

    for fam in ("vpu", "mxu"):
        for overlap in (False, True):
            cfg = GemmConfig(backend=f"shard-{fam}", mesh=mesh,
                             shard_layout="k", overlap_collective=overlap)
            got = np.asarray(run_packed(cfg))
            t_us = _time(run_packed, cfg, warmup=0, iters=2)
            yield {
                "backend": f"shard-{fam}-packed/k", "ways": ways,
                "overlap": overlap, "M": m, "N": n, "K": k,
                "bits": 1, "devices": ndev,
                "sharded_us": round(t_us, 1),
                "exact_match": bool((got == base1).all()),
            }

    e, t_rows = 2, m
    w_grp = jnp.stack([jnp.where(w.T >= 0, 1.0, -1.0),
                       jnp.where(w.T >= 0, -1.0, 1.0)])
    w_grp_p = jnp.stack([bitpack.pack_sign(w_grp[i]) for i in range(e)])
    gs = jnp.asarray([t_rows - 3, 3], jnp.int32)
    base_g = np.asarray(dispatch.quant_gemm_grouped(
        x, w_grp_p, gs, k_true=k, config=GemmConfig(backend="vpu")))

    def run_grouped(cfg):
        return dispatch.quant_gemm_grouped(x, w_grp_p, gs, k_true=k,
                                           config=cfg)

    for fam in ("vpu", "mxu"):
        for overlap in (False, True):
            cfg = GemmConfig(backend=f"shard-{fam}", mesh=mesh,
                             shard_layout="k", overlap_collective=overlap)
            got = np.asarray(run_grouped(cfg))
            t_us = _time(run_grouped, cfg, warmup=0, iters=2)
            yield {
                "backend": f"shard-{fam}-grouped/k", "ways": ways,
                "overlap": overlap, "E": e, "M": m, "N": n, "K": k,
                "bits": 1, "devices": ndev,
                "sharded_us": round(t_us, 1),
                "exact_match": bool((got == base_g).all()),
            }


def kbit_rows(small: bool = False):
    """Sweep bit width k over a fixed conv-mapped GEMM (jnp/XLA reference
    path, like the fig1-3 rows; the Pallas plane kernel is correctness-
    checked in the equiv table)."""
    from repro.core import quant

    m, k, n = (32, 288, 16) if small else (128, 2304, 64)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    t_dense = _time(_dense, a, w)
    for bits in (1, 2, 4, 8):
        if bits == 1:
            ap = bitpack.pack_sign(a)
            wp = bitpack.pack_sign(w.T)
            t_packed = _time(_xnor_packed, ap, wp, k)
        else:
            ap = bitpack.pack_planes(quant.act_codes(a, bits), bits)
            wp = bitpack.pack_planes(quant.weight_codes(w.T, bits), bits)
            t_packed = _time(_plane_gemm, ap, wp)
        yield {
            "bits": bits, "M": m, "N": n, "K": k,
            "plane_pairs": bits * bits,
            "dense_f32_us": t_dense,
            "packed_gemm_us": t_packed,
            "us_per_plane_pair": t_packed / (bits * bits),
            "packed_bytes_frac_of_f32": bits / 32,
            "speedup_vs_dense": t_dense / t_packed,
        }
