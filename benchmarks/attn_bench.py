"""Fused decode-attention benchmark: the Pallas flash-decode kernel
(kernels/attn_decode.py) vs the dense gather + masked-sdpa oracle, over
both KV layouts and the quantized KV storage tiers.

Rows:

* ``equivalence`` — fused kernel output vs ``_sdpa`` over the SAME
  storage's :meth:`gather` view (the oracle dequantizes the same codes
  the kernel reads), at ragged per-row lengths crossing block
  boundaries, for layout in {contiguous, paged} x kv_bits in
  {fp, int8, 1bit}.  ``max_err`` must sit at fp-accumulation level
  (<= 2e-5) for every tier — the fused path reorders the softmax
  accumulation but reads identical KV values.  Quantized rows ALSO
  report ``quant_err`` — the gathered dequantized cache vs the fp
  values that were written — against per-tier bounds (int8 per-group
  absmax: tight; 1-bit sign + per-head alpha: the XNOR tier, loose by
  construction).  Both checks fold into the CI-gated ``exact_match``.
* ``latency`` — per-decode-step wall time, fused vs gather, at the
  serve shapes (cache_len 2048, decode M in {1, 8, 32}), both layouts,
  kv_bits sweep.  The fused path reads the pool in place through the
  block table (split-KV grid, tuned via select_attn_tiles); the gather
  baseline materializes the dense (B, L) view every step — on paged
  storage that is a real per-step copy, on contiguous it is free, which
  is why the contiguous win comes only from the masked-sdpa's wasted
  NEG_INF lanes.  ``speedup`` > 1 means fused wins; rows carry no
  ``exact_match`` (timing, not correctness).
* ``pool-bytes`` — KV-cache bytes per cached token per layer for the
  fp32 / int8 / 1-bit storage tiers (codes + scale planes, from
  kv_code_shapes), with the reduction factor vs fp32.  The int8/1-bit
  rows gate ``exact_match`` on the bytes actually shrinking — paired
  with their ``equivalence`` error-bound rows this is the ISSUE's
  "pool-bytes reduction with its error-bound row passing" criterion.

Timing notes: interpret-mode Pallas on CPU; the fused kernel's win
grows with cache_len (the gather path's dense materialization + full
masked score matrix scale with L, the split-KV grid streams it).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn import attention as A
from repro.kernels import attn_decode as AK

_TIERS = (None, 8, 1)
# int8 gates MAX abs err (per-group absmax keeps it ~scale/254); 1-bit
# gates MEAN abs err — sign + per-head alpha has per-element error up to
# ~max|x|, but its mean is E|x - alpha*sign(x)| ~ 0.6 at unit variance
_QUANT_ERR_BOUND = {8: 0.05, 1: 0.8}


def _mk_kv(layout: str, kv_bits, block_size: int):
    if layout == "pgd":
        return A.PagedKVCache(block_size=block_size, kv_bits=kv_bits)
    return A.ContiguousKVCache(kv_bits=kv_bits)


def _fill(kv, cfg, b, cache_len, lens, key, layout):
    """Build a cache with per-row ragged fills (fp values returned too)."""
    cache = kv.init(b, cfg, cache_len, jnp.float32)
    if layout == "pgd":
        bps = cache["table"].shape[1]
        cache["table"] = jnp.arange(b * bps, dtype=jnp.int32).reshape(b, bps)
    kvh, dh = cfg.n_kv_heads, cfg.d_head
    fp_k = np.zeros((b, cache_len, kvh, dh), np.float32)
    fp_v = np.zeros((b, cache_len, kvh, dh), np.float32)
    # one masked fill_window pass per DISTINCT length: the paged pool is
    # SHARED across slots, so ragged per-row writes go through write_mask
    # (rows of other lengths masked off), never by slicing cache leaves
    for ln in sorted(set(lens)):
        ks = jax.random.normal(jax.random.fold_in(key, ln), (b, ln, kvh, dh))
        vs = jax.random.normal(jax.random.fold_in(key, 1000 + ln),
                               (b, ln, kvh, dh))
        wm = np.asarray([x == ln for x in lens])
        for i in np.flatnonzero(wm):
            fp_k[i, :ln], fp_v[i, :ln] = np.asarray(ks[i]), np.asarray(vs[i])
        pos = jnp.broadcast_to(jnp.arange(ln, dtype=jnp.int32), (b, ln))
        cache = kv.fill_window(cache, ks, vs, pos, jnp.asarray(wm))
    return cache, fp_k, fp_v


def _dense_cache(kv, cfg, b, cache_len, key, layout, block_size):
    """A fully-populated cache straight through the layout's codec (the
    latency rows don't exercise the write path, so skip the one-hot
    fills and lay the encoded leaves out directly)."""
    kvh, dh = cfg.n_kv_heads, cfg.d_head
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (b, cache_len, kvh, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (b, cache_len, kvh, dh))
    enc = kv._encode(k, v)
    pos = jnp.broadcast_to(jnp.arange(cache_len, dtype=jnp.int32),
                           (b, cache_len))
    if layout == "ctg":
        return {**enc, "slot_pos": pos}
    bps = cache_len // block_size
    # pool order (blocks, KVH, block_size, ...): heads outside the rows
    cache = {n: x.reshape((b * bps, block_size) + x.shape[2:]).swapaxes(1, 2)
             for n, x in enc.items()}
    cache["pool_pos"] = pos.reshape(b * bps, block_size)
    cache["table"] = jnp.arange(b * bps, dtype=jnp.int32).reshape(b, bps)
    return cache


def _oracle(cfg, kv, cache, qg, q_pos):
    """The gather + masked-sdpa reference over the same storage."""
    k, v, spos = kv.gather(cache)
    return A._sdpa(cfg, qg, k, v, A._mask(cfg, q_pos, spos))


def _bench(fn, *args, iters=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def rows(small: bool = False):
    kvh, g, dh = 2, 2, 16
    cfg = A.AttnConfig(d_model=kvh * g * dh, n_heads=kvh * g,
                       n_kv_heads=kvh, d_head=dh)
    key = jax.random.PRNGKey(0)

    # -- equivalence + quantization error bounds (ragged lengths crossing
    # block boundaries; oracle gathers the SAME quantized storage) --
    eq_len = 64 if small else 256
    bs = 16
    b = 4
    lens = [eq_len, eq_len - bs - 3, bs + 1, 2]
    for layout in ("ctg", "pgd"):
        for bits in _TIERS:
            kv = _mk_kv(layout, bits, bs)
            cache, fp_k, fp_v = _fill(kv, cfg, b, eq_len, lens, key, layout)
            q = jax.random.normal(jax.random.fold_in(key, 7),
                                  (b, 1, kvh, g, dh))
            q_pos = jnp.asarray([[ln] for ln in lens], jnp.int32)
            fused = kv.attend(cache, q, q_pos, cfg)
            ref = _oracle(cfg, kv, cache, q, q_pos)
            max_err = float(jnp.max(jnp.abs(fused - ref)))
            row = {
                "mode": "equivalence", "layout": layout,
                "kv_bits": bits or "fp", "batch": b, "cache_len": eq_len,
                "max_err": f"{max_err:.2e}",
            }
            ok = max_err <= 2e-5
            if bits is not None:
                dk, dv, dpos = kv.gather(cache)
                filled = np.asarray(dpos) >= 0  # (B, L)
                ek = np.abs(np.asarray(dk) - fp_k)[filled]
                ev = np.abs(np.asarray(dv) - fp_v)[filled]
                red = np.max if bits == 8 else np.mean
                qerr = max(float(red(ek)), float(red(ev)))
                row["quant_err"] = f"{qerr:.3f}"
                row["quant_err_bound"] = _QUANT_ERR_BOUND[bits]
                ok = ok and qerr <= _QUANT_ERR_BOUND[bits]
            row["exact_match"] = ok
            yield row

    # -- latency: fused vs gather per decode step at the serve shapes.
    # cache_len stays 2048 even under --smoke: the fused win scales with
    # L (that IS the measurement), only the decode-M sweep shrinks --
    L = 2048
    pbs = 256
    for layout in ("ctg", "pgd"):
        for m in (1, 8) if small else (1, 8, 32):
            for bits in _TIERS:
                kv = _mk_kv(layout, bits, pbs)
                cache = _dense_cache(kv, cfg, m, L, key, layout, pbs)
                q = jax.random.normal(jax.random.fold_in(key, 9),
                                      (m, 1, kvh, g, dh))
                q_pos = jnp.full((m, 1), L - 1, jnp.int32)

                fused = jax.jit(lambda c, q, p: kv.attend(c, q, p, cfg))
                gather = jax.jit(lambda c, q, p: _oracle(cfg, kv, c, q, p))
                t_f = _bench(fused, cache, q, q_pos)
                t_g = _bench(gather, cache, q, q_pos)
                yield {
                    "mode": "latency", "layout": layout,
                    "kv_bits": bits or "fp", "m": m, "cache_len": L,
                    "block_size": pbs if layout == "pgd" else "",
                    "fused_us": round(t_f, 1), "gather_us": round(t_g, 1),
                    "speedup": round(t_g / t_f, 2),
                }

    # -- pool-bytes: storage footprint per cached token per layer --
    fp_bytes = None
    for bits in _TIERS:
        (code, cdt), sc = AK.kv_code_shapes(bits, kvh, dh, jnp.float32)
        per_tok = 2 * (int(np.prod(code)) * jnp.dtype(cdt).itemsize
                       + (int(np.prod(sc[0])) * jnp.dtype(sc[1]).itemsize
                          if sc is not None else 0))
        if bits is None:
            fp_bytes = per_tok
        yield {
            "mode": "pool-bytes", "kv_bits": bits or "fp",
            "kv_heads": kvh, "d_head": dh,
            "bytes_per_token": per_tok,
            "reduction_vs_fp": round(fp_bytes / per_tok, 2),
            **({"exact_match": per_tok < fp_bytes} if bits else {}),
        }
