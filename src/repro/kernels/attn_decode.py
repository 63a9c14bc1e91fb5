"""Gather-free fused decode attention: a Pallas flash-decode kernel over
the KV cache's own storage — paged pool blocks consumed THROUGH the
per-slot block tables, or contiguous slabs viewed as an identity-table
pool — with optional quantized (int8 / 1-bit-scaled) KV dequantized per
block tile in VMEM.

Why this kernel exists: the serving hot path used to call
``kv.gather(cache)`` every decode step, every layer — on the paged layout
that materialises a full dense ``(B, cache_len, KVH, Dh)`` K AND V copy
via pool indexing before ``_sdpa`` sweeps the entire static cache length
under a mask.  Attention was the dominant per-step byte mover (the GEMMs
are packed; the KV was not).  This kernel reads each mapped cache block
in place exactly once:

* **grid** ``(B, ceil(bps / spb))`` — batch x split-KV steps, the split
  axis innermost and sequential, so the online-softmax running state
  (m, l, acc) of every kv-head lives in VMEM scratch across the splits
  of one slot and the partial-max/sum combine happens as the splits
  retire; the normalised output is written once at the last split.
* **block tables** — the tables ride in SMEM as scalar prefetch
  (``pltpu.PrefetchScalarGridSpec``).  Each split step covers ``spb``
  table entries of the query's slot: the pool is passed ``spb`` times,
  and operand ``e``'s index map DMAs exactly block ``table[b, j*spb+e]``
  — VMEM holds ``spb`` blocks, never the pool.  Unmapped entries (-1:
  slot shorter than the table, or a retired slot) map to block 0, whose
  repeated index costs no new DMA, and are skipped in the body
  (``pl.when`` — no FLOPs), which is also what keeps junk blocks out of
  the softmax: a skipped block contributes exactly nothing to (m, l,
  acc).
* **pool order** ``(num_blocks, KVH, block_size, Dh)`` — heads outside
  the token rows, so one block carries every head (one DMA per table
  entry) and a head's ``(block_size, Dh)`` tile is a whole-dim Mosaic
  tile, read by a leading-dim index.
* **per-row length masking** — ``pool_pos`` rides along per block; rows
  carry -1 for never-written / truncated / write-masked positions and the
  in-kernel mask reproduces ``nn/attention._mask`` exactly (pos >= 0,
  causal, sliding window), so ragged lengths, speculative rollback and
  retired rows all fall out of the position plane.
* **quantized KV** (``kv_bits``): 8 -> int8 codes + per-(head, dh-group)
  absmax scales; 1 -> sign bytes (8 lanes per uint8) + per-head alpha
  (the XNOR tier, mean-|x| a la BMXNet Eq. 1).  The kernel dequantises
  one (block_size, Dh) tile at a time in VMEM — HBM only ever moves the
  narrow codes, 2-4x (int8) to ~16x (1-bit) fewer KV bytes per step.

The contiguous layout routes through the SAME kernel: a ``(B, L, KVH,
...)`` slab is transposed to a ``(B * L/t, KVH, t, ...)`` pool with an
arange block table (one XLA copy of the slab per call — the paged layout
stores the kernel's order and pays none), where the tile ``t`` is the
autotunable split-KV block.  Queries
are a ``(B, C)`` tile — C == 1 is plain decode, C > 1 is the chunked-
prefill / speculative-verify window (per-row causal masking from the
absolute positions, exactly like the jnp path).

Like every kernel here it interprets on the CPU backend and compiles on
TPU (:func:`repro.kernels.resolve_interpret`).

Numerics vs the gather oracle (``kv.gather`` + ``_sdpa``): scores and
softmax run in fp32 with the same scale/softcap/mask semantics; only the
summation ORDER differs (block-wise online rescale vs one full-length
softmax), so fp-KV results agree to tight fp32 allclose — the CI bench
family gates that, plus greedy token identity on the serve rig.
Quantized-KV rows agree with the oracle reading the SAME quantized pool
(both dequantise identical codes) and carry a measured error bound vs
the fp reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -2.0e38
# fp32 scores and PV sums on the MXU too (not one bf16 pass), matching the
# gather oracle's arithmetic
_FP32 = jax.lax.Precision.HIGHEST

DEFAULT_CTG_TILE = 512  # contiguous split-KV tile (tokens per grid step)
DEFAULT_PGD_SPB = 4  # paged table entries per grid step


# ---------------------------------------------------------------------------
# Quantized KV storage codecs — shared by the cache write paths
# (nn/attention.py quantises on fill) and the in-kernel dequant below.
# ---------------------------------------------------------------------------


def kv_scale_groups(d_head: int) -> int:
    """dh-group count for the int8 absmax scales: 32-channel groups when
    Dh divides, else one group per head (smoke heads are Dh=16)."""
    return d_head // 32 if d_head % 32 == 0 else 1


def kv_quantize(bits: int, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """fp (..., KVH, Dh) -> (codes, scale).

    * bits == 8: int8 codes (..., KVH, Dh), fp32 absmax scales
      (..., KVH, n_groups) per (head, dh-group) — symmetric round-to-
      nearest, absmax/127.
    * bits == 1: sign bytes (..., KVH, Dh/8) uint8 (lane i of byte w is
      element 8w+i, sign(0) = +1) + per-head fp32 alpha (..., KVH, 1) =
      mean |x| over Dh (XNOR-Net Eq. 1 applied to the cache) — one scale
      group, so both tiers' scales are (..., KVH, groups).
    """
    xf = x.astype(jnp.float32)
    dh = x.shape[-1]
    if bits == 8:
        g = kv_scale_groups(dh)
        grp = xf.reshape(*x.shape[:-1], g, dh // g)
        amax = jnp.abs(grp).max(axis=-1)
        scale = jnp.maximum(amax / 127.0, 1e-30)
        codes = jnp.clip(jnp.round(grp / scale[..., None]), -127, 127)
        return codes.reshape(x.shape).astype(jnp.int8), scale
    if bits == 1:
        if dh % 8:
            raise ValueError(f"kv_bits=1 needs d_head % 8 == 0, got {dh}")
        alpha = jnp.abs(xf).mean(axis=-1, keepdims=True)
        bits_ = (xf >= 0).astype(jnp.uint8).reshape(*x.shape[:-1], dh // 8, 8)
        weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
        words = (bits_ * weights).sum(axis=-1, dtype=jnp.uint8)
        return words, alpha
    raise ValueError(f"kv_bits must be 8 or 1, got {bits}")


def kv_dequantize(bits: int, codes: jax.Array, scale: jax.Array,
                  d_head: int, dtype=jnp.float32) -> jax.Array:
    """Invert :func:`kv_quantize`: (codes, scale) -> fp (..., KVH, Dh)."""
    if bits == 8:
        g = kv_scale_groups(d_head)
        grp = codes.astype(jnp.float32).reshape(
            *codes.shape[:-1], g, d_head // g)
        return (grp * scale[..., None]).reshape(
            *codes.shape[:-1], d_head).astype(dtype)
    if bits == 1:
        shifts = jnp.arange(8, dtype=jnp.uint8)
        b = (codes[..., None] >> shifts) & jnp.uint8(1)
        signs = (2.0 * b.astype(jnp.float32) - 1.0).reshape(
            *codes.shape[:-1], d_head)
        return (signs * scale).astype(dtype)
    raise ValueError(f"kv_bits must be 8 or 1, got {bits}")


def kv_code_shapes(bits: int | None, kvh: int, dh: int, dtype):
    """Per-token trailing (shape, dtype) pairs for the K (or V) leaf and
    its scale leaf under a given storage tier; scale entry is None for fp.
    Used by both cache layouts' ``init`` so allocation cannot drift from
    the codec."""
    if bits is None:
        return ((kvh, dh), dtype), None
    if bits == 8:
        return ((kvh, dh), jnp.int8), ((kvh, kv_scale_groups(dh)),
                                       jnp.float32)
    if bits == 1:
        if dh % 8:
            raise ValueError(f"kv_bits=1 needs d_head % 8 == 0, got {dh}")
        return ((kvh, dh // 8), jnp.uint8), ((kvh, 1), jnp.float32)
    raise ValueError(f"kv_bits must be None, 8 or 1, got {bits}")


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------


def _expand_lanes(cols: jax.Array, width: int) -> jax.Array:
    """(rows, n) -> (rows, n * width): column ``i`` repeated over lanes
    ``[i*width, (i+1)*width)``.  Built from static width-1 lane slices and
    selects, which Mosaic lowers; a lane reshape would not."""
    rows, n = cols.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, n * width), 1)
    out = jnp.broadcast_to(cols[:, :1], (rows, n * width))
    for i in range(1, n):
        out = jnp.where(lane >= i * width, cols[:, i:i + 1], out)
    return out


def _dequant_tile(kv_bits, ref, scale_ref, h, dh):
    """Head ``h`` of one pool block -> (bs, Dh) fp32.  The same arithmetic
    as :func:`kv_dequantize`, spelled in whole-tile ops."""
    codes = ref[0, h]  # (bs, Dh-coded)
    if kv_bits is None:
        return codes.astype(jnp.float32)
    scale = scale_ref[0, h]  # (bs, groups) fp32
    if kv_bits == 8:
        vals = codes.astype(jnp.float32)
    else:
        lane = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], dh), 1)
        byte = _expand_lanes(codes.astype(jnp.int32), 8)
        vals = 2.0 * ((byte >> (lane % 8)) & 1).astype(jnp.float32) - 1.0
    return vals * _expand_lanes(scale, dh // scale.shape[1])


def _make_kernel(*, kvh, cg, dh, bps, spb, n_steps, kv_bits, sm_scale, cap,
                 causal, window):
    """Build the flash-decode kernel body for one static configuration.

    Ref order: table (SMEM scalar prefetch), q, q_pos, then per block
    operand ``e < spb``: pool_k, (k_scale), pool_v, (v_scale), pool_pos;
    then out and the m/l/acc scratch.  All compile-time shape knobs arrive
    through the closure — the repo's kernels are traced per jitted
    configuration anyway.
    """
    per = 3 if kv_bits is None else 5

    def kernel(tab_ref, q_ref, qp_ref, *refs):
        blocks = [refs[e * per:(e + 1) * per] for e in range(spb)]
        o_ref, m_ref, l_ref, acc_ref = refs[spb * per:]
        b = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full((kvh, cg, 1), NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros((kvh, cg, 1), jnp.float32)
            acc_ref[...] = jnp.zeros((kvh, cg, dh), jnp.float32)

        qp = qp_ref[0]  # (cg, 1) absolute query positions

        for e, blk_refs in enumerate(blocks):
            if kv_bits is None:
                pk_ref, pv_ref, pp_ref = blk_refs
                ks_ref = vs_ref = None
            else:
                pk_ref, ks_ref, pv_ref, vs_ref, pp_ref = blk_refs
            jj = j * spb + e
            # grid-level skip: unmapped (-1) table entries and the ragged
            # tail of the last split step cost nothing and add nothing
            mapped = (jj < bps) & (tab_ref[b, jnp.minimum(jj, bps - 1)] >= 0)

            @pl.when(mapped)
            def _accumulate(pk_ref=pk_ref, ks_ref=ks_ref, pv_ref=pv_ref,
                            vs_ref=vs_ref, pp_ref=pp_ref):
                kp = pp_ref[0]  # (1, bs); empty / truncated rows carry -1
                valid = kp >= 0
                if causal:
                    valid &= kp <= qp
                if window is not None:
                    valid &= kp > qp - window
                for h in range(kvh):
                    qt = q_ref[0, h].astype(jnp.float32)  # (cg, Dh)
                    kt = _dequant_tile(kv_bits, pk_ref, ks_ref, h, dh)
                    vt = _dequant_tile(kv_bits, pv_ref, vs_ref, h, dh)
                    s = jax.lax.dot_general(
                        qt, kt, (((1,), (1,)), ((), ())), precision=_FP32,
                        preferred_element_type=jnp.float32) * sm_scale
                    if cap is not None:
                        s = cap * jnp.tanh(s / cap)
                    s = jnp.where(valid, s, NEG_INF)
                    m_prev = m_ref[h]
                    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    p = jnp.exp(s - m_new)
                    l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
                    acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                        p, vt, precision=_FP32,
                        preferred_element_type=jnp.float32)
                    m_ref[h] = m_new

        @pl.when(j == n_steps - 1)
        def _finish():
            # combine: the splits' partial (m, l, acc) have already been
            # merged by the running rescale; normalise and emit.  Fully
            # masked rows (l == 0: empty slot) emit zeros — callers only
            # consume active rows (same contract as write_mask).
            o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "kv_bits", "sm_scale", "logit_softcap",
                     "causal", "window", "blocks_per_step", "interpret"))
def flash_decode_paged(
    table: jax.Array,  # (B, bps) int32 block ids, -1 = unmapped
    q: jax.Array,  # (B, C, KVH, G, Dh)
    q_pos: jax.Array,  # (B, C) int32 absolute query positions
    pool_k: jax.Array,  # (nb, KVH, bs, Dh) fp | int8 codes | uint8 signs
    pool_v: jax.Array,
    pool_pos: jax.Array,  # (nb, bs) int32, -1 = empty
    k_scale: jax.Array | None = None,  # (nb, KVH, bs, groups) fp32
    v_scale: jax.Array | None = None,
    *,
    block_size: int,
    kv_bits: int | None = None,
    sm_scale: float,
    logit_softcap: float | None = None,
    causal: bool = True,
    window: int | None = None,
    blocks_per_step: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused paged flash-decode attention: (B, C, KVH, G, Dh) fp32 out.

    Consumes the paged pool directly through ``table`` — no dense gather;
    see the module docstring for grid/mask/quantisation semantics."""
    b, c, kvh, g, dh = q.shape
    nb, bs = pool_pos.shape
    bps = table.shape[1]
    assert bs == block_size, (bs, block_size)
    spb = min(blocks_per_step or DEFAULT_PGD_SPB, bps)
    n_steps = -(-bps // spb)
    cg = c * g

    # head-major query/output tiles: (B, KVH, C*G, Dh), row = c*G + g
    qh = q.transpose(0, 2, 1, 3, 4).reshape(b, kvh, cg, dh)
    qp = jnp.repeat(q_pos.astype(jnp.int32), g, axis=1)[:, :, None]

    def blk_map(e, nd):
        def index(b_, j, tab):
            blk = tab[b_, jnp.minimum(j * spb + e, bps - 1)]
            return (jnp.maximum(blk, 0),) + (0,) * (nd - 1)
        return index

    in_specs = [
        pl.BlockSpec((1, kvh, cg, dh), lambda b_, j, tab: (b_, 0, 0, 0)),
        pl.BlockSpec((1, cg, 1), lambda b_, j, tab: (b_, 0, 0)),
    ]
    operands = [qh, qp]
    leaves = [pool_k, pool_v] if kv_bits is None else \
        [pool_k, k_scale, pool_v, v_scale]
    leaves.append(pool_pos.reshape(nb, 1, bs))
    for e in range(spb):
        for leaf in leaves:
            in_specs.append(pl.BlockSpec((1,) + leaf.shape[1:],
                                         blk_map(e, leaf.ndim)))
            operands.append(leaf)

    kernel = _make_kernel(
        kvh=kvh, cg=cg, dh=dh, bps=bps, spb=spb, n_steps=n_steps,
        kv_bits=kv_bits, sm_scale=sm_scale, cap=logit_softcap,
        causal=causal, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kvh, cg, dh),
                                   lambda b_, j, tab: (b_, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((kvh, cg, 1), jnp.float32),
                pltpu.VMEM((kvh, cg, 1), jnp.float32),
                pltpu.VMEM((kvh, cg, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, cg, dh), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(table.astype(jnp.int32), *operands)
    return out.reshape(b, kvh, c, g, dh).transpose(0, 2, 1, 3, 4)


def flash_decode_contig(
    q: jax.Array,  # (B, C, KVH, G, Dh)
    q_pos: jax.Array,  # (B, C)
    k: jax.Array,  # (B, L, KVH, Dh) fp | codes
    v: jax.Array,
    slot_pos: jax.Array,  # (B, L) int32
    k_scale: jax.Array | None = None,  # (B, L, KVH[, groups])
    v_scale: jax.Array | None = None,
    *,
    kv_bits: int | None = None,
    sm_scale: float,
    logit_softcap: float | None = None,
    causal: bool = True,
    window: int | None = None,
    kv_tile: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Contiguous-slab variant: the per-slot ``(B, L, KVH, ...)`` slab is
    a pool of ``L / t`` tiles per slot under an arange block table (one
    transpose into the kernel's ``(blocks, KVH, t, ...)`` order), after
    which the SAME paged kernel runs.  ``kv_tile`` is the split-KV tile
    (autotuned via :func:`select_attn_tiles`)."""
    b, l = slot_pos.shape
    t = kv_tile or DEFAULT_CTG_TILE
    while l % t:  # tile must divide the slab; fall back toward 1
        t //= 2
    nt = l // t

    def pooled(x):  # (B, L, KVH, d) -> (B*nt, KVH, t, d)
        x = x.reshape(b * nt, t, *x.shape[2:])
        return x if x.ndim == 2 else x.swapaxes(1, 2)

    table = jnp.arange(b * nt, dtype=jnp.int32).reshape(b, nt)
    return flash_decode_paged(
        table, q, q_pos, pooled(k), pooled(v), pooled(slot_pos),
        None if k_scale is None else pooled(k_scale),
        None if v_scale is None else pooled(v_scale),
        block_size=t, kv_bits=kv_bits, sm_scale=sm_scale,
        logit_softcap=logit_softcap, causal=causal, window=window,
        blocks_per_step=1, interpret=interpret)


# ---------------------------------------------------------------------------
# Tile selection + autotune — attention entries ride the SAME persisted
# tile cache as the GEMM backends (kernels/dispatch.py), keyed
# (m=B*C, n=cache_len, kw=Dh, backend="attn-ctg"/"attn-pgd"); only the
# TileConfig's ``bkw`` slot is meaningful (contiguous: split-KV tile
# tokens; paged: table entries per grid step).
# ---------------------------------------------------------------------------


def _attn_key(b: int, c: int, cache_len: int, dh: int, layout: str):
    return (b * c, cache_len, dh, f"attn-{layout}")


def select_attn_tiles(b: int, c: int, cache_len: int, dh: int,
                      layout: str) -> int:
    """Tuned split-KV knob for a decode shape, else the default.
    ``layout``: "ctg" (returns the kv tile) | "pgd" (blocks per step)."""
    from repro.kernels import dispatch

    hit = dispatch._tuned_tiles().get(_attn_key(b, c, cache_len, dh, layout))
    if hit is not None:
        return hit.bkw
    return DEFAULT_CTG_TILE if layout == "ctg" else DEFAULT_PGD_SPB


def _tile_candidates(layout: str, cache_len: int, block_size: int):
    if layout == "ctg":
        return sorted({t for t in (64, 128, 256, 512, 1024)
                       if t <= cache_len and cache_len % t == 0}
                      | {cache_len})
    bps = cache_len // block_size
    return sorted({s for s in (1, 2, 4, 8, 16) if s <= bps} | {bps})


def autotune_attn_tiles(b: int, c: int, cache_len: int, kvh: int, dh: int,
                        layout: str, *, g: int = 1, block_size: int = 16,
                        kv_bits: int | None = None, iters: int = 3,
                        interpret: bool | None = None):
    """Time the fused kernel over the split-KV candidates for one decode
    shape and register the winner in dispatch's tuned-tile cache (the
    committed ``benchmarks/tile_cache.json``; ``REPRO_TILE_CACHE`` seeds
    it back at load).  Returns (winner, per-candidate seconds)."""
    import time

    from repro.kernels import dispatch

    key = jax.random.PRNGKey(0)
    kq, kk, kv_, kp = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, c, kvh, g, dh), jnp.float32)
    q_pos = jnp.broadcast_to(
        jnp.arange(cache_len - c, cache_len, dtype=jnp.int32), (b, c))
    kf = jax.random.normal(kk, (b, cache_len, kvh, dh), jnp.float32)
    vf = jax.random.normal(kv_, (b, cache_len, kvh, dh), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(cache_len, dtype=jnp.int32),
                           (b, cache_len))
    del kp
    sm = dh ** -0.5

    def run_ctg(t):
        return flash_decode_contig(
            q, q_pos, kf, vf, pos, kv_bits=None, sm_scale=sm, kv_tile=t,
            interpret=interpret)

    def run_pgd(s):
        bs = block_size
        nt = cache_len // bs
        table = jnp.arange(b * nt, dtype=jnp.int32).reshape(b, nt)
        return flash_decode_paged(
            table, q, q_pos, kf.reshape(b * nt, bs, kvh, dh).swapaxes(1, 2),
            vf.reshape(b * nt, bs, kvh, dh).swapaxes(1, 2),
            pos.reshape(b * nt, bs),
            block_size=bs, kv_bits=None, sm_scale=sm, blocks_per_step=s,
            interpret=interpret)

    run = run_ctg if layout == "ctg" else run_pgd
    timings = {}
    for cand in _tile_candidates(layout, cache_len, block_size):
        run(cand).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            run(cand).block_until_ready()
        timings[cand] = (time.perf_counter() - t0) / iters
    win = min(timings, key=timings.get)
    dispatch._tuned_tiles()[_attn_key(b, c, cache_len, dh, layout)] = \
        dispatch.TileConfig(bm=b * c, bn=cache_len, bkw=win, chunk_words=win)
    return win, timings
