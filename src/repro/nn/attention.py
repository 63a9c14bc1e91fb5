"""Attention in all the variants the assigned pool needs.

One implementation covers: MHA/GQA/MQA (grouped einsum — KV is never
materialised per-query-head), causal / bidirectional / sliding-window /
alternating local-global, attn-logit softcapping (gemma2), QKV bias (qwen2),
RoPE, cross-attention (whisper), KV-cache decode with per-batch positions
(ring buffer for local layers), and a chunked online-softmax path for long
prefill (32k) where materialising (S, S) scores would blow HBM.

All projections run through ``QCtx.dense`` => they obey the BMXNet
quantization policy like every other GEMM in the framework.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import qlayers
from repro.kernels import attn_decode as attn_kernels
from repro.kernels.attn_decode import (kv_code_shapes, kv_dequantize,
                                       kv_quantize)
from repro.nn.common import QCtx, rope, softcap

Params = dict[str, Any]

NEG_INF = -2.0e38


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    rope_theta: float = 10000.0
    use_rope: bool = True
    qkv_bias: bool = False
    logit_softcap: float | None = None
    window: int | None = None  # sliding window; None = global
    causal: bool = True
    query_scale: float | None = None  # default d_head ** -0.5
    # chunked-path knobs
    full_attn_max_seq: int = 4096
    chunk_q: int = 512
    chunk_kv: int = 1024
    # decode-attention execution (serving): route attn_decode/attn_window
    # through the Pallas flash-decode kernel (kernels/attn_decode.py)
    # instead of gather + _sdpa.  False keeps the gather path — the
    # oracle the fused kernel is CI-gated against (the fused_prologue
    # idiom).  Cross-attention reads always stay on the gather path.
    fused_attn: bool = False
    # KV-cache storage tier: None = fp compute dtype; 8 = int8 codes +
    # per-(head, dh-group) absmax scales; 1 = packed sign bytes + per-head
    # alpha (the XNOR tier).  The KVCache layout carrying the same value
    # quantises on write; gather() dequantises, so the oracle path reads
    # the identical quantized pool.
    kv_bits: int | None = None

    @property
    def groups(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def scale(self) -> float:
        return self.query_scale if self.query_scale is not None else self.d_head**-0.5


def attn_init(key: jax.Array, cfg: AttnConfig, *, dtype=jnp.float32) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    h, kvh, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_model
    return {
        "q": qlayers.dense_init(kq, d, h * dh, bias=cfg.qkv_bias, dtype=dtype),
        "k": qlayers.dense_init(kk, d, kvh * dh, bias=cfg.qkv_bias, dtype=dtype),
        "v": qlayers.dense_init(kv, d, kvh * dh, bias=cfg.qkv_bias, dtype=dtype),
        "o": qlayers.dense_init(ko, h * dh, d, dtype=dtype),
    }


def _project_qkv(params, x, positions, cfg: AttnConfig, ctx: QCtx, path: str):
    b, s, _ = x.shape
    q = ctx.dense(params["q"], x, f"{path}/q").reshape(b, s, cfg.n_heads, cfg.d_head)
    k = ctx.dense(params["k"], x, f"{path}/k").reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = ctx.dense(params["v"], x, f"{path}/v").reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mask(cfg: AttnConfig, q_pos, k_pos):
    """(..., Sq, Sk) bool validity mask from absolute positions."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    m = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if cfg.causal:
        m &= kp <= qp
    if cfg.window is not None:
        m &= kp > qp - cfg.window
    m &= kp >= 0  # empty cache slots carry position -1
    return m


def _sdpa(cfg: AttnConfig, q, k, v, mask):
    """Grouped scaled-dot-product attention with softcap.

    q: (B, Sq, KVH, G, Dh); k, v: (B, Sk, KVH, Dh); mask: (B, Sq, Sk) bool.
    Returns (B, Sq, KVH, G, Dh).
    """
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32
    ) * cfg.scale
    scores = softcap(scores, cfg.logit_softcap)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)


def _sdpa_chunked(cfg: AttnConfig, q, k, v, q_pos, k_pos):
    """Online-softmax attention, O(chunk_q * chunk_kv) score memory.

    Same signature/semantics as _sdpa but mask is derived from positions and
    both sequence axes are processed in chunks (flash-attention recurrence in
    pure jnp; the Pallas variant is a §Perf item).
    """
    b, sq, kvh, g, dh = q.shape
    sk = k.shape[1]
    cq, ck = min(cfg.chunk_q, sq), min(cfg.chunk_kv, sk)
    assert sq % cq == 0, (sq, cq)
    if sk % ck:  # pad KV to a chunk multiple; pad slots masked via pos=-1
        pad = ck - sk % ck
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
        sk += pad
    nq, nk = sq // cq, sk // ck

    qc = q.reshape(b, nq, cq, kvh, g, dh).transpose(1, 0, 2, 3, 4, 5)
    qpc = q_pos.reshape(b, nq, cq).transpose(1, 0, 2)
    kc = k.reshape(b, nk, ck, kvh, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nk, ck, kvh, dh).transpose(1, 0, 2, 3, 4)
    kpc = k_pos.reshape(b, nk, ck).transpose(1, 0, 2)

    def q_block(carry, qb):
        qi, qp = qb

        def kv_block(st, kb):
            m_run, l_run, acc = st
            ki, vi, kp = kb
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qi, ki, preferred_element_type=jnp.float32
            ) * cfg.scale
            s = softcap(s, cfg.logit_softcap)
            valid = _mask(cfg, qp, kp)  # (b, cq, ck)
            s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_run * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vi.dtype), vi
            ).astype(jnp.float32)
            return (m_new, l_new, acc), None

        init = (
            jnp.full((b, kvh, g, cq), NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, g, cq), jnp.float32),
            jnp.zeros((b, kvh, g, cq, dh), jnp.float32),
        )
        (m_f, l_f, acc), _ = jax.lax.scan(kv_block, init, (kc, vc, kpc))
        out = acc / jnp.maximum(l_f, 1e-37)[..., None]
        return carry, out.transpose(0, 3, 1, 2, 4)  # (b, cq, kvh, g, dh)

    _, outs = jax.lax.scan(q_block, None, (qc, qpc))  # (nq, b, cq, kvh, g, dh)
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, kvh, g, dh)


def attn_forward(
    params: Params,
    x: jax.Array,  # (B, S, D)
    positions: jax.Array,  # (B, S)
    cfg: AttnConfig,
    ctx: QCtx,
    path: str,
    *,
    kv: tuple[jax.Array, jax.Array] | None = None,  # cross-attention K/V src
    kv_positions: jax.Array | None = None,
) -> jax.Array:
    """Full-sequence forward (training / prefill)."""
    b, s, _ = x.shape
    if kv is None:
        q, k, v = _project_qkv(params, x, positions, cfg, ctx, path)
        k_pos = positions
    else:
        q = ctx.dense(params["q"], x, f"{path}/q").reshape(
            b, s, cfg.n_heads, cfg.d_head
        )
        if cfg.use_rope:
            q = rope(q, positions, cfg.rope_theta)
        k, v = kv
        k_pos = kv_positions

    qg = q.reshape(b, s, cfg.n_kv_heads, cfg.groups, cfg.d_head)
    if max(s, k.shape[1]) <= cfg.full_attn_max_seq:
        mask = _mask(cfg, positions, k_pos)
        out = _sdpa(cfg, qg, k, v, mask)
    else:
        out = _sdpa_chunked(cfg, qg, k, v, positions, k_pos)
    out = out.reshape(b, s, cfg.n_heads * cfg.d_head).astype(ctx.compute_dtype)
    return ctx.dense(params["o"], out, f"{path}/o")


def cross_kv(
    params: Params, enc: jax.Array, cfg: AttnConfig, ctx: QCtx, path: str
):
    """Project encoder output to K/V once (whisper prefill)."""
    b, t, _ = enc.shape
    k = ctx.dense(params["k"], enc, f"{path}/k").reshape(b, t, cfg.n_kv_heads, cfg.d_head)
    v = ctx.dense(params["v"], enc, f"{path}/v").reshape(b, t, cfg.n_kv_heads, cfg.d_head)
    return k, v


# --------------------------------------------------------------------------
# KV cache (decode) — one KVCache API, two layouts
# --------------------------------------------------------------------------


def insert_rows(big: jax.Array, small: jax.Array, slots: jax.Array) -> jax.Array:
    """Write the G leading rows of ``small`` into batch rows ``slots`` of
    ``big`` (both batch-leading; ``slots``: (G,) int32, traced-safe).  The
    per-slot building block of the continuous-batching scheduler's cache
    insertion (``ContiguousKVCache.insert`` tree-maps this over every
    cache leaf)."""
    for g in range(small.shape[0]):
        big = jax.lax.dynamic_update_slice_in_dim(
            big, small[g:g + 1].astype(big.dtype), slots[g], axis=0
        )
    return big


def zero_rows(x: jax.Array, slot: jax.Array) -> jax.Array:
    """Zero batch row ``slot`` (recurrent-state reset on slot retirement)."""
    return jax.lax.dynamic_update_slice_in_dim(
        x, jnp.zeros((1,) + x.shape[1:], x.dtype), slot, axis=0
    )


@dataclasses.dataclass(frozen=True)
class KVCache:
    """Layout handle for attention KV caches.

    A KVCache instance is a STATIC descriptor (hashable, jit-closure-safe);
    the cache state itself is a plain dict pytree that flows through the
    jitted serving functions.  Both layouts implement the same surface, so
    model code never branches on which layout is live:

    * ``init(b, cfg, cache_len, dtype)``  -> empty cache pytree
    * ``insert(cache, sub, slots)``       -> write a (G,)-batch prefill
      sub-cache into G batch slots (admission)
    * ``reset(cache, slot)``              -> retire one slot (rows become
      invisible to :func:`_mask`)
    * ``fill(cache, k, v, positions, write_mask=None)`` -> store projected
      k/v at absolute positions
    * ``fill_window(cache, k, v, positions, write_mask=None)`` -> same
      contract but positions are per-row windows ``pos_start[b] + [0..C)``
      at ARBITRARY per-row starts (speculative verify / chunked prefill);
      the paged scatter already handles that, the contiguous layout needs
      a one-hot write instead of its arange-assuming prefill path
    * ``truncate(cache, lengths)``        -> per-row rollback: rows of
      slot ``b`` at positions ``>= lengths[b]`` become invisible to
      :func:`_mask` again (speculative decode rejects a proposed suffix)
    * ``gather(cache)``                   -> ``(k, v, pos)`` dense views
      ``(B, L, KVH, Dh) x2 + (B, L)`` that attention consumes

    Layouts: :class:`ContiguousKVCache` (per-slot (B, L, H, Dh) storage —
    the PR 5 scheduler layout) and :class:`PagedKVCache` (shared block
    pool + per-slot int32 block tables — block-granular allocation and
    refcounted prefix sharing; see serve/engine.py).

    Both layouts optionally store K/V quantized (``kv_bits``: 8 = int8
    codes + per-(head, dh-group) absmax scales, 1 = packed sign bytes +
    per-head alpha — kernels/attn_decode.py owns the codec): ``fill`` /
    ``fill_window`` / ``insert`` quantise the projected fp k/v on write
    (scale leaves ride beside the code leaves through the same one-hot /
    scatter machinery), ``gather`` dequantises, and position/visibility
    bookkeeping (``reset``/``truncate``) is tier-agnostic — it only ever
    touches the position plane.  ``attend`` runs the fused flash-decode
    kernel directly on this layout's own storage (no dense gather; codes
    dequantise per block tile in VMEM) — the ``AttnConfig.fused_attn``
    hot path, with gather + ``_sdpa`` as its oracle."""

    def init(self, b: int, cfg: AttnConfig, cache_len: int,
             dtype=jnp.bfloat16) -> Params:
        raise NotImplementedError

    def insert(self, cache: Params, sub: Params, slots: jax.Array) -> Params:
        raise NotImplementedError

    def reset(self, cache: Params, slot: jax.Array) -> Params:
        raise NotImplementedError

    def fill(self, cache: Params, k, v, positions,
             write_mask: jax.Array | None = None) -> Params:
        raise NotImplementedError

    def fill_window(self, cache: Params, k, v, positions,
                    write_mask: jax.Array | None = None) -> Params:
        """Window write at arbitrary per-row start positions.  The paged
        scatter handles that natively; layouts whose ``fill`` assumes
        aligned prefill positions override this."""
        return self.fill(cache, k, v, positions, write_mask)

    def truncate(self, cache: Params, lengths: jax.Array) -> Params:
        """Roll row ``b`` back to ``lengths[b]`` tokens: positions
        ``>= lengths[b]`` become invisible (and rewritable) again.  Rows
        whose content is already shorter are untouched (no-op), so one
        batchwide call serves ragged speculative accept lengths."""
        raise NotImplementedError

    def gather(self, cache: Params):
        raise NotImplementedError

    def attend(self, cache: Params, q, q_pos, cfg: "AttnConfig",
               interpret: bool | None = None):
        """Fused flash-decode attention straight off this layout's own
        storage (``AttnConfig.fused_attn``); q (B, C, KVH, G, Dh), q_pos
        (B, C) -> (B, C, KVH, G, Dh) fp32.  Value-equivalent to
        ``_sdpa(cfg, q, *gather(cache))`` under :func:`_mask`."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class ContiguousKVCache(KVCache):
    """Per-slot contiguous storage: ``k``/``v`` (B, cache_len, KVH, Dh) +
    ``slot_pos`` (B, cache_len) (+ ``k_scale``/``v_scale`` when
    ``kv_bits`` stores codes — base-class docstring).  ``gather`` is free
    for fp (returns the arrays) and a dequant for quantized tiers.
    Local (sliding-window) layers use cache_len == window as a ring."""

    kv_bits: int | None = None

    def _encode(self, k, v) -> Params:
        """Projected fp k/v (B, S, KVH, Dh) -> the storage leaves this
        layout persists for them (codes + scales under ``kv_bits``)."""
        if self.kv_bits is None:
            return {"k": k, "v": v}
        kc, ks = kv_quantize(self.kv_bits, k)
        vc, vs = kv_quantize(self.kv_bits, v)
        return {"k": kc, "k_scale": ks, "v": vc, "v_scale": vs}

    def init(self, b, cfg: AttnConfig, cache_len, dtype=jnp.bfloat16):
        (code, cdt), sc = kv_code_shapes(self.kv_bits, cfg.n_kv_heads,
                                         cfg.d_head, dtype)
        out = {
            "k": jnp.zeros((b, cache_len) + code, cdt),
            "v": jnp.zeros((b, cache_len) + code, cdt),
            "slot_pos": jnp.full((b, cache_len), -1, jnp.int32),
        }
        if sc is not None:
            out["k_scale"] = jnp.zeros((b, cache_len) + sc[0], sc[1])
            out["v_scale"] = jnp.zeros((b, cache_len) + sc[0], sc[1])
        return out

    def insert(self, cache, sub, slots):
        """Batch-row insertion per leaf.  Works on ANY batch-leading cache
        pytree (models tree-map it over attention + recurrent leaves).
        The inserted ``slot_pos`` rows carry -1 beyond the prompt, which
        retires the previous occupant's stale rows.  Quantized tiers
        encode the fp prefill sub-cache's k/v on the way in (the sub-cache
        is always fp contiguous — lm.prefill's scratch layout)."""
        if self.kv_bits is not None and "k_scale" in cache:
            enc = self._encode(sub["k"], sub["v"])
            return {
                name: insert_rows(big, enc.get(name, sub.get(name)), slots)
                for name, big in cache.items()
            }
        return jax.tree.map(
            lambda big, small: insert_rows(big, small, slots), cache, sub
        )

    def reset(self, cache, slot):
        """Retire one batch slot: mark every row of that slot empty
        (``slot_pos = -1``) so :func:`_mask` hides it from future queries.
        K/V bytes are left in place — the next occupant's prefill insertion
        overwrites the whole slot (and carries its own -1 rows past the
        prompt), so stale keys can never become visible again."""
        cache_len = cache["slot_pos"].shape[1]
        slot_pos = jax.lax.dynamic_update_slice(
            cache["slot_pos"], jnp.full((1, cache_len), -1, jnp.int32),
            (slot, 0)
        )
        return {**cache, "slot_pos": slot_pos}

    def fill(self, cache, k, v, positions, write_mask=None):
        """Write to the cache.  k/v: (B, S, KVH, Dh), positions: (B, S).
        Slots are ``pos % cache_len`` (ring for local layers; identity when
        cache_len >= S).  ``write_mask=False`` rows skip the write on the
        S == 1 path; the prefill paths ignore it — storage is slot-
        private, so a junk write from a retired batch row can never leak
        into another request (admission's full-slot ``insert`` overwrite
        is the safety mechanism), the mask is only honored where the
        speculative-decode loop needs idle rows' positions left alone.

        No scatters: scatter onto a model-sharded cache triggers GSPMD
        "involuntary full rematerialization" (the cache gets replicated —
        measured 0.86 s/step of collectives on granite decode_32k).
        Instead:

        * S == 1 (decode, per-batch positions): one-hot select write —
          elementwise, any sharding, SPMD-safe.
        * S > 1 (prefill): positions are the standard arange; the write is
          a dynamic-update-slice (cache_len >= S) or a roll of the last
          cache_len tokens (ring wrap), both SPMD-friendly.  Windows at
          per-row starts go through :meth:`fill_window` instead.
        """
        cache_len = cache["slot_pos"].shape[1]
        s = k.shape[1]
        enc = self._encode(k, v)  # leaf name -> (B, S, ...) storage value
        out = dict(cache)
        if s == 1:
            slots = positions % cache_len  # (B, 1)
            mask = jnp.arange(cache_len)[None, :] == slots  # (B, L)
            if write_mask is not None:
                mask &= write_mask[:, None]
            for name, val in enc.items():
                m = mask.reshape(mask.shape + (1,) * (val.ndim - 2))
                out[name] = jnp.where(m, val.astype(cache[name].dtype),
                                      cache[name])
            out["slot_pos"] = jnp.where(mask, positions, cache["slot_pos"])
            return out

        if s <= cache_len:
            for name, val in enc.items():
                out[name] = jax.lax.dynamic_update_slice(
                    cache[name], val.astype(cache[name].dtype),
                    (0,) * val.ndim)
            out["slot_pos"] = jax.lax.dynamic_update_slice(
                cache["slot_pos"], positions, (0, 0))
            return out

        # ring wrap: keep the last cache_len tokens; token at position p
        # lands in slot p % cache_len, i.e. a cyclic roll by
        # (s - cache_len) % L.
        shift = (s - cache_len) % cache_len
        for name, val in enc.items():
            out[name] = jnp.roll(val[:, s - cache_len:], shift,
                                 axis=1).astype(cache[name].dtype)
        out["slot_pos"] = jnp.roll(positions[:, s - cache_len:], shift, axis=1)
        return out

    def fill_window(self, cache, k, v, positions, write_mask=None):
        """C-token window write at per-row start positions (speculative
        verify, draft restart windows).  One-hot select per window token —
        the same SPMD-safe no-scatter trick as the S == 1 decode path,
        vectorized over C: window token c of row b lands in slot
        ``positions[b, c] % cache_len``.  Within a row the window
        positions are consecutive, so the per-token one-hots never
        collide and the 0/1-coefficient einsum below reproduces a direct
        write bit-exactly (a one-hot sum selects exactly one addend, so
        accumulating in fp32 and casting back to the storage dtype —
        including integer code leaves — is lossless)."""
        cache_len = cache["slot_pos"].shape[1]
        if k.shape[1] == 1:
            return self.fill(cache, k, v, positions, write_mask)
        enc = self._encode(k, v)
        slots = positions % cache_len  # (B, C)
        oh = slots[:, :, None] == jnp.arange(cache_len)[None, None, :]
        if write_mask is not None:
            oh &= write_mask[:, None, None]
        hit = oh.any(axis=1)  # (B, L): does any window token land here?
        ohf = oh.astype(jnp.float32)
        out = dict(cache)
        for name, val in enc.items():
            upd = jnp.einsum("bcl,bc...->bl...", ohf,
                             val.astype(jnp.float32))
            hm = hit.reshape(hit.shape + (1,) * (val.ndim - 2))
            out[name] = jnp.where(hm, upd.astype(cache[name].dtype),
                                  cache[name])
        out["slot_pos"] = jnp.where(
            hit, (oh * positions[:, :, None]).sum(axis=1),
            cache["slot_pos"])
        return out

    def truncate(self, cache, lengths):
        """Rows at positions >= lengths[b] flip to ``slot_pos = -1``:
        invisible to :func:`_mask` and rewritten in place by the next
        window (overwrite-before-read, exactly like slot recycling).  K/V
        bytes stay — same hygiene argument as :meth:`reset`."""
        slot_pos = cache["slot_pos"]
        return {**cache, "slot_pos": jnp.where(
            slot_pos >= lengths[:, None], -1, slot_pos)}

    def gather(self, cache):
        if self.kv_bits is None:
            return cache["k"], cache["v"], cache["slot_pos"]
        dh = cache["k"].shape[-1] * (8 if self.kv_bits == 1 else 1)
        k = kv_dequantize(self.kv_bits, cache["k"], cache["k_scale"], dh)
        v = kv_dequantize(self.kv_bits, cache["v"], cache["v_scale"], dh)
        return k, v, cache["slot_pos"]

    def attend(self, cache, q, q_pos, cfg: AttnConfig, interpret=None):
        b, c = q_pos.shape
        tile = attn_kernels.select_attn_tiles(
            b, c, cache["slot_pos"].shape[1], cfg.d_head, "ctg")
        return attn_kernels.flash_decode_contig(
            q, q_pos, cache["k"], cache["v"], cache["slot_pos"],
            cache.get("k_scale"), cache.get("v_scale"),
            kv_bits=self.kv_bits, sm_scale=cfg.scale,
            logit_softcap=cfg.logit_softcap, causal=cfg.causal,
            window=cfg.window, kv_tile=tile, interpret=interpret)


@dataclasses.dataclass(frozen=True)
class PagedKVCache(KVCache):
    """Block-table paged storage over a shared pool.

    Leaves: ``pool_k``/``pool_v`` (num_blocks, KVH, block_size, Dh) —
    heads outside the token rows, the order the fused kernel DMAs
    (kernels/attn_decode.py) — (quantized tiers store codes here plus
    scale pools ``pool_ks``/``pool_vs`` (num_blocks, KVH, block_size,
    groups) riding the same scatters — base-class docstring),
    ``pool_pos`` (num_blocks, block_size) int32 absolute token positions
    (-1 = empty), ``table`` (B, blocks_per_slot) int32 block ids (-1 =
    unmapped — the whole slot is invisible).  Token at slot-local position
    ``p`` lives in block ``table[b, p // block_size]`` at offset
    ``p % block_size``, so ``gather`` reassembles each slot's tokens in
    position order — the dense view is VALUE-identical to the contiguous
    layout's storage, which is what makes paged serving bit-identical.

    The block table is part of the cache pytree: the host-side allocator
    (serve/engine.BlockAllocator) rewrites table rows and resets freshly
    allocated blocks' ``pool_pos`` at admission; the jitted fill/gather
    below only ever follow the table.  Invariants the allocator maintains:

    * a block is referenced by at most one WRITABLE slot position range;
      refcount > 1 blocks (shared prompt prefixes) are never written —
      chunked prefill starts at the first novel token and decode writes at
      pos >= prompt_len, both past any shared full block;
    * freshly allocated blocks get ``pool_pos = -1`` before the table row
      lands, so a previous occupant's stale keys are invisible;
    * retired slots keep decoding junk in the shape-static step — their
      writes MUST be dropped (``write_mask``), because their freed blocks
      may already belong to another slot.
    """

    block_size: int = 16
    kv_bits: int | None = None

    def _encode(self, k, v) -> Params:
        """Projected fp k/v (B, S, KVH, Dh) -> the pool leaves this layout
        persists for them (codes + scale pools under ``kv_bits``)."""
        if self.kv_bits is None:
            return {"pool_k": k, "pool_v": v}
        kc, ks = kv_quantize(self.kv_bits, k)
        vc, vs = kv_quantize(self.kv_bits, v)
        return {"pool_k": kc, "pool_ks": ks, "pool_v": vc, "pool_vs": vs}

    def _scatter(self, cache, flat, enc, positions):
        """Scatter encoded (B, S, KVH, ...) leaves + positions to flattened
        pool indices ``flat`` ((B*S,); invalid -> nb*bs, mode='drop')."""
        nb, bs = cache["pool_pos"].shape
        n = flat.shape[0]
        blk, off = flat // bs, flat % bs  # invalid -> blk nb: dropped
        out = dict(cache)
        for name, val in enc.items():
            pool = cache[name]  # (nb, KVH, bs, ...)
            rows = val.astype(pool.dtype).reshape(
                (n, pool.shape[1]) + pool.shape[3:])
            out[name] = pool.at[blk, :, off].set(rows, mode="drop")
        out["pool_pos"] = (cache["pool_pos"].reshape(nb * bs)
                           .at[flat].set(positions.reshape(-1), mode="drop")
                           .reshape(nb, bs))
        return out

    def _flat(self, cache, positions, write_mask):
        """(B, S) flattened pool indices; invalid/masked writes -> index
        num_blocks*block_size, dropped by scatter mode='drop'."""
        bs = self.block_size
        table = cache["table"]
        bps = table.shape[1]
        nb = cache["pool_pos"].shape[0]
        blk_idx = jnp.clip(positions // bs, 0, bps - 1)  # (B, S)
        blk = jnp.take_along_axis(table, blk_idx, axis=1)  # (B, S)
        valid = (positions >= 0) & (positions < bps * bs) & (blk >= 0)
        if write_mask is not None:
            valid &= write_mask[:, None]
        flat = jnp.clip(blk, 0) * bs + positions % bs
        return jnp.where(valid, flat, nb * bs)

    def init(self, b, cfg: AttnConfig, cache_len, dtype=jnp.bfloat16):
        bs = self.block_size
        if cache_len % bs:
            raise ValueError(
                f"cache_len {cache_len} not a multiple of kv block size {bs}")
        bps = cache_len // bs
        nb = b * bps  # the contiguous layout's exact footprint
        (code, cdt), sc = kv_code_shapes(self.kv_bits, cfg.n_kv_heads,
                                         cfg.d_head, dtype)
        def pool(shape, dt):  # per-token (KVH, d) -> (nb, KVH, bs, d)
            return jnp.zeros((nb, shape[0], bs) + shape[1:], dt)

        out = {
            "pool_k": pool(code, cdt),
            "pool_v": pool(code, cdt),
            "pool_pos": jnp.full((nb, bs), -1, jnp.int32),
            "table": jnp.full((b, bps), -1, jnp.int32),
        }
        if sc is not None:
            out["pool_ks"] = pool(*sc)
            out["pool_vs"] = pool(*sc)
        return out

    def insert(self, cache, sub, slots):
        """Write a (G, L, ...) CONTIGUOUS prefill sub-cache into the G
        slots' mapped blocks (positions from ``sub['slot_pos']``; -1 rows
        are dropped — freshly allocated blocks were already pos-reset by
        the allocator, which replaces the contiguous layout's full-slot
        overwrite invariant)."""
        pos = sub["slot_pos"]  # (G, L)
        table_rows = cache["table"][slots]  # (G, bps)
        flat = self._flat({**cache, "table": table_rows}, pos, None)
        return self._scatter(cache, flat.reshape(-1),
                             self._encode(sub["k"], sub["v"]), pos)

    def reset(self, cache, slot):
        """Retire one slot: unmap its table row (-1) so ``gather`` masks
        the whole slot.  Block bookkeeping (refcount decrement, free-list
        return) is the HOST allocator's job — pool bytes are untouched, so
        a block shared with a live slot keeps serving its holder."""
        bps = cache["table"].shape[1]
        table = jax.lax.dynamic_update_slice(
            cache["table"], jnp.full((1, bps), -1, jnp.int32), (slot, 0)
        )
        return {**cache, "table": table}

    def fill(self, cache, k, v, positions, write_mask=None):
        """Scatter k/v/pos through the block table.  Distinct (row, pos)
        pairs always hit distinct pool entries (the allocator never maps a
        writable position range of two slots onto one block), so the
        scatter is deterministic; ``write_mask=False`` rows (retired or
        still-prefilling slots decoding junk) are dropped entirely."""
        flat = self._flat(cache, positions, write_mask).reshape(-1)
        return self._scatter(cache, flat, self._encode(k, v), positions)

    def truncate(self, cache, lengths):
        """Rollback through the table: every mapped pool row of slot ``b``
        holding a position >= ``lengths[b]`` flips to ``pool_pos = -1`` —
        invisible to :func:`_mask` and rewritable by the next window
        (the speculative verify overwrites the rolled-back range before
        reading it, exactly as decode overwrites a fresh block).

        Safe under sharing: a shared-prefix block's positions are all
        ``< prompt_len <= lengths[b]`` for every holder, so its scattered
        values are unchanged (holders write back identical bytes) — only
        the truncating slot's PRIVATE tail blocks actually flip.  Block
        *ownership* is untouched; the host allocator keeps its refcounts
        (the engine maps a slot's full table at admission, so rollback is
        a visibility change, not a deallocation — tail blocks drain back
        to the allocator at retirement via ``BlockAllocator.trim``)."""
        table = cache["table"]  # (B, bps)
        b, bps = table.shape
        bs = self.block_size
        nb = cache["pool_pos"].shape[0]
        safe = jnp.clip(table, 0)
        pos = cache["pool_pos"][safe]  # (B, bps, bs)
        newpos = jnp.where(pos >= lengths[:, None, None], -1, pos)
        # scatter back through the table; unmapped rows (-1) -> index
        # nb*bs, dropped
        blk = jnp.where(table >= 0, safe, nb)[:, :, None]
        flat = (blk * bs + jnp.arange(bs)[None, None, :]).reshape(-1)
        pool_pos = (cache["pool_pos"].reshape(nb * bs)
                    .at[flat].set(newpos.reshape(-1), mode="drop")
                    .reshape(nb, bs))
        return {**cache, "pool_pos": pool_pos}

    def gather(self, cache):
        """Dense (B, L, KVH, Dh) views via the table — position order, so
        the result matches the contiguous layout's storage bit-for-bit.
        Unmapped table entries (-1) read block 0 but report pos -1, which
        :func:`_mask` hides."""
        table = cache["table"]  # (B, bps)
        b, bps = table.shape
        bs = self.block_size
        safe = jnp.clip(table, 0)

        def rows(name):  # (nb, KVH, bs, d) -> (B, bps, bs, KVH, d)
            return cache[name][safe].swapaxes(2, 3)

        k = rows("pool_k")
        v = rows("pool_v")
        if self.kv_bits is not None:
            dh_fp = k.shape[-1] * (8 if self.kv_bits == 1 else 1)
            k = kv_dequantize(self.kv_bits, k, rows("pool_ks"), dh_fp)
            v = kv_dequantize(self.kv_bits, v, rows("pool_vs"), dh_fp)
        pos = jnp.where(table[:, :, None] >= 0, cache["pool_pos"][safe], -1)
        kvh, dh = k.shape[-2:]
        return (k.reshape(b, bps * bs, kvh, dh),
                v.reshape(b, bps * bs, kvh, dh),
                pos.reshape(b, bps * bs))

    def attend(self, cache, q, q_pos, cfg: AttnConfig, interpret=None):
        b, c = q_pos.shape
        cache_len = cache["table"].shape[1] * self.block_size
        spb = attn_kernels.select_attn_tiles(b, c, cache_len, cfg.d_head,
                                             "pgd")
        return attn_kernels.flash_decode_paged(
            cache["table"], q, q_pos, cache["pool_k"], cache["pool_v"],
            cache["pool_pos"], cache.get("pool_ks"), cache.get("pool_vs"),
            block_size=self.block_size, kv_bits=self.kv_bits,
            sm_scale=cfg.scale, logit_softcap=cfg.logit_softcap,
            causal=cfg.causal, window=cfg.window,
            blocks_per_step=min(spb, cache["table"].shape[1]),
            interpret=interpret)


CONTIGUOUS = ContiguousKVCache()


def attn_decode(
    params: Params,
    x: jax.Array,  # (B, 1, D)
    pos: jax.Array,  # (B,) int32 — position of this token
    cache: Params,
    cfg: AttnConfig,
    ctx: QCtx,
    path: str,
    *,
    cross: bool = False,
    kv: KVCache | None = None,
    write_mask: jax.Array | None = None,
) -> tuple[jax.Array, Params]:
    """One decode step against the cache; returns (out (B,1,D), new cache).

    ``cross=True`` reads a static cross-attention cache (no write, no mask
    beyond slot validity).  ``kv`` selects the cache layout (default
    contiguous); ``write_mask`` (B,) drops inactive rows' cache writes on
    layouts where block recycling makes junk writes unsafe (paged)."""
    if kv is None:
        kv = CONTIGUOUS
    b = x.shape[0]
    positions = pos[:, None]
    if cross:
        q = ctx.dense(params["q"], x, f"{path}/q").reshape(
            b, 1, cfg.n_heads, cfg.d_head
        )
        if cfg.use_rope:
            q = rope(q, positions, cfg.rope_theta)
    else:
        q, k_new, v_new = _project_qkv(params, x, positions, cfg, ctx, path)
        with jax.named_scope("kv_write"):
            cache = kv.fill(cache, k_new, v_new, positions, write_mask)

    qg = q.reshape(b, 1, cfg.n_kv_heads, cfg.groups, cfg.d_head)
    with jax.named_scope("attend"):
        if cfg.fused_attn and not cross:
            out = kv.attend(cache, qg, positions, cfg,
                            interpret=ctx.gemm_config._interpret)
        else:
            k, v, k_pos = kv.gather(cache)
            mask = _mask(cfg, positions, k_pos)  # (B, 1, L)
            out = _sdpa(cfg, qg, k, v, mask)
    out = out.reshape(b, 1, cfg.n_heads * cfg.d_head).astype(ctx.compute_dtype)
    return ctx.dense(params["o"], out, f"{path}/o"), cache


def attn_window(
    params: Params,
    x: jax.Array,  # (B, C, D)
    positions: jax.Array,  # (B, C) absolute positions of these tokens
    cache: Params,
    cfg: AttnConfig,
    ctx: QCtx,
    path: str,
    kv: KVCache,
    *,
    write_mask: jax.Array | None = None,
) -> tuple[jax.Array, Params]:
    """A C-token window against the cache: project, store the window's
    k/v, then attend over the FULL gathered cache (the window included —
    causality comes from the position mask).  ``attn_decode`` is the C==1
    special case; chunked prefill is the general one, where each chunk of
    a long prompt attends to everything already cached (earlier chunks,
    shared prefix blocks) plus itself, so one jitted shape serves decode,
    chunked prefill, shared-prefix suffix prefill, and the speculative
    verify window (per-row starts — hence ``fill_window``)."""
    b, c, _ = x.shape
    q, k_new, v_new = _project_qkv(params, x, positions, cfg, ctx, path)
    with jax.named_scope("kv_write"):
        cache = kv.fill_window(cache, k_new, v_new, positions, write_mask)
    qg = q.reshape(b, c, cfg.n_kv_heads, cfg.groups, cfg.d_head)
    with jax.named_scope("attend"):
        if cfg.fused_attn:
            out = kv.attend(cache, qg, positions, cfg,
                            interpret=ctx.gemm_config._interpret)
        else:
            k, v, k_pos = kv.gather(cache)
            mask = _mask(cfg, positions, k_pos)  # (B, C, L)
            out = _sdpa(cfg, qg, k, v, mask)
    out = out.reshape(b, c, cfg.n_heads * cfg.d_head).astype(ctx.compute_dtype)
    return ctx.dense(params["o"], out, f"{path}/o"), cache
