"""Unified decoder-only LM covering the dense / MoE / hybrid / SSM / VLM
members of the assigned pool.

A model is a stack of blocks; block ``i`` gets a *mixer* (attn, local_attn,
rglru, rwkv6) and an *ffn* (mlp, moe, rwkv_cmix) from cyclic patterns —
which is exactly how the real architectures are specified (gemma2
alternates local/global, recurrentgemma cycles (rglru, rglru, local_attn),
deepseek-moe is dense-FFN for the first layer then MoE, ...).

Every GEMM goes through QCtx.dense, so one `--quant` flag turns any of
these architectures into its BMXNet-binarized variant.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import qlayers
from repro.nn import attention as attn_lib
from repro.nn import mlp as mlp_lib
from repro.nn import rglru as rglru_lib
from repro.nn import rwkv6 as rwkv_lib
from repro.nn.common import QCtx, embed_init, norm_apply, norm_init, softcap

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    vocab_size: int
    mixer_pattern: tuple[str, ...] = ("attn",)
    ffn_pattern: tuple[str, ...] = ("mlp",)
    attn: attn_lib.AttnConfig | None = None
    local_attn: attn_lib.AttnConfig | None = None
    rglru: rglru_lib.RGLRUConfig | None = None
    rwkv: rwkv_lib.RWKV6Config | None = None
    mlp: mlp_lib.MLPConfig | None = None
    moe: mlp_lib.MoEConfig | None = None
    first_dense_layers: int = 0  # deepseek-moe: dense FFN for first layer(s)
    first_dense_mlp: mlp_lib.MLPConfig | None = None
    norm: str = "rmsnorm"
    post_norm: bool = False  # gemma2 post-sublayer norms
    embed_norm: bool = False  # rwkv ln0
    embed_scale: bool = False  # gemma family: x *= sqrt(d)
    tie_embeddings: bool = False
    logit_softcap: float | None = None
    # pad the vocab so embedding/lm_head/logits shard over the model axis
    # (granite 49155, internvl 151655: unpadded => replicated fp32 logits,
    # measured 117 GB/device on internvl train_4k).  0 = no padding.
    vocab_pad_to: int = 0
    max_seq: int = 0  # 0 = rope-only (no learned positions)
    # VLM (stub frontend per assignment: precomputed patch embeddings)
    vision_prefix: int = 0
    d_vision: int = 0

    @property
    def padded_vocab(self) -> int:
        if self.vocab_pad_to:
            m = self.vocab_pad_to
            return (self.vocab_size + m - 1) // m * m
        return self.vocab_size

    def mixer_kind(self, i: int) -> str:
        return self.mixer_pattern[i % len(self.mixer_pattern)]

    def ffn_kind(self, i: int) -> str:
        k = self.ffn_pattern[i % len(self.ffn_pattern)]
        if k == "moe" and i < self.first_dense_layers:
            return "dense_first"
        return k


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def init(key: jax.Array, cfg: LMConfig, dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, cfg.n_layers + 3)
    p: Params = {
        "embed": embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dtype)
    }
    if cfg.embed_norm:
        p["embed_ln"] = norm_init(cfg.norm, cfg.d_model)
    if cfg.vision_prefix:
        p["frontend_proj"] = qlayers.dense_init(
            keys[1], cfg.d_vision, cfg.d_model, dtype=dtype
        )
    layers = []
    for i in range(cfg.n_layers):
        layers.append(_block_init(keys[i + 2], i, cfg, dtype))
    p["layers"] = layers
    p["final_norm"] = norm_init(cfg.norm, cfg.d_model)
    if not cfg.tie_embeddings:
        p["lm_head"] = qlayers.dense_init(
            keys[-1], cfg.d_model, cfg.padded_vocab, dtype=dtype
        )
    return p


def _block_init(key, i: int, cfg: LMConfig, dtype) -> Params:
    km, kf = jax.random.split(key)
    mixer = cfg.mixer_kind(i)
    ffn = cfg.ffn_kind(i)
    blk: Params = {"pre_norm": norm_init(cfg.norm, cfg.d_model)}
    if mixer == "attn":
        blk["attn"] = attn_lib.attn_init(km, cfg.attn, dtype=dtype)
    elif mixer == "local_attn":
        blk["attn"] = attn_lib.attn_init(km, cfg.local_attn, dtype=dtype)
    elif mixer == "rglru":
        blk["rglru"] = rglru_lib.rglru_init(km, cfg.rglru, dtype=dtype)
    elif mixer == "rwkv6":
        blk["tmix"] = rwkv_lib.timemix_init(km, cfg.rwkv, dtype=dtype)
    else:
        raise ValueError(mixer)
    if cfg.post_norm:
        blk["post_mixer_norm"] = norm_init(cfg.norm, cfg.d_model)
        blk["post_ffn_norm"] = norm_init(cfg.norm, cfg.d_model)
    blk["pre_ffn_norm"] = norm_init(cfg.norm, cfg.d_model)
    if ffn == "mlp":
        blk["mlp"] = mlp_lib.mlp_init(kf, cfg.mlp, dtype=dtype)
    elif ffn == "dense_first":
        blk["mlp"] = mlp_lib.mlp_init(kf, cfg.first_dense_mlp, dtype=dtype)
    elif ffn == "moe":
        blk["moe"] = mlp_lib.moe_init(kf, cfg.moe, dtype=dtype)
    elif ffn == "rwkv_cmix":
        blk["cmix"] = rwkv_lib.chanmix_init(kf, cfg.rwkv, dtype=dtype)
    else:
        raise ValueError(ffn)
    return blk


# --------------------------------------------------------------------------
# forward (training / prefill)
# --------------------------------------------------------------------------


def _embed(params, cfg: LMConfig, ctx: QCtx, tokens, vision_embeds):
    x = params["embed"]["table"].astype(ctx.compute_dtype)[tokens]
    if cfg.vision_prefix:
        vis = ctx.dense(
            params["frontend_proj"],
            vision_embeds.astype(ctx.compute_dtype),
            "frontend_proj",
        )
        x = jnp.concatenate([vis, x], axis=1)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, ctx.compute_dtype)
    if cfg.embed_norm:
        x = norm_apply(cfg.norm, params["embed_ln"], x)
    return x


def _mixer_forward(blk, i, x, positions, cfg: LMConfig, ctx, path):
    kind = cfg.mixer_kind(i)
    if kind in ("attn", "local_attn"):
        acfg = cfg.attn if kind == "attn" else cfg.local_attn
        return attn_lib.attn_forward(blk["attn"], x, positions, acfg, ctx,
                                     f"{path}/attn")
    if kind == "rglru":
        return rglru_lib.rglru_forward(blk["rglru"], x, cfg.rglru, ctx,
                                       f"{path}/rglru")
    if kind == "rwkv6":
        return rwkv_lib.timemix_forward(blk["tmix"], x, cfg.rwkv, ctx,
                                        f"{path}/tmix")
    raise ValueError(kind)


def _ffn_forward(blk, i, x, cfg: LMConfig, ctx, path):
    kind = cfg.ffn_kind(i)
    if kind == "mlp":
        return mlp_lib.mlp_apply(blk["mlp"], x, cfg.mlp, ctx, f"{path}/mlp"), 0.0
    if kind == "dense_first":
        return (
            mlp_lib.mlp_apply(blk["mlp"], x, cfg.first_dense_mlp, ctx,
                              f"{path}/mlp"),
            0.0,
        )
    if kind == "moe":
        return mlp_lib.moe_apply(blk["moe"], x, cfg.moe, ctx, f"{path}/moe")
    if kind == "rwkv_cmix":
        return (
            rwkv_lib.chanmix_forward(blk["cmix"], x, cfg.rwkv, ctx,
                                     f"{path}/cmix"),
            0.0,
        )
    raise ValueError(kind)


def block_forward(blk, i, x, positions, cfg: LMConfig, ctx: QCtx):
    path = f"layers/{i}"
    h = norm_apply(cfg.norm, blk["pre_norm"], x)
    h = _mixer_forward(blk, i, h, positions, cfg, ctx, path)
    if cfg.post_norm:
        h = norm_apply(cfg.norm, blk["post_mixer_norm"], h)
    x = x + h
    h = norm_apply(cfg.norm, blk["pre_ffn_norm"], x)
    h, aux = _ffn_forward(blk, i, h, cfg, ctx, path)
    if cfg.post_norm:
        h = norm_apply(cfg.norm, blk["post_ffn_norm"], h)
    return x + h, aux


def _logits(params, cfg: LMConfig, ctx: QCtx, x):
    x = norm_apply(cfg.norm, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "bsd,vd->bsv", x, params["embed"]["table"].astype(x.dtype)
        )
    else:
        logits = ctx.dense(params["lm_head"], x, "lm_head")
    logits = softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    return logits


def _cycle_len(cfg: LMConfig) -> int:
    import math
    return math.lcm(len(cfg.mixer_pattern), len(cfg.ffn_pattern))


def forward(
    params: Params,
    cfg: LMConfig,
    ctx: QCtx,
    tokens: jax.Array,  # (B, S_text)
    vision_embeds: jax.Array | None = None,  # (B, P, d_vision)
    remat: bool = False,
    scan_blocks: bool = False,
    seq_parallel: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence causal forward.  Returns (logits (B,S,V), aux loss).

    ``scan_blocks`` runs the (homogeneous-cycle) layer stack as a
    ``lax.scan`` over stacked params — the production pattern: activation
    memory is bounded by one cycle body + per-layer residuals instead of
    the whole unrolled stack.  Requires a cycle-uniform quant policy (layer
    paths collapse to ``layers/cyc<j>``).  The unrolled path is kept for
    cost attribution (XLA cost_analysis counts a loop body only once).
    """
    x = _embed(params, cfg, ctx, tokens, vision_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    aux_total = jnp.zeros((), jnp.float32)

    # Megatron-style sequence parallelism: constrain the residual stream to
    # sequence-sharding over 'model' between blocks.  GSPMD then turns the
    # per-block TP all-reduces into reduce-scatter + all-gather pairs (half
    # the wire bytes) and the saved residuals shrink by the model-axis size.
    sp = None
    if (seq_parallel and ctx.mesh is not None
            and "model" in ctx.mesh.axis_names and s % dict(ctx.mesh.shape)["model"] == 0):
        from jax.sharding import NamedSharding, PartitionSpec as P

        dp = tuple(a for a in ("pod", "data") if a in ctx.mesh.axis_names)
        sp = NamedSharding(ctx.mesh, P(dp if dp else None, "model", None))

    def constrain(y):
        return jax.lax.with_sharding_constraint(y, sp) if sp is not None else y

    fn = block_forward
    if remat:
        fn = jax.checkpoint(  # cfg/ctx/idx are static pytree-less args
            block_forward, static_argnums=(1, 4, 5), policy=None,
        )

    if not scan_blocks:
        for i, blk in enumerate(params["layers"]):
            x, aux = fn(blk, i, constrain(x), positions, cfg, ctx)
            aux_total = aux_total + aux
        return _logits(params, cfg, ctx, x), aux_total

    cycle = _cycle_len(cfg)
    prefix = cfg.first_dense_layers
    groups = (cfg.n_layers - prefix) // cycle
    tail_start = prefix + groups * cycle  # e.g. recurrentgemma: 26 = 8*3 + 2
    for i in range(prefix):
        x, aux = fn(params["layers"][i], i, x, positions, cfg, ctx)
        aux_total = aux_total + aux

    # stack per cycle position j: leaves get a leading `groups` dim.
    # kind(prefix + g*cycle + j) == kind(prefix + j) since cycle is a
    # multiple of both pattern lengths -> the body is g-independent.
    stacks = tuple(
        jax.tree.map(
            lambda *ls: jnp.stack(ls),
            *[params["layers"][prefix + g * cycle + j] for g in range(groups)],
        )
        for j in range(cycle)
    )

    def body(carry, blks):
        xc, auxc = carry
        for j in range(cycle):
            xc, a = fn(blks[j], prefix + j, constrain(xc), positions, cfg, ctx)
            auxc = auxc + a
        return (constrain(xc), auxc), None

    (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), stacks)
    for i in range(tail_start, cfg.n_layers):
        x, aux = fn(params["layers"][i], i, x, positions, cfg, ctx)
        aux_total = aux_total + aux
    return _logits(params, cfg, ctx, x), aux_total


# --------------------------------------------------------------------------
# serving: prefill + decode
# --------------------------------------------------------------------------


def init_cache(
    cfg: LMConfig, b: int, cache_len: int, dtype=jnp.bfloat16,
    kv: attn_lib.KVCache | None = None,
) -> Params:
    """``kv`` selects the attention cache layout (default contiguous).
    The paged layout supports pure-attention stacks only — ring (local)
    and recurrent layers keep slot-private state that block paging has no
    story for (serve/engine validates before choosing paged)."""
    kv = attn_lib.CONTIGUOUS if kv is None else kv
    layers = []
    for i in range(cfg.n_layers):
        kind = cfg.mixer_kind(i)
        if kind == "attn":
            c = kv.init(b, cfg.attn, cache_len, dtype)
        elif kind == "local_attn":
            c = attn_lib.CONTIGUOUS.init(
                b, cfg.local_attn, min(cfg.local_attn.window, cache_len), dtype
            )
        elif kind == "rglru":
            c = rglru_lib.rglru_cache_init(b, cfg.rglru)
        elif kind == "rwkv6":
            c = {
                "S": jnp.zeros(
                    (b, cfg.rwkv.n_heads, cfg.rwkv.d_head, cfg.rwkv.d_head),
                    jnp.float32,
                ),
                "shift": jnp.zeros((b, cfg.d_model), dtype),
            }
        else:
            raise ValueError(kind)
        if cfg.ffn_kind(i) == "rwkv_cmix":
            c["cm_shift"] = jnp.zeros((b, cfg.d_model), dtype)
        layers.append(c)
    return {"layers": layers}


def cache_insert(cache: Params, sub: Params, slots: jax.Array,
                 kv: attn_lib.KVCache | None = None) -> Params:
    """Slot-targeted cache insertion for the continuous-batching scheduler:
    write a (G,)-batch CONTIGUOUS prefill cache into G slots of the
    serving batch cache.  ``slots``: (G,) int32 slot indices (traced-safe).

    Contiguous layout: every cache leaf is batch-leading (attention
    k/v/slot_pos, rglru h/conv, rwkv S/shift, cm_shift), so one row
    insertion per leaf covers them all.  The inserted ``slot_pos`` rows
    carry -1 beyond the prompt (init_cache default), which is what retires
    the previous occupant's stale rows — ``nn/attention._mask`` masks
    ``pos < 0``.  Paged layout: the sub-cache's valid rows scatter into
    the slots' mapped blocks (``PagedKVCache.insert``); the allocator's
    pos-reset of freshly mapped blocks replaces the full-slot-overwrite
    invariant."""
    kv = attn_lib.CONTIGUOUS if kv is None else kv
    if isinstance(kv, attn_lib.ContiguousKVCache) and kv.kv_bits is None:
        # fp contiguous: cache and sub are structurally identical pytrees,
        # one tree-mapped row insertion covers every leaf.  Quantized
        # contiguous caches carry scale leaves the fp sub-cache lacks, so
        # they take the per-layer path (kv.insert encodes on the way in).
        return jax.tree.map(
            lambda big, small: attn_lib.insert_rows(big, small, slots),
            cache, sub,
        )
    return {"layers": [kv.insert(lc, sub_lc, slots)
                       for lc, sub_lc in zip(cache["layers"], sub["layers"])]}


def cache_reset(cfg: LMConfig, cache: Params, slot: jax.Array,
                kv: attn_lib.KVCache | None = None) -> Params:
    """Retire one serving slot: attention rows become invisible
    (``slot_pos = -1`` / table row unmapped, via ``kv.reset``) and
    recurrent state rows are zeroed.

    Contiguous layout: this is hygiene, not the safety mechanism — the
    shape-static decode step keeps writing the retired slot's junk k/v
    each step, and ``fill`` stores those with VISIBLE positions (>= 0).
    What actually protects the next occupant is :func:`cache_insert`
    overwriting the ENTIRE slot (all rows, recurrent state included) at
    admission — do not weaken that to a partial insert.  Paged layout:
    junk writes would land in POOL blocks that may already belong to
    another slot, so the scheduler additionally write-masks retired rows
    (``decode_step(..., write_mask=active)``)."""
    kv = attn_lib.CONTIGUOUS if kv is None else kv
    layers = []
    for i, lc in enumerate(cache["layers"]):
        lc = dict(lc)
        kind = cfg.mixer_kind(i)
        if kind == "attn":
            lc.update(kv.reset(lc, slot))
        elif kind == "local_attn":
            lc.update(attn_lib.CONTIGUOUS.reset(lc, slot))
        elif kind == "rglru":
            lc["h"] = attn_lib.zero_rows(lc["h"], slot)
            lc["conv"] = attn_lib.zero_rows(lc["conv"], slot)
        elif kind == "rwkv6":
            lc["S"] = attn_lib.zero_rows(lc["S"], slot)
            lc["shift"] = attn_lib.zero_rows(lc["shift"], slot)
        if "cm_shift" in lc:
            lc["cm_shift"] = attn_lib.zero_rows(lc["cm_shift"], slot)
        layers.append(lc)
    return {"layers": layers}


def cache_truncate(cfg: LMConfig, cache: Params, lengths: jax.Array,
                   kv: attn_lib.KVCache | None = None) -> Params:
    """Per-row KV rollback for speculative decode: row ``b`` keeps its
    first ``lengths[b]`` token positions, everything at ``pos >=
    lengths[b]`` becomes invisible again (``kv.truncate`` per attention
    layer).  Rows whose cache is already shorter are no-ops, so one
    batchwide jitted call covers ragged accept lengths.  Pure-attention
    stacks only — recurrent state has no per-position rollback story
    (the same restriction as :func:`decode_window`)."""
    kv = attn_lib.CONTIGUOUS if kv is None else kv
    layers = []
    for i, lc in enumerate(cache["layers"]):
        if cfg.mixer_kind(i) != "attn":
            raise ValueError(
                f"cache_truncate supports pure-attention stacks; layer "
                f"{i} is {cfg.mixer_kind(i)!r}")
        layers.append({**lc, **kv.truncate(lc, lengths)})
    return {"layers": layers}


def decode_step(
    params: Params,
    cfg: LMConfig,
    ctx: QCtx,
    cache: Params,
    tokens: jax.Array,  # (B, 1)
    pos: jax.Array,  # (B,) absolute position of this token
    kv: attn_lib.KVCache | None = None,
    write_mask: jax.Array | None = None,
) -> tuple[jax.Array, Params]:
    """One token for every sequence in the batch.  Returns (logits, cache).

    ``kv`` selects the attention cache layout; ``write_mask`` (B,) bool
    drops cache writes for inactive batch rows (required on the paged
    layout, where recycled blocks make junk writes unsafe).

    Named scopes ``embed``, ``attn`` (the mixer, of every kind), ``ffn``
    and ``head``, the same in every layer, label the step's device ops in
    a profiler trace."""
    with jax.named_scope("embed"):
        x = params["embed"]["table"].astype(ctx.compute_dtype)[tokens]
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model**0.5, ctx.compute_dtype)
        if cfg.embed_norm:
            x = norm_apply(cfg.norm, params["embed_ln"], x)

    new_layers = []
    for i, blk in enumerate(params["layers"]):
        path = f"layers/{i}"
        lc = dict(cache["layers"][i])
        with jax.named_scope("attn"):
            h = norm_apply(cfg.norm, blk["pre_norm"], x)
            kind = cfg.mixer_kind(i)
            if kind in ("attn", "local_attn"):
                acfg = cfg.attn if kind == "attn" else cfg.local_attn
                h, ac = attn_lib.attn_decode(
                    blk["attn"], h, pos, lc, acfg, ctx, f"{path}/attn",
                    kv=kv if kind == "attn" else None, write_mask=write_mask,
                )
                lc.update(ac)
            elif kind == "rglru":
                h, rc = rglru_lib.rglru_decode(
                    blk["rglru"], h, lc, cfg.rglru, ctx, f"{path}/rglru"
                )
                lc.update(rc)
            elif kind == "rwkv6":
                h, tc = rwkv_lib.timemix_decode(
                    blk["tmix"], h,
                    {"S": lc["S"], "shift": lc["shift"]},
                    cfg.rwkv, ctx, f"{path}/tmix",
                )
                lc.update(tc)
            if cfg.post_norm:
                h = norm_apply(cfg.norm, blk["post_mixer_norm"], h)
            x = x + h

        with jax.named_scope("ffn"):
            h = norm_apply(cfg.norm, blk["pre_ffn_norm"], x)
            fkind = cfg.ffn_kind(i)
            if fkind == "rwkv_cmix":
                h = rwkv_lib.chanmix_forward(
                    blk["cmix"], h, cfg.rwkv, ctx, f"{path}/cmix",
                    shift_state=lc["cm_shift"],
                )
                lc["cm_shift"] = norm_apply(
                    cfg.norm, blk["pre_ffn_norm"], x
                )[:, 0].astype(lc["cm_shift"].dtype)
            else:
                h, _ = _ffn_forward(blk, i, h, cfg, ctx, path)
            if cfg.post_norm:
                h = norm_apply(cfg.norm, blk["post_ffn_norm"], h)
            x = x + h
        new_layers.append(lc)

    with jax.named_scope("head"):
        logits = _logits(params, cfg, ctx, x)
    return logits, {"layers": new_layers}


def decode_window(
    params: Params,
    cfg: LMConfig,
    ctx: QCtx,
    cache: Params,
    tokens: jax.Array,  # (B, C)
    pos_start: jax.Array,  # (B,) absolute position of each row's first token
    kv: attn_lib.KVCache,
    write_mask: jax.Array | None = None,
    logits_all: bool = False,
) -> tuple[jax.Array, Params]:
    """A C-token window for every batch row against the cache: the
    serving primitive behind chunked prefill, paged decode (C == 1), AND
    the speculative verify pass (``logits_all=True``).

    Each row's tokens sit at positions ``pos_start[b] + [0..C)``; their
    k/v are stored through ``kv.fill_window`` and attention runs over the
    full gathered cache, so a chunk attends to everything already cached
    for its slot (earlier chunks, refcounted shared-prefix blocks) plus
    itself.  Rows with ``write_mask=False`` (idle or decoding slots while
    another row prefills) compute junk and write nothing.  Pure-attention
    stacks only.  Returns LAST-position logits (B, 1, V) — the only ones
    admission samples from — and the updated cache; ``logits_all=True``
    returns every position's logits (B, C, V) instead, which is how the
    speculative target scores all C proposed continuations in ONE call
    (logit row c conditions on window tokens <= c via the causal mask —
    exactly the sequential decode distribution at each position).  Named
    scopes as in :func:`decode_step`."""
    b, c = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"]["table"].astype(ctx.compute_dtype)[tokens]
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model**0.5, ctx.compute_dtype)
        if cfg.embed_norm:
            x = norm_apply(cfg.norm, params["embed_ln"], x)
    positions = pos_start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]

    new_layers = []
    for i, blk in enumerate(params["layers"]):
        path = f"layers/{i}"
        if cfg.mixer_kind(i) != "attn":
            raise ValueError(
                f"decode_window supports pure-attention stacks; layer {i} "
                f"is {cfg.mixer_kind(i)!r}")
        lc = dict(cache["layers"][i])
        with jax.named_scope("attn"):
            h = norm_apply(cfg.norm, blk["pre_norm"], x)
            h, ac = attn_lib.attn_window(
                blk["attn"], h, positions, lc, cfg.attn, ctx, f"{path}/attn",
                kv, write_mask=write_mask,
            )
            lc.update(ac)
            if cfg.post_norm:
                h = norm_apply(cfg.norm, blk["post_mixer_norm"], h)
            x = x + h

        with jax.named_scope("ffn"):
            h = norm_apply(cfg.norm, blk["pre_ffn_norm"], x)
            h, _ = _ffn_forward(blk, i, h, cfg, ctx, path)
            if cfg.post_norm:
                h = norm_apply(cfg.norm, blk["post_ffn_norm"], h)
            x = x + h
        new_layers.append(lc)

    with jax.named_scope("head"):
        logits = _logits(params, cfg, ctx,
                         x if logits_all else x[:, -1:, :])
    return logits, {"layers": new_layers}


def prefill(
    params: Params,
    cfg: LMConfig,
    ctx: QCtx,
    tokens: jax.Array,
    cache_len: int,
    vision_embeds: jax.Array | None = None,
) -> tuple[jax.Array, Params]:
    """Process the prompt, build the cache, return last-position logits.

    Implemented as forward + cache extraction for attention layers and a
    state-producing pass for recurrent layers.  For simplicity and
    numerical parity we rerun the mixers' state-producing variants.
    """
    x = _embed(params, cfg, ctx, tokens, vision_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cache = init_cache(cfg, b, cache_len, ctx.compute_dtype)

    for i, blk in enumerate(params["layers"]):
        path = f"layers/{i}"
        lc = cache["layers"][i]
        h = norm_apply(cfg.norm, blk["pre_norm"], x)
        kind = cfg.mixer_kind(i)
        if kind in ("attn", "local_attn"):
            acfg = cfg.attn if kind == "attn" else cfg.local_attn
            q, k, v = attn_lib._project_qkv(
                blk["attn"], h, positions, acfg, ctx, f"{path}/attn"
            )
            cache["layers"][i] = {
                **lc, **attn_lib.CONTIGUOUS.fill(lc, k, v, positions)}
            qg = q.reshape(b, s, acfg.n_kv_heads, acfg.groups, acfg.d_head)
            if s <= acfg.full_attn_max_seq:
                out = attn_lib._sdpa(acfg, qg, k, v,
                                     attn_lib._mask(acfg, positions, positions))
            else:
                out = attn_lib._sdpa_chunked(acfg, qg, k, v, positions, positions)
            out = out.reshape(b, s, acfg.n_heads * acfg.d_head)
            h = ctx.dense(blk["attn"]["o"], out.astype(ctx.compute_dtype),
                          f"{path}/attn/o")
        elif kind == "rglru":
            h, state = _rglru_prefill(blk["rglru"], h, cfg.rglru, ctx,
                                      f"{path}/rglru")
            cache["layers"][i] = {**lc, **state}
        elif kind == "rwkv6":
            h, state = _rwkv_prefill(blk["tmix"], h, cfg.rwkv, ctx,
                                     f"{path}/tmix")
            cache["layers"][i] = {**lc, **state}
        if cfg.post_norm:
            h = norm_apply(cfg.norm, blk["post_mixer_norm"], h)
        x = x + h

        hf = norm_apply(cfg.norm, blk["pre_ffn_norm"], x)
        if cfg.ffn_kind(i) == "rwkv_cmix":
            cache["layers"][i]["cm_shift"] = hf[:, -1].astype(ctx.compute_dtype)
            h = rwkv_lib.chanmix_forward(blk["cmix"], hf, cfg.rwkv, ctx,
                                         f"{path}/cmix")
        else:
            h, _ = _ffn_forward(blk, i, hf, cfg, ctx, path)
        if cfg.post_norm:
            h = norm_apply(cfg.norm, blk["post_ffn_norm"], h)
        x = x + h

    logits = _logits(params, cfg, ctx, x[:, -1:, :])
    return logits, cache


def _rglru_prefill(p, x, rcfg, ctx, path):
    """rglru forward + final state (recompute conv tail + h)."""
    y = rglru_lib.rglru_forward(p, x, rcfg, ctx, path)
    # final hidden state: rerun gates on the last conv output
    u = ctx.dense(p["in_x"], x, f"{path}/in_x")
    u_c = rglru_lib._conv_train(p, u)
    a, bterm = rglru_lib._gates(p, u_c)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    _, h = jax.lax.associative_scan(combine, (a, bterm), axis=1)
    state = {
        "h": h[:, -1],
        "conv": u[:, -(rcfg.conv_width - 1):, :].astype(jnp.float32),
    }
    return y, state


def _rwkv_prefill(p, x, rcfg, ctx, path):
    xx = rwkv_lib._shift_train(x) - x
    r, k, v, lw, g = rwkv_lib._timemix_pre(p, x, xx, rcfg, ctx, path)
    u = p["bonus_u"].astype(jnp.float32)
    b = x.shape[0]
    s0 = jnp.zeros((b, rcfg.n_heads, rcfg.d_head, rcfg.d_head), jnp.float32)
    y, s_fin = rwkv_lib._wkv_chunked(r, k, v, lw, u, s0, rcfg.chunk, ctx)
    y = rwkv_lib._group_norm(p["gn"], y, rcfg.n_heads, rcfg.d_head)
    y = (y.astype(ctx.compute_dtype)) * g
    out = ctx.dense(p["o"], y, f"{path}/o")
    state = {"S": s_fin, "shift": x[:, -1].astype(ctx.compute_dtype)}
    return out, state
