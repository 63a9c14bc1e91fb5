"""Feed-forward blocks: gated MLP (llama/gemma family), plain MLP (whisper),
and MoE with shared + routed experts (deepseek-moe / qwen2-moe).

MoE dispatch is sort-based ragged grouping: tokens are argsorted by expert,
contracted against the stacked expert weights, and scattered back with
their gate weights.  Fake-quant training contracts with
`jax.lax.ragged_dot`; packed serving keeps the expert stacks bit-packed
and contracts with `kernels.dispatch.quant_gemm_grouped` (the batched
xnor kernels).  The router always stays full precision (policy
fp_patterns include "router"); expert GEMMs quantize like any other GEMM
(see DESIGN.md §4).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import qlayers, quant
from repro.kernels import dispatch
from repro.nn.common import ACTIVATIONS, QCtx

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    d_model: int
    d_ff: int
    act: str = "silu"
    gated: bool = True


def mlp_init(key, cfg: MLPConfig, *, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    p = {
        "up": qlayers.dense_init(ks[0], cfg.d_model, cfg.d_ff, dtype=dtype),
        "down": qlayers.dense_init(ks[1], cfg.d_ff, cfg.d_model, dtype=dtype),
    }
    if cfg.gated:
        p["gate"] = qlayers.dense_init(ks[2], cfg.d_model, cfg.d_ff, dtype=dtype)
    return p


def mlp_apply(params: Params, x, cfg: MLPConfig, ctx: QCtx, path: str):
    act = ACTIVATIONS[cfg.act]
    up = ctx.dense(params["up"], x, f"{path}/up")
    if cfg.gated:
        gate = ctx.dense(params["gate"], x, f"{path}/gate")
        h = act(gate) * up
    else:
        h = act(up)
    return ctx.dense(params["down"], h, f"{path}/down")


# --------------------------------------------------------------------------
# MoE
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_expert: int  # per-expert FFN width (fine-grained)
    n_routed: int
    n_shared: int
    top_k: int
    act: str = "silu"
    n_routed_padded: int | None = None  # pad experts for EP divisibility
    router_scale_norm: bool = True  # normalise top-k gate weights to sum 1

    @property
    def e(self) -> int:
        return self.n_routed_padded or self.n_routed


def moe_init(key, cfg: MoEConfig, *, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 5)
    e, d, f = cfg.e, cfg.d_model, cfg.d_expert
    std_in, std_f = d**-0.5, f**-0.5
    p: Params = {
        "router": qlayers.dense_init(ks[0], d, e, dtype=dtype),
        "experts": {
            "up": jax.random.normal(ks[1], (e, d, f), dtype) * std_in,
            "gate": jax.random.normal(ks[2], (e, d, f), dtype) * std_in,
            "down": jax.random.normal(ks[3], (e, f, d), dtype) * std_f,
        },
    }
    if cfg.n_shared:
        shared_cfg = MLPConfig(d, cfg.d_expert * cfg.n_shared, cfg.act)
        p["shared"] = mlp_init(ks[4], shared_cfg, dtype=dtype)
    return p


def _router_probs(params, x2, cfg: MoEConfig, ctx: QCtx, path: str):
    """(T, E) probs — router forced fp by policy; padded experts masked."""
    logits = ctx.dense(params["router"], x2, f"{path}/router")
    logits = logits.astype(jnp.float32)
    if cfg.n_routed_padded and cfg.n_routed_padded > cfg.n_routed:
        pad_mask = jnp.arange(cfg.e) >= cfg.n_routed
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    return jax.nn.softmax(logits, axis=-1)


def _expert_quant(w, ctx: QCtx, path: str):
    spec = ctx.policy.spec(path)
    if spec.is_fp:
        return w.astype(ctx.compute_dtype)
    return quant.quantize_weight(w.astype(jnp.float32), spec.w_bits).astype(
        ctx.compute_dtype
    )


def _expert_weights(experts: Params, ctx: QCtx, path: str) -> Params:
    """Expert weight bundle — packed-serving aware.

    Fake-quant: ``{"up"/"gate": (E, D, F), "down": (E, F, D)}`` quantized
    stacks for ``lax.ragged_dot``.  Packed serving: the converter's
    ``{name}_packed`` uint32 stacks ``(E, d_out, Kw)`` pass through
    UNTOUCHED — the contraction runs on the packed xnor kernels via
    ``dispatch.quant_gemm_grouped``, so only packed words cross HBM (the
    32x-traffic part of the paper's insight; daBNN makes the same point).
    """
    if "up_packed" in experts:
        return {k: v for k, v in experts.items() if k.endswith("_packed")}
    return {
        name: _expert_quant(experts[name], ctx, path)
        for name in ("up", "gate", "down")
    }


def _moe_compute_local(xs_q, gate_w, gate_idx, ew, cfg: MoEConfig, spec,
                       compute_dtype, gemm_config, e_base, e_count,
                       capacity: int | None):
    """Sort-based ragged expert compute over experts [e_base, e_base+e_count).

    Runs either globally (single device; e_base=0, e_count=E) or per model
    shard inside shard_map (EP).  ``ew`` is the `_expert_weights` bundle:
    fake-quant stacks contract with ``lax.ragged_dot``; packed stacks go
    through the grouped packed GEMM.  Returns the weighted scatter-add
    (T, D).
    """
    t, d = xs_q.shape
    k = gate_idx.shape[1]
    flat_e = gate_idx.reshape(-1)
    e_local = flat_e - e_base
    owned = (e_local >= 0) & (e_local < e_count)
    sort_key = jnp.where(owned, e_local, e_count)  # non-owned last
    order = jnp.argsort(sort_key)
    cap = capacity if capacity is not None else t * k
    sel = order[:cap]
    tok_of = sel // k
    xs = xs_q[tok_of]  # (cap, D)

    gs_full = jnp.bincount(sort_key, length=e_count + 1)[:e_count]
    cum = jnp.cumsum(gs_full)
    gs = (jnp.clip(cum, 0, cap)
          - jnp.clip(cum - gs_full, 0, cap)).astype(jnp.int32)

    act = ACTIVATIONS[cfg.act]
    if "up_packed" in ew:
        # packed serving: rows stay sorted, weights stay bit-packed; the
        # dispatch layer buckets rows per expert and runs the batched
        # xnor / bit-plane kernel (or lowers to ragged_dot on the "xla"
        # backend).  The spec's bit widths route 1-bit stacks to the xnor
        # kernels and k-bit plane stacks to the DoReFa plane kernels.
        hu, hg = dispatch.quant_gemm_grouped(
            xs.astype(jnp.float32), (ew["up_packed"], ew["gate_packed"]),
            gs, k_true=d, config=gemm_config, out_dtype=jnp.float32,
            w_bits=spec.w_bits, a_bits=spec.a_bits)
        h = act(hg) * hu
        ye = dispatch.quant_gemm_grouped(
            h, ew["down_packed"], gs, k_true=cfg.d_expert,
            config=gemm_config, out_dtype=compute_dtype,
            w_bits=spec.w_bits, a_bits=spec.a_bits)
    else:
        hu = jax.lax.ragged_dot(xs, ew["up"], gs)
        hg = jax.lax.ragged_dot(xs, ew["gate"], gs)
        h = act(hg) * hu
        if not spec.is_fp:
            h = quant.quantize_act(h.astype(jnp.float32), spec.a_bits).astype(
                compute_dtype
            )
        ye = jax.lax.ragged_dot(h, ew["down"], gs)  # (cap, D)

    w_sel = gate_w.reshape(-1)[sel]
    w_sel = jnp.where(owned[sel], w_sel, 0.0).astype(ye.dtype)
    return jnp.zeros((t, d), ye.dtype).at[tok_of].add(ye * w_sel[:, None])


def moe_apply(
    params: Params, x, cfg: MoEConfig, ctx: QCtx, path: str
) -> tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux load-balance loss scalar).

    Distribution: with ``ctx.mesh`` set, experts are EP-sharded over
    'model' and dispatch runs inside ``shard_map`` — each (data x model)
    shard sorts ITS tokens for ITS experts locally and the partial outputs
    psum over 'model'.  No token all-to-all, and crucially no global
    argsort under GSPMD (the auto-partitioned sort replicated everything:
    measured 70 s/step of collectives on deepseek-moe train_4k)."""
    b, s, d = x.shape
    t = b * s
    x2 = x.reshape(t, d)

    probs = _router_probs(params, x2, cfg, ctx, path)  # (T, E)
    gate_w, gate_idx = jax.lax.top_k(probs, cfg.top_k)  # (T, K)
    if cfg.router_scale_norm:
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    flat_e = gate_idx.reshape(-1)

    spec = ctx.policy.spec(f"{path}/experts")
    a_q = (
        quant.quantize_act(x2.astype(jnp.float32), spec.a_bits)
        if not spec.is_fp
        else x2
    ).astype(ctx.compute_dtype)

    ew = _expert_weights(params["experts"], ctx, f"{path}/experts")

    mesh = ctx.mesh
    use_ep = (
        mesh is not None
        and "model" in mesh.axis_names
        and cfg.e % dict(mesh.shape)["model"] == 0
    )
    if not use_ep:
        y = _moe_compute_local(a_q, gate_w, gate_idx, ew, cfg, spec,
                               ctx.compute_dtype, ctx.gemm_config,
                               0, cfg.e, None)
    else:
        from jax.sharding import PartitionSpec as P

        msize = dict(mesh.shape)["model"]
        e_loc = cfg.e // msize
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        n_dp = 1
        for a in dp:
            n_dp *= dict(mesh.shape)[a]
        t_loc = t // n_dp if t % n_dp == 0 else t
        # capacity_factor x load-balance slack over the balanced share
        # (GemmConfig.capacity_factor, default 2.0) — overflow rows drop,
        # and the grouped prologue never quantizes/packs dropped rows
        cf = ctx.gemm_config.capacity_factor
        cf = 2.0 if cf is None else cf  # explicit 0.0 must not mean unset
        # (the 64-row floor below still applies at tiny factors)
        cap = min(max(int(cf * t_loc * cfg.top_k) // msize, 64),
                  t_loc * cfg.top_k)

        # inside the EP shard_map body the GEMMs must run single-device:
        # a shard-* backend would nest a second shard_map over the same
        # mesh (dispatch.unsharded strips the family to its inner kernel)
        gemm_config = dispatch.unsharded(ctx.gemm_config)

        def local(xq, gw, gi, ew_loc):
            mi = jax.lax.axis_index("model")
            y_part = _moe_compute_local(
                xq, gw, gi, ew_loc, cfg, spec, ctx.compute_dtype,
                gemm_config, mi * e_loc, e_loc, cap)
            return jax.lax.psum(y_part, "model")

        dspec = P(dp if dp else None)
        from jax import shard_map

        y = shard_map(
            local, mesh=mesh,
            in_specs=(dspec, dspec, dspec, P("model")),
            out_specs=dspec,
            check_vma=False,
        )(a_q, gate_w, gate_idx, ew)

    # ---- shared experts + aux loss ---------------------------------------
    if "shared" in params:
        shared_cfg = MLPConfig(d, cfg.d_expert * cfg.n_shared, cfg.act)
        y = y + mlp_apply(params["shared"], x, shared_cfg, ctx, f"{path}/shared").reshape(t, d)

    # Switch-style load-balance aux: E * sum_e f_e * p_e
    me = probs.mean(0)  # (E,)
    ce = jnp.zeros((cfg.e,), jnp.float32).at[flat_e].add(1.0) / (t * cfg.top_k)
    aux = cfg.n_routed * jnp.sum(me * ce)

    return y.reshape(b, s, d).astype(ctx.compute_dtype), aux
