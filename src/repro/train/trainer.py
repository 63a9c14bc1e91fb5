"""Train-step factory: grad + clip + AdamW, with microbatch accumulation,
remat, and optional 1-bit cross-member gradient compression.

Two step factories share one gradient-accumulation core:

* ``make_train_step(spec, ...)`` — the single-program step

      (params, opt_state, batch) -> (params, opt_state, metrics)

  suitable for ``jax.jit`` with in/out shardings from dist/sharding.py.
  The same function lowers on 1 CPU device (smoke tests) and on the
  256/512-chip production meshes (dry-run) — that symmetry is the whole
  point.

* ``make_sharded_train_step(spec, ..., train_cfg, mesh)`` — the DP(xTP)
  step over :class:`TrainState`: the whole step runs inside ``shard_map``,
  each data-parallel member computes gradients on its batch shard, and the
  gradient exchange over ``TrainConfig.dp_axis`` is either the plain
  ``psum`` mean (the CI-gated oracle — equal to the single-device step
  with ``microbatch=dp`` to fp32 rounding: XLA's psum sums the members in
  its own order, not the microbatch scan's left fold) or the 1-bit
  error-feedback collective ``dist.compress.compressed_psum`` (the paper's
  ~32x wire shrink, §2.2.3, applied to training traffic).  The EF residual
  is member-local state: :class:`TrainState.ef` leaves carry a leading
  ``(dp, ...)`` member axis sharded over ``dp_axis``, so checkpointing the
  state makes compressed-training resume exact.  Non-DP mesh axes (e.g.
  'model') pass through the body replicated — size 1 on the CPU smoke
  rig; large-model tensor parallelism stays on the GSPMD
  :class:`TrainLayouts` path.

Metrics: both steps emit ``loss``/``ce``/``aux``/``n_tokens`` (summed, not
averaged, across microbatches and members)/``grad_norm``/``lr``; with
``bit_flip_metrics`` they add the 1809.10463 training-health signal — the
per-layer fraction of binarized weights whose master sign changed this
step (``bit_flip/<layer>`` + the weighted overall ``bit_flip_rate``) — and
the compressed step reports the static wire ``grad_compress_ratio``.  Feed
the dict to a ``train.tracker.Tracker``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from jax import shard_map
from repro.configs.common import ArchSpec
from repro.core.policy import PolicySchedule, QuantPolicy
from repro.dist import compress as dist_compress
from repro.models import lm as lm_model
from repro.models import whisper as whisper_model
from repro.nn.common import QCtx
from repro.optim import adamw
from repro.train import losses

Pytree = Any

# aux metric keys accumulated by SUM (not mean) across microbatches and DP
# members — counters, not averages
SUM_AUX_KEYS = ("n_tokens",)


@dataclasses.dataclass(frozen=True)
class TrainLayouts:
    """ZeRO-1 layout pair (pytrees of NamedSharding).

    ``compute``: TP-only (weights replicated across 'data') — what the
    matmuls contract against.  ``master``: fp32 master params + moments
    sharded over ('data' x 'model').  The step casts/constrains between
    them: one bf16 all-gather (params) + one fp32 reduce-scatter (grads)
    per step, instead of GSPMD resharding activations (DESIGN.md §5).
    """

    compute: object
    master: object


@dataclasses.dataclass
class TrainState:
    """Everything a training run must checkpoint to resume exactly.

    ``params``: fp32 master parameters.  ``opt_state``: AdamW moments +
    step.  ``ef``: the member-local 1-bit error-feedback residual pytree —
    leaves shaped ``(dp, *param.shape)`` (leading axis = DP member, sharded
    over the data axis inside the sharded step) when gradient compression
    is on, the empty pytree ``{}`` otherwise.  Registered as a jax pytree
    and understood by ckpt/manager.py, so ``CheckpointManager.save(step,
    state)`` round-trips it bit-exactly.
    """

    params: Pytree
    opt_state: Pytree
    ef: Pytree


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.params, s.opt_state, s.ef), None),
    lambda _, children: TrainState(*children),
)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Knobs of the sharded train step (jit-static).

    ``grad_compress`` selects the 1-bit EF collective for the DP gradient
    exchange; ``microbatch`` is the number of *per-member* sequential
    accumulation chunks; ``bit_flip_metrics`` emits the per-layer
    binarized-sign-flip rates (no-op metrics-wise when the policy has no
    binary GEMMs).
    """

    remat: bool = False
    microbatch: int | None = None
    grad_compress: bool = False
    dp_axis: str = "data"
    scan_blocks: bool = False
    seq_parallel: bool = False
    bit_flip_metrics: bool = False


def _constrain(tree, shardings):
    return jax.tree.map(jax.lax.with_sharding_constraint, tree, shardings)


def _cast_floating(tree, dtype):
    def c(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree.map(c, tree)


def loss_fn_for(spec: ArchSpec) -> Callable:
    if spec.family == "lm":
        return functools.partial(losses.lm_loss, lm_model.forward)
    if spec.family == "whisper":
        return functools.partial(losses.whisper_loss, whisper_model.forward)
    raise ValueError(spec.family)


def _split_micro(batch: dict, n: int) -> dict:
    """(B, ...) -> (n, B/n, ...) for scan-based accumulation."""
    def r(x):
        b = x.shape[0]
        assert b % n == 0, (b, n)
        return x.reshape(n, b // n, *x.shape[1:])
    return jax.tree.map(r, batch)


def _reduce_aux(aux: dict, reduce_mean, reduce_sum) -> dict:
    if not isinstance(aux, dict):
        return {}
    return {
        k: (reduce_sum(v) if k in SUM_AUX_KEYS else reduce_mean(v))
        for k, v in aux.items()
    }


def _accumulate_grads(grad_fn, params, batch, microbatch):
    """(loss, aux, fp32 grads), averaged over ``microbatch`` sequential
    chunks (left-fold scan; ``SUM_AUX_KEYS`` aux entries summed instead).

    The single-chunk form is the plain ``grad_fn`` call; the DP step's
    psum over members computes the same mean in another summation order,
    so DP(dp) matches microbatch=dp on one device to fp32 rounding.
    """
    if not microbatch or microbatch <= 1:
        (loss, aux), grads = grad_fn(params, batch)
        return loss, aux, _cast_floating(grads, jnp.float32)

    micro = _split_micro(batch, microbatch)

    def acc(carry, mb):
        g_acc, l_acc = carry
        (l, aux), g = grad_fn(params, mb)
        g_acc = jax.tree.map(
            lambda a, b: a + b.astype(jnp.float32), g_acc, g
        )
        return (g_acc, l_acc + l), aux

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (g_sum, loss_sum), aux_stack = jax.lax.scan(acc, (g0, 0.0), micro)
    grads = jax.tree.map(lambda g: g / microbatch, g_sum)
    # aux rides along as stacked (microbatch,) scan outputs: average them
    # (sum for counters) so metrics parity holds with the non-microbatch
    # path instead of silently dropping aux
    aux = _reduce_aux(aux_stack, lambda v: v.mean(0), lambda v: v.sum(0))
    return loss_sum / microbatch, aux, grads


# ---------------------------------------------------------------------------
# bit-flip-rate metrics (Bethge et al. 1809.10463 §5: the fraction of
# binarized weights whose sign changed this step — high early, decaying as
# training settles; flat zero = dead, non-decaying = thrashing)
# ---------------------------------------------------------------------------


def binary_weight_paths(params: Pytree, policy: QuantPolicy) -> list[str]:
    """Paths of weight leaves the policy binarizes (w_bits == 1).

    Matches the layer-path convention of nn.common.QCtx: a GEMM weight
    leaf ``.../<layer>/w`` is binarized iff ``policy.spec(".../<layer>")``
    says so.  Pure tree-structure walk — safe on tracers.
    """
    out: list[str] = []

    def rec(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                rec(v, f"{path}/{k}" if path else str(k))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                rec(v, f"{path}/{i}" if path else str(i))
        elif (path.endswith("/w") and getattr(node, "ndim", 0) >= 2
              and policy.spec(path[:-2]).w_bits == 1):
            out.append(path)

    rec(params, "")
    return out


def _get_by_path(tree: Pytree, path: str):
    node = tree
    for seg in path.split("/"):
        node = node[int(seg)] if isinstance(node, (list, tuple)) else node[seg]
    return node


def bit_flip_metrics(
    policy: QuantPolicy, old_params: Pytree, new_params: Pytree
) -> dict:
    """Per-layer + overall sign-flip rates of the binarized master weights
    between two steps.  ``{}`` when the policy binarizes nothing."""
    paths = binary_weight_paths(old_params, policy)
    if not paths:
        return {}
    out = {}
    flips_total = 0.0
    n_total = 0
    for p in paths:
        a = _get_by_path(old_params, p)
        b = _get_by_path(new_params, p)
        # sign convention of core.quant.binarize: x >= 0 -> +1
        flips = jnp.sum(((a >= 0) != (b >= 0)).astype(jnp.float32))
        out[f"bit_flip/{p[:-2]}"] = flips / a.size
        flips_total = flips_total + flips
        n_total += a.size
    out["bit_flip_rate"] = flips_total / n_total
    return out


# ---------------------------------------------------------------------------
# single-program step (GSPMD / single device)
# ---------------------------------------------------------------------------


def make_train_step(
    spec: ArchSpec,
    cfg: Any,
    ctx: QCtx,
    opt_cfg: adamw.AdamWConfig,
    *,
    remat: bool = True,
    microbatch: int | None = None,
    layouts: TrainLayouts | None = None,
    scan_blocks: bool = False,
    seq_parallel: bool = False,
    bit_flip_metrics_on: bool = False,
):
    """ZeRO-1 step over (master fp32 params, opt state, batch)."""
    loss_fn = loss_fn_for(spec)

    def compute_loss(params, batch):
        return loss_fn(params, cfg, ctx, batch, remat=remat,
                       scan_blocks=scan_blocks, seq_parallel=seq_parallel)

    grad_fn = jax.value_and_grad(compute_loss, has_aux=True)

    def train_step(master, opt_state, batch):
        # master (ZeRO-sharded fp32) -> compute layout (TP-only, bf16):
        # GSPMD lowers the constraint to one bf16 all-gather over 'data'.
        params = _cast_floating(master, ctx.compute_dtype)
        if layouts is not None:
            params = _constrain(params, layouts.compute)

        loss, aux, grads = _accumulate_grads(grad_fn, params, batch,
                                             microbatch)

        # grads -> master layout in fp32: one reduce-scatter over 'data'
        if layouts is not None:
            grads = _constrain(grads, layouts.master)

        new_master, opt_state, opt_metrics = adamw.update(
            grads, opt_state, master, opt_cfg
        )
        metrics = {"loss": loss, **opt_metrics}
        if isinstance(aux, dict):
            metrics.update(aux)
        if bit_flip_metrics_on:
            metrics.update(bit_flip_metrics(ctx.policy, master, new_master))
        return new_master, opt_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# sharded DP(xTP) step over TrainState
# ---------------------------------------------------------------------------


def _axis_size(mesh, axis: str) -> int:
    shape = mesh.shape
    sizes = dict(shape) if hasattr(shape, "keys") else dict(
        zip(mesh.axis_names, shape))
    if axis not in sizes:
        raise ValueError(
            f"dp axis {axis!r} not on mesh axes {tuple(sizes)}"
        )
    return sizes[axis]


def train_state_init(
    spec: ArchSpec,
    cfg: Any,
    key: jax.Array,
    *,
    grad_compress: bool = False,
    dp: int = 1,
) -> TrainState:
    """Fresh :class:`TrainState`; ``ef`` is zeros with a leading ``(dp,)``
    member axis when compressing, the empty pytree otherwise."""
    params, opt_state = init_all(spec, cfg, key)
    ef: Pytree = {}
    if grad_compress:
        ef = jax.tree.map(
            lambda p: jnp.zeros((max(dp, 1),) + p.shape, jnp.float32), params
        )
    return TrainState(params=params, opt_state=opt_state, ef=ef)


def ef_matches(state: TrainState, dp: int) -> bool:
    """Whether ``state.ef`` was produced at this DP degree (elastic resume
    onto a different data-axis size must re-init the residual)."""
    leaves = jax.tree.leaves(state.ef)
    return all(leaf.shape[0] == dp for leaf in leaves)


def make_sharded_train_step(
    spec: ArchSpec,
    cfg: Any,
    ctx: QCtx,
    opt_cfg: adamw.AdamWConfig,
    train_cfg: TrainConfig,
    mesh,
):
    """DP(xTP) step ``(TrainState, batch) -> (TrainState, metrics)``.

    The batch shards over ``train_cfg.dp_axis`` (dim 0 of every leaf);
    params/opt replicate; ``state.ef`` leaves shard their leading member
    axis.  Inside the ``shard_map`` body each member runs the (optionally
    microbatched) gradient computation on its shard, then the gradient
    mean over the DP axis is either ``lax.psum / dp`` (uncompressed — the
    oracle, to fp32 rounding) or ``dist.compress.compressed_psum`` (1-bit EF).
    The returned callable is jit-able (``jax.jit(step, donate_argnums=0)``);
    metrics come out replicated.

    TP note: mesh axes other than ``dp_axis`` pass through the body
    replicated, so a 2-D ('data', 'model') mesh works with any model-axis
    size but the body's compute does not partition over 'model' — the
    smoke rig runs model=1; large-model TP training uses the GSPMD
    ``TrainLayouts`` path.  ``ctx`` must therefore not carry a ``shard-*``
    GEMM backend or an MoE mesh (nested shard_map).
    """
    tc = train_cfg
    dp = _axis_size(mesh, tc.dp_axis)
    loss_fn = loss_fn_for(spec)

    def compute_loss(params, batch):
        return loss_fn(params, cfg, ctx, batch, remat=tc.remat,
                       scan_blocks=tc.scan_blocks,
                       seq_parallel=tc.seq_parallel)

    grad_fn = jax.value_and_grad(compute_loss, has_aux=True)

    def body(master, opt_state, ef, batch):
        params = _cast_floating(master, ctx.compute_dtype)
        loss, aux, grads = _accumulate_grads(grad_fn, params, batch,
                                             tc.microbatch)

        extra = {}
        if tc.grad_compress:
            extra["grad_compress_ratio"] = (
                dist_compress.payload_bytes(grads, compressed=False)
                / dist_compress.payload_bytes(grads, compressed=True)
            )
            # residual is member-local: drop this member's leading axis,
            # compress + psum-mean, carry the new residual back
            e_local = jax.tree.map(lambda x: x[0], ef)
            grads, e_new = dist_compress.compressed_psum(
                grads, e_local, tc.dp_axis
            )
            ef = jax.tree.map(lambda x: x[None], e_new)
        else:
            # the same mean as microbatch=dp on one device, summed in
            # the collective's order (equal to fp32 rounding)
            grads = jax.tree.map(
                lambda g: jax.lax.psum(g, tc.dp_axis) / dp, grads
            )
        loss = jax.lax.psum(loss, tc.dp_axis) / dp
        aux = _reduce_aux(
            aux,
            lambda v: jax.lax.psum(v, tc.dp_axis) / dp,
            lambda v: jax.lax.psum(v, tc.dp_axis),
        )

        new_master, opt_state, opt_metrics = adamw.update(
            grads, opt_state, master, opt_cfg
        )
        metrics = {"loss": loss, **opt_metrics, **aux, **extra}
        if tc.bit_flip_metrics:
            metrics.update(bit_flip_metrics(ctx.policy, master, new_master))
        return new_master, opt_state, ef, metrics

    P = jax.sharding.PartitionSpec
    sharded = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(), P(tc.dp_axis), P(tc.dp_axis)),
        out_specs=(P(), P(), P(tc.dp_axis), P()),
        check_vma=False,
    )

    def step(state: TrainState, batch):
        params, opt_state, ef, metrics = sharded(
            state.params, state.opt_state, state.ef, batch
        )
        return TrainState(params=params, opt_state=opt_state, ef=ef), metrics

    return step


class PolicyScheduledStep:
    """Host-side dispatcher over a :class:`core.policy.PolicySchedule`.

    ``build_fn(policy) -> step`` is called lazily once per schedule stage
    (a QuantPolicy is jit-static, so each stage owns one compiled step);
    calling ``(state, batch, step=i)`` routes to the stage containing
    ``i``.  Carried state (TrainState / params trees) flows across stage
    boundaries unchanged — only the compiled computation swaps.
    """

    def __init__(self, build_fn: Callable, schedule: PolicySchedule):
        self._build = build_fn
        self.schedule = schedule
        self._cache: dict[int, Callable] = {}

    def __call__(self, state, batch, *, step: int):
        idx = self.schedule.stage_index(step)
        fn = self._cache.get(idx)
        if fn is None:
            fn = self._cache[idx] = self._build(self.schedule.stages[idx][1])
        return fn(state, batch)

    @property
    def compiled_stages(self) -> int:
        return len(self._cache)


def init_all(spec: ArchSpec, cfg: Any, key: jax.Array):
    """(params, opt_state) init for any family."""
    if spec.family == "lm":
        params = lm_model.init(key, cfg)
    elif spec.family == "whisper":
        params = whisper_model.init(key, cfg)
    else:
        raise ValueError(spec.family)
    return params, adamw.init(params)
