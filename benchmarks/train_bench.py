"""Train bench family: the sharded DP train step's correctness gates plus
throughput context.

Two gated rows (CI fails the build via ``run.py --fail-on-mismatch`` if
either reports ``exact_match=False``):

* ``dp_equivalence`` — the uncompressed DP step (psum-mean gradient
  exchange over the 'data' axis) is BIT-IDENTICAL to the single-device
  step with ``microbatch=dp``: same left-fold reduction order, so every
  per-step loss and every final parameter leaf must match exactly.  This
  is the oracle the 1-bit compressed path is measured against.
* ``compressed_vs_uncompressed`` — 1-bit EF gradient compression
  (dist/compress.compressed_psum) trains to within a loss tolerance of
  the uncompressed run over the same schedule (deterministic on CPU, so
  the gate is stable), while shrinking gradient wire bytes ~32x.

The compressed run also logs per-step metrics through a
``train.tracker.JsonlTracker`` to ``BENCH_train_tracker.jsonl`` — the CI
artifact that demonstrates the tracker layer end-to-end (loss, bit-flip
rates, compression ratio, tokens/sec).

Needs >= 2 devices (the CI bench-smoke job forces 8 virtual host
devices); on fewer it emits a single ungated ``skipped`` row.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import QuantPolicy
from repro.data import synthetic
from repro.models import registry
from repro.nn.common import QCtx
from repro.optim import adamw
from repro.train import trainer
from repro.train.tracker import JsonlTracker
from repro.launch.mesh import make_mesh

TRACKER_ARTIFACT = "BENCH_train_tracker.jsonl"


def _setup(smoke: bool):
    spec = registry.get("granite-3-2b")
    cfg = spec.smoke
    policy = QuantPolicy.binary()
    ctx = QCtx(policy=policy, compute_dtype=jnp.float32)
    steps = 12 if smoke else 30
    opt_cfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=3, total_steps=steps)
    dcfg = synthetic.DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                global_batch=8, seed=0)
    batches = [synthetic.batch_at(dcfg, i) for i in range(steps)]
    return spec, cfg, ctx, opt_cfg, steps, batches


def _run_dp(spec, cfg, ctx, opt_cfg, batches, mesh, *, compress,
            tracker=None):
    tc = trainer.TrainConfig(remat=False, grad_compress=compress,
                             bit_flip_metrics=compress)
    dp = dict(mesh.shape)["data"]
    state = trainer.train_state_init(
        spec, cfg, jax.random.PRNGKey(0), grad_compress=compress, dp=dp)
    step_fn = jax.jit(trainer.make_sharded_train_step(
        spec, cfg, ctx, opt_cfg, tc, mesh))
    losses, m = [], {}
    t0 = None
    with mesh:
        for i, b in enumerate(batches):
            if i == 1:
                jax.block_until_ready(state.params)
                t0 = time.perf_counter()
            state, m = step_fn(state, b)
            losses.append(float(m["loss"]))
            if tracker is not None:
                tracker.log(m, step=i + 1)
    jax.block_until_ready(state.params)
    us = (time.perf_counter() - t0) / max(len(batches) - 1, 1) * 1e6
    return state, losses, m, us


def rows(smoke: bool = False):
    if len(jax.devices()) < 2:
        yield {"name": "skipped", "reason": "needs >= 2 devices "
               "(CI forces 8 virtual host devices)"}
        return

    spec, cfg, ctx, opt_cfg, steps, batches = _setup(smoke)
    dp = 4
    mesh = make_mesh((dp, 1), ("data", "model"))

    # --- single-device oracle: microbatch=dp is the same chunked fold ----
    params, opt = trainer.init_all(spec, cfg, jax.random.PRNGKey(0))
    single = jax.jit(trainer.make_train_step(
        spec, cfg, ctx, opt_cfg, remat=False, microbatch=dp))
    s_losses = []
    for b in batches:
        params, opt, m = single(params, opt, b)
        s_losses.append(float(m["loss"]))

    # --- uncompressed DP: the oracle to fp32 rounding --------------------
    # (psum sums the members in its own order, not the microbatch scan's
    # left fold: a few ULPs apart, so rtol = atol = 1e-6)
    u_state, u_losses, _, us_u = _run_dp(
        spec, cfg, ctx, opt_cfg, batches, mesh, compress=False)
    leaves_eq = all(
        np.allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(u_state.params))
    )
    exact = bool(leaves_eq and np.allclose(s_losses, u_losses, rtol=1e-6,
                                           atol=1e-6))
    yield {"name": "dp_equivalence", "dp": dp, "steps": steps,
           "us_per_step": round(us_u, 1), "exact_match": exact}

    # --- compressed DP: loss tracks the uncompressed run ----------------
    with JsonlTracker(TRACKER_ARTIFACT) as trk:
        _, c_losses, c_m, us_c = _run_dp(
            spec, cfg, ctx, opt_cfg, batches, mesh, compress=True,
            tracker=trk)
    # EF keeps the compressed trajectory within a few percent of the
    # uncompressed one at these smoke scales; deterministic on CPU so a
    # fixed relative tolerance gates stably
    tol = 0.10
    gap = abs(c_losses[-1] - u_losses[-1]) / abs(u_losses[-1])
    yield {"name": "compressed_vs_uncompressed", "dp": dp, "steps": steps,
           "final_loss_uncompressed": round(u_losses[-1], 4),
           "final_loss_compressed": round(c_losses[-1], 4),
           "rel_gap": round(gap, 4), "tolerance": tol,
           "compress_ratio": round(float(c_m["grad_compress_ratio"]), 2),
           "bit_flip_rate": round(float(c_m["bit_flip_rate"]), 5),
           "us_per_step": round(us_c, 1),
           "tracker_artifact": TRACKER_ARTIFACT,
           "exact_match": bool(gap <= tol)}
