"""Integration: training actually learns (fp and binary), microbatching is
consistent (including aux metrics), remat doesn't change the math, and the
sharded DP step is bit-identical to the single-device step (uncompressed)
or still learns (1-bit EF compressed)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import QuantPolicy
from repro.data import synthetic
from repro.models import registry
from repro.nn.common import QCtx
from repro.optim import adamw
from repro.train import trainer
from repro.launch.mesh import make_mesh


def _run(quant, steps=60, arch="deepseek-7b", lr=6e-3):
    spec = registry.get(arch)
    cfg = spec.smoke
    pol = (QuantPolicy.binary() if quant == "binary"
           else QuantPolicy.full_precision())
    ctx = QCtx(policy=pol, compute_dtype=jnp.float32)
    opt = adamw.AdamWConfig(lr=lr, warmup_steps=5, total_steps=steps)
    params, opt_state = trainer.init_all(spec, cfg, jax.random.PRNGKey(0))
    step_fn = jax.jit(trainer.make_train_step(spec, cfg, ctx, opt,
                                              remat=False))
    dcfg = synthetic.DataConfig(cfg.vocab_size, seq_len=32, global_batch=16)
    losses = []
    for i in range(steps):
        params, opt_state, m = step_fn(params, opt_state,
                                       synthetic.batch_at(dcfg, i))
        losses.append(float(m["loss"]))
    return losses


def test_fp_training_learns():
    losses = _run("fp")
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 1.0, losses[-5:]


def test_binary_training_learns():
    """The BNN trains too (paper Table 1: binary accuracy close to fp)."""
    losses = _run("binary", steps=80)
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.5, losses[-5:]


def test_microbatch_equivalence():
    """4 microbatches == single batch, same loss trajectory (fp32)."""
    spec = registry.get("granite-3-2b")
    cfg = spec.smoke
    ctx = QCtx(policy=QuantPolicy.full_precision(), compute_dtype=jnp.float32)
    opt = adamw.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    dcfg = synthetic.DataConfig(cfg.vocab_size, seq_len=16, global_batch=8)

    def run(micro):
        params, opt_state = trainer.init_all(spec, cfg, jax.random.PRNGKey(0))
        fn = jax.jit(trainer.make_train_step(spec, cfg, ctx, opt,
                                             remat=False, microbatch=micro))
        out = []
        for i in range(3):
            params, opt_state, m = fn(params, opt_state,
                                      synthetic.batch_at(dcfg, i))
            out.append(float(m["loss"]))
        return out

    # CE is per-token mean; microbatches have equal token counts
    np.testing.assert_allclose(run(None), run(4), rtol=2e-3)


def test_microbatch_aux_metrics_parity():
    """Regression: the microbatch scan used to drop aux metrics (aux = {});
    now both paths report the full set, with counters summed and the rest
    averaged across chunks."""
    spec = registry.get("granite-3-2b")
    cfg = spec.smoke
    ctx = QCtx(policy=QuantPolicy.full_precision(), compute_dtype=jnp.float32)
    opt = adamw.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    dcfg = synthetic.DataConfig(cfg.vocab_size, seq_len=16, global_batch=8)
    batch = synthetic.batch_at(dcfg, 0)

    def metrics(micro):
        params, opt_state = trainer.init_all(spec, cfg, jax.random.PRNGKey(0))
        fn = jax.jit(trainer.make_train_step(spec, cfg, ctx, opt,
                                             remat=False, microbatch=micro))
        _, _, m = fn(params, opt_state, batch)
        return m

    m1, m4 = metrics(None), metrics(4)
    for key in ("ce", "aux", "n_tokens"):
        assert key in m1 and key in m4, key
    assert set(m1) == set(m4)
    # n_tokens is a counter: summed over chunks, not averaged
    assert float(m1["n_tokens"]) == float(m4["n_tokens"]) == 8 * 16
    np.testing.assert_allclose(float(m1["ce"]), float(m4["ce"]), rtol=2e-3)


# DP vs single-device: the same sums in another reduction order differ by
# a few fp32 ULPs (eps 1.2e-7) per step; 1e-6 allows ~8 ULPs at unit scale
_DP_TOL = dict(rtol=1e-6, atol=1e-6)


def _dp_mesh_or_skip(dp):
    if len(jax.devices()) < dp:
        pytest.skip(f"needs {dp} devices, have {len(jax.devices())}")
    return make_mesh((dp, 1), ("data", "model"))


@pytest.mark.parametrize("dp", [2, 4, 8])
def test_dp_uncompressed_bit_identical(dp):
    """The uncompressed DP step matches the single-device step with
    microbatch=dp at every split, to fp32 rounding.  Not bit for bit:
    XLA's psum over 'data' sums the members' gradients in its own order,
    not the microbatch scan's left fold, and a reordered fp32 sum differs
    in the last place (1 ULP, 5.96e-8 abs, observed) — hence _DP_TOL."""
    mesh = _dp_mesh_or_skip(dp)
    spec = registry.get("granite-3-2b")
    cfg = spec.smoke
    ctx = QCtx(policy=QuantPolicy.binary(), compute_dtype=jnp.float32)
    opt = adamw.AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=10)
    dcfg = synthetic.DataConfig(cfg.vocab_size, seq_len=16, global_batch=8)

    params, opt_state = trainer.init_all(spec, cfg, jax.random.PRNGKey(0))
    single = jax.jit(trainer.make_train_step(spec, cfg, ctx, opt,
                                             remat=False, microbatch=dp))
    state = trainer.train_state_init(spec, cfg, jax.random.PRNGKey(0))
    sharded = jax.jit(trainer.make_sharded_train_step(
        spec, cfg, ctx, opt, trainer.TrainConfig(grad_compress=False), mesh))

    with mesh:
        for i in range(3):
            batch = synthetic.batch_at(dcfg, i)
            params, opt_state, ms = single(params, opt_state, batch)
            state, md = sharded(state, batch)
            np.testing.assert_allclose(float(ms["loss"]), float(md["loss"]),
                                       **_DP_TOL)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **_DP_TOL)


def test_dp_compressed_learns():
    """1-bit EF gradient compression still trains the BNN (the residual
    feedback repays the quantization error over steps)."""
    dp, steps = 4, 40
    mesh = _dp_mesh_or_skip(dp)
    spec = registry.get("granite-3-2b")
    cfg = spec.smoke
    ctx = QCtx(policy=QuantPolicy.binary(), compute_dtype=jnp.float32)
    opt = adamw.AdamWConfig(lr=6e-3, warmup_steps=5, total_steps=steps)
    dcfg = synthetic.DataConfig(cfg.vocab_size, seq_len=32, global_batch=8)
    state = trainer.train_state_init(spec, cfg, jax.random.PRNGKey(0),
                                     grad_compress=True, dp=dp)
    fn = jax.jit(trainer.make_sharded_train_step(
        spec, cfg, ctx, opt, trainer.TrainConfig(grad_compress=True), mesh))
    losses = []
    with mesh:
        for i in range(steps):
            state, m = fn(state, synthetic.batch_at(dcfg, i))
            losses.append(float(m["loss"]))
    assert float(m["grad_compress_ratio"]) > 25.0
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.5, losses[-5:]


def test_remat_matches_no_remat():
    spec = registry.get("deepseek-7b")
    cfg = spec.smoke
    ctx = QCtx(policy=QuantPolicy.full_precision(), compute_dtype=jnp.float32)
    opt = adamw.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    dcfg = synthetic.DataConfig(cfg.vocab_size, seq_len=16, global_batch=4)
    batch = synthetic.batch_at(dcfg, 0)

    outs = []
    for remat in (False, True):
        params, opt_state = trainer.init_all(spec, cfg, jax.random.PRNGKey(0))
        fn = jax.jit(trainer.make_train_step(spec, cfg, ctx, opt, remat=remat))
        _, _, m = fn(params, opt_state, batch)
        outs.append(float(m["loss"]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5)


@pytest.mark.parametrize("kbits", [2, 4])
def test_kbit_quantized_training_learns(kbits):
    """DoReFa path (paper §2.1, 2<=k<=31) also trains."""
    spec = registry.get("deepseek-7b")
    cfg = spec.smoke
    ctx = QCtx(policy=QuantPolicy.quantized(kbits), compute_dtype=jnp.float32)
    opt = adamw.AdamWConfig(lr=6e-3, warmup_steps=5, total_steps=50)
    params, opt_state = trainer.init_all(spec, cfg, jax.random.PRNGKey(0))
    fn = jax.jit(trainer.make_train_step(spec, cfg, ctx, opt, remat=False))
    dcfg = synthetic.DataConfig(cfg.vocab_size, seq_len=32, global_batch=16)
    losses = []
    for i in range(50):
        params, opt_state, m = fn(params, opt_state, synthetic.batch_at(dcfg, i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.5
