"""End-to-end system test: train a binary LM on synthetic data, checkpoint,
resume, convert to the packed serving format, and serve — the full BMXNet
lifecycle (train with floats -> pack bits -> serve with xnor)."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.manager import CheckpointManager
from repro.core import converter
from repro.core.policy import QuantPolicy
from repro.data import synthetic
from repro.models import registry
from repro.nn.common import QCtx
from repro.optim import adamw
from repro.serve.engine import Engine, EngineConfig
from repro.train import trainer


def test_full_lifecycle(tmp_path):
    spec = registry.get("granite-3-2b")
    cfg = spec.smoke
    policy = QuantPolicy.binary()
    ctx = QCtx(policy=policy, compute_dtype=jnp.float32)
    opt = adamw.AdamWConfig(lr=3e-3, warmup_steps=3, total_steps=30)

    params, opt_state = trainer.init_all(spec, cfg, jax.random.PRNGKey(0))
    step_fn = jax.jit(trainer.make_train_step(spec, cfg, ctx, opt,
                                              remat=False))
    dcfg = synthetic.DataConfig(cfg.vocab_size, seq_len=24, global_batch=8)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))

    losses = []
    for i in range(15):
        params, opt_state, m = step_fn(params, opt_state,
                                       synthetic.batch_at(dcfg, i))
        losses.append(float(m["loss"]))
    mgr.save(15, {"params": params, "opt": opt_state})

    # ---- simulated preemption: restore and continue -----------------------
    step, tree = mgr.restore({"params": params, "opt": opt_state})
    assert step == 15
    params2, opt2 = tree["params"], tree["opt"]
    for i in range(15, 30):
        params2, opt2, m = step_fn(params2, opt2, synthetic.batch_at(dcfg, i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses

    # ---- convert + packed serving -----------------------------------------
    host = jax.tree.map(np.asarray, params2)
    packed, report = converter.convert(host, policy)
    # smoke config: the fp embedding table dominates a d=64/V=512 model, so
    # the end-to-end ratio is ~3x here (full-size LMs reach ~10x, see
    # benchmarks lm_sizes; the per-layer ratio is ~25-32x either way)
    assert report.ratio > 3, report.summary()
    packed = jax.tree.map(jnp.asarray, packed)

    eng_fq = Engine(spec, cfg, ctx, params2,
                    EngineConfig(batch=2, cache_len=48, max_new_tokens=5))
    eng_pk = Engine(spec, cfg, ctx, packed,
                    EngineConfig(batch=2, cache_len=48, max_new_tokens=5))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32)
    np.testing.assert_array_equal(eng_fq.generate(prompts),
                                  eng_pk.generate(prompts))


def test_train_launcher_cli(tmp_path):
    """The actual CLI driver runs (deliverable b)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "granite-3-2b",
         "--smoke", "--steps", "6", "--batch", "4", "--seq", "16",
         "--quant", "binary", "--ckpt-dir", str(tmp_path / "c"),
         "--ckpt-every", "3", "--log-every", "2",
         "--export-packed", str(tmp_path / "packed.npz")],
        capture_output=True, text=True, timeout=540,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=Path(__file__).parents[1],
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "loss=" in out.stdout
    assert "packed export" in out.stdout
    assert (tmp_path / "packed.npz").exists()


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(env_dir, tmp_path, monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins and no other path is set in code;
    unset, the cache lives in the checkout's .jax_cache."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import compile_cache

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.use_compile_cache()
        if env_dir:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == saved[0]
        else:
            assert got == str(Path(__file__).parents[1] / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])
        compilation_cache.reset_cache()
