"""Sharded DP training: the 1-bit EF compressed collective under a real
multi-member shard_map (property sweep over 2/4/8-way 'data' splits), EF
residual member-locality, compressed-resume exactness through the
checkpoint manager, the tracker layer, and policy schedules."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro.ckpt.manager import CheckpointManager
from jax import shard_map
from repro.core.policy import PolicySchedule, QuantPolicy
from repro.data import synthetic
from repro.dist import compress
from repro.models import registry
from repro.nn.common import QCtx
from repro.optim import adamw
from repro.train import tracker as tracker_mod
from repro.train import trainer
from repro.launch.mesh import make_mesh


def _n_dev():
    return len(jax.devices())


def _mesh_or_skip(dp, tp=1):
    if _n_dev() < dp * tp:
        pytest.skip(f"needs {dp * tp} devices, have {_n_dev()}")
    return make_mesh((dp, tp), ("data", "model"))


# ---------------------------------------------------------------------------
# compressed_psum under real multi-member shard_map
# ---------------------------------------------------------------------------


def _sim_member(g, e):
    """numpy re-implementation of dist.compress.compress_leaf."""
    acc = g + e
    scale = np.mean(np.abs(acc), dtype=np.float32)
    c = np.where(acc >= 0, scale, -scale).astype(np.float32)
    return c, acc - c


@settings(max_examples=10, deadline=None)  # each draw compiles a mesh step
@given(seed=st.integers(0, 2**31 - 1), dp=st.sampled_from([2, 4, 8]))
def test_compressed_psum_property(seed, dp):
    """Per-member EF residual locality + the EF-SGD invariant, on a real
    dp-member 'data' mesh: every member's returned residual is exactly its
    own quantization error, the psum mean matches a per-member numpy
    simulation, and mean(true) - mean(compressed) == mean(residual)."""
    if _n_dev() < dp:
        return  # this draw needs a bigger rig; other draws still run
    mesh = make_mesh((dp,), ("data",))
    rng = np.random.default_rng(seed)
    shapes = {"a": (3, 5), "b": (7,), "c": (2, 2, 4)}
    g = {k: (rng.standard_normal((dp,) + s) * rng.uniform(0.1, 10.0))
         .astype(np.float32) for k, s in shapes.items()}
    e = {k: (rng.standard_normal((dp,) + s) * 0.1).astype(np.float32)
         for k, s in shapes.items()}

    def body(gm, em):
        gl = jax.tree.map(lambda x: x[0], gm)
        el = jax.tree.map(lambda x: x[0], em)
        mean, e_new = compress.compressed_psum(gl, el, "data")
        return (jax.tree.map(lambda x: x[None], mean),
                jax.tree.map(lambda x: x[None], e_new))

    f = shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                  out_specs=(P("data"), P("data")), check_vma=False)
    got_m, got_e = jax.jit(f)(g, e)

    for k in shapes:
        comp = np.empty_like(g[k])
        enew = np.empty_like(g[k])
        for mbr in range(dp):
            comp[mbr], enew[mbr] = _sim_member(g[k][mbr], e[k][mbr])
        mean = comp.sum(0) / dp
        gm, ge = np.asarray(got_m[k]), np.asarray(got_e[k])
        # the psum mean is replicated to every member and matches the sim
        for mbr in range(dp):
            np.testing.assert_allclose(gm[mbr], mean, rtol=2e-5, atol=1e-4)
        # EF locality: member i's residual is exactly its own error
        np.testing.assert_allclose(ge, enew, rtol=2e-5, atol=1e-4)
        # EF-SGD invariant: the compressed mean undershoots the true mean
        # by exactly the mean residual (what error feedback repays next
        # step)
        acc_mean = (g[k].astype(np.float64) + e[k]).sum(0) / dp
        np.testing.assert_allclose(acc_mean - gm[0], ge.sum(0) / dp,
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# trainer-level DP behavior
# ---------------------------------------------------------------------------


def _setup(seq=16, batch=8, steps=20):
    spec = registry.get("granite-3-2b")
    cfg = spec.smoke
    ctx = QCtx(policy=QuantPolicy.binary(), compute_dtype=jnp.float32)
    opt = adamw.AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=steps)
    dcfg = synthetic.DataConfig(cfg.vocab_size, seq_len=seq,
                                global_batch=batch)
    return spec, cfg, ctx, opt, dcfg


def _run_compressed(mesh, spec, cfg, ctx, opt, dcfg, state, lo, hi):
    tc = trainer.TrainConfig(grad_compress=True)
    fn = jax.jit(trainer.make_sharded_train_step(spec, cfg, ctx, opt, tc,
                                                 mesh))
    with mesh:
        for i in range(lo, hi):
            state, m = fn(state, synthetic.batch_at(dcfg, i))
    return state, m


def test_ef_residual_is_member_local():
    """After compressed steps the EF leaves differ across members — the
    residual is per-member state, not a broadcast."""
    mesh = _mesh_or_skip(4)
    spec, cfg, ctx, opt, dcfg = _setup()
    state = trainer.train_state_init(spec, cfg, jax.random.PRNGKey(0),
                                     grad_compress=True, dp=4)
    state, _ = _run_compressed(mesh, spec, cfg, ctx, opt, dcfg, state, 0, 2)
    leaves = jax.tree.leaves(state.ef)
    assert all(leaf.shape[0] == 4 for leaf in leaves)
    distinct = any(
        not np.array_equal(np.asarray(leaf[0]), np.asarray(leaf[m]))
        for leaf in leaves for m in range(1, 4)
    )
    assert distinct, "EF residuals identical across members"


def test_compressed_resume_bit_identical(tmp_path):
    """Save mid-run, restore, continue: bit-identical to uninterrupted
    compressed training (the EF residual rides in TrainState)."""
    mesh = _mesh_or_skip(4)
    spec, cfg, ctx, opt, dcfg = _setup()

    def fresh():
        return trainer.train_state_init(spec, cfg, jax.random.PRNGKey(0),
                                        grad_compress=True, dp=4)

    full, _ = _run_compressed(mesh, spec, cfg, ctx, opt, dcfg, fresh(), 0, 6)

    half, _ = _run_compressed(mesh, spec, cfg, ctx, opt, dcfg, fresh(), 0, 3)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, half)
    step, restored = mgr.restore(fresh())
    assert step == 3 and isinstance(restored, trainer.TrainState)
    assert trainer.ef_matches(restored, 4)
    resumed, _ = _run_compressed(mesh, spec, cfg, ctx, opt, dcfg, restored,
                                 3, 6)

    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dp_tp_2d_mesh_uncompressed_matches_single_device():
    """A 2-D ('data','model') mesh passes the model axis through
    replicated: DP=2 x TP=2 uncompressed == single-device microbatch=2, to
    fp32 rounding (the psum reorders the gradient sum — see
    test_train_integration.test_dp_uncompressed_bit_identical)."""
    mesh = _mesh_or_skip(2, tp=2)
    spec, cfg, ctx, opt, dcfg = _setup()

    params, opt_state = trainer.init_all(spec, cfg, jax.random.PRNGKey(0))
    single = jax.jit(trainer.make_train_step(spec, cfg, ctx, opt,
                                             remat=False, microbatch=2))
    tc = trainer.TrainConfig(grad_compress=False)
    state = trainer.train_state_init(spec, cfg, jax.random.PRNGKey(0))
    sharded = jax.jit(trainer.make_sharded_train_step(spec, cfg, ctx, opt,
                                                      tc, mesh))
    with mesh:
        for i in range(2):
            b = synthetic.batch_at(dcfg, i)
            params, opt_state, ms = single(params, opt_state, b)
            state, md = sharded(state, b)
            np.testing.assert_allclose(float(ms["loss"]), float(md["loss"]),
                                       rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# tracker
# ---------------------------------------------------------------------------


def test_jsonl_tracker_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with tracker_mod.JsonlTracker(path) as trk:
        trk.log({"loss": jnp.float32(1.5), "n": 3}, step=1)
        trk.log({"loss": np.float64(0.75)}, step=2)
    rows = tracker_mod.read_jsonl(path)
    assert rows == [{"step": 1, "loss": 1.5, "n": 3.0},
                    {"step": 2, "loss": 0.75}]


def test_jsonl_tracker_finish_then_log_raises(tmp_path):
    trk = tracker_mod.JsonlTracker(str(tmp_path / "m.jsonl"))
    trk.finish()
    trk.finish()  # idempotent
    with pytest.raises(ValueError):
        trk.log({"x": 1.0}, step=1)


def test_jsonl_tracker_append_mode(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with tracker_mod.JsonlTracker(path) as trk:
        trk.log({"a": 1.0}, step=1)
    with tracker_mod.JsonlTracker(path, append=True) as trk:
        trk.log({"a": 2.0}, step=2)
    assert [r["step"] for r in tracker_mod.read_jsonl(path)] == [1, 2]


def test_tracker_coerces_bad_values_to_nan(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with tracker_mod.JsonlTracker(path) as trk:
        trk.log({"bad": object()}, step=1)
    assert math.isnan(tracker_mod.read_jsonl(path)[0]["bad"])


def test_composite_and_noop_trackers(tmp_path):
    a = tracker_mod.JsonlTracker(str(tmp_path / "a.jsonl"))
    b = tracker_mod.JsonlTracker(str(tmp_path / "b.jsonl"))
    with tracker_mod.CompositeTracker([a, b, tracker_mod.NoopTracker()]) as c:
        c.log({"x": 1.0}, step=5)
    for t in (a, b):
        assert tracker_mod.read_jsonl(t.path) == [{"step": 5, "x": 1.0}]
    assert a._f is None and b._f is None  # finish fanned out


# ---------------------------------------------------------------------------
# policy schedules
# ---------------------------------------------------------------------------


def test_schedule_validation():
    with pytest.raises(ValueError):
        PolicySchedule(stages=())
    with pytest.raises(ValueError):
        PolicySchedule(stages=((5, QuantPolicy.binary()),))
    with pytest.raises(ValueError):
        PolicySchedule(stages=((0, QuantPolicy.binary()),
                               (10, QuantPolicy.binary()),
                               (10, QuantPolicy.full_precision())))


def test_schedule_lookup():
    fp, bn = QuantPolicy.full_precision(), QuantPolicy.binary()
    s = PolicySchedule(stages=((0, fp), (10, bn)))
    assert s.at(0) == fp and s.at(9) == fp
    assert s.at(10) == bn and s.at(10_000) == bn
    assert s.stage_index(9) == 0 and s.stage_index(10) == 1
    assert s.boundaries() == (10,)
    assert PolicySchedule.constant(bn).boundaries() == ()


def test_two_stage_binarization_schedule():
    s = PolicySchedule.two_stage_binarization(100, scale=True)
    (s0, p1), (s1, p2) = s.stages
    assert (s0, s1) == (0, 100)
    assert p1.w_bits == 1 and p1.a_bits != 1  # stage 1: fp activations
    assert p2.w_bits == 1 and p2.a_bits == 1  # stage 2: fully binary
    assert p1.scale and p2.scale


def test_scale_schedule():
    s = PolicySchedule.scale_schedule(50)
    assert s.at(0).scale and not s.at(50).scale
    s = PolicySchedule.scale_schedule(50, scale_first=False)
    assert not s.at(0).scale and s.at(50).scale


def test_scheduled_training_crosses_boundary():
    """PolicyScheduledStep compiles one step per stage and carries state
    across the recompile boundary."""
    spec, cfg, _, opt, dcfg = _setup()
    schedule = PolicySchedule.two_stage_binarization(3)

    def build(pol):
        base = jax.jit(trainer.make_train_step(
            spec, cfg, QCtx(policy=pol, compute_dtype=jnp.float32), opt,
            remat=False))

        def step(state, batch):
            p, o, m = base(state.params, state.opt_state, batch)
            return trainer.TrainState(p, o, state.ef), m

        return step

    stepper = trainer.PolicyScheduledStep(build, schedule)
    state = trainer.train_state_init(spec, cfg, jax.random.PRNGKey(0))
    losses = []
    for i in range(6):
        state, m = stepper(state, synthetic.batch_at(dcfg, i), step=i)
        losses.append(float(m["loss"]))
    assert stepper.compiled_stages == 2
    assert all(np.isfinite(losses))
