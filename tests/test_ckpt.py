"""Checkpoint manager: atomicity, corruption fallback, retention, async,
packed export."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.manager import CheckpointManager, export_packed
from repro.core.policy import QuantPolicy
from repro.core import qlayers
from repro.launch.mesh import make_mesh


def _tree(seed=0):
    key = jax.random.PRNGKey(seed)
    return {
        "params": {"layers": [
            {"w": jax.random.normal(jax.random.fold_in(key, i), (8, 8))}
            for i in range(3)
        ]},
        "opt": {"step": jnp.asarray(7, jnp.int32)},
    }


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(10, tree)
    step, got = mgr.restore(tree)
    assert step == 10
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_skips_corrupt_checkpoint(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(1, tree)
    mgr.save(2, _tree(seed=1))
    # corrupt the newest
    arr = os.path.join(str(tmp_path), "step_00000002", "arrays.npz")
    with open(arr, "r+b") as f:
        f.seek(200)
        f.write(b"\xde\xad\xbe\xef" * 8)
    step, got = mgr.restore(tree)
    assert step == 1  # fell back past the corrupt one


def test_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree())
    assert mgr.all_steps() == [3, 4]


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, _tree(), blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 5


def test_no_tmp_dir_left_behind(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree())
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_export_packed(tmp_path):
    params = {"lay": qlayers.dense_init(jax.random.PRNGKey(0), 256, 128),
              "head": qlayers.dense_init(jax.random.PRNGKey(1), 128, 16)}
    params = jax.tree.map(np.asarray, params)
    path = str(tmp_path / "packed.npz")
    rep = export_packed(params, QuantPolicy.binary(), path)
    assert rep.n_packed == 1  # 'head' stays fp
    assert os.path.exists(path)
    data = np.load(path)
    assert any("w_packed" in k for k in data.files)


def test_dataclass_roundtrip_empty_ef(tmp_path):
    """TrainState (a dataclass pytree) flattens field-wise; the empty-ef
    form (compression off) round-trips to an empty dict."""
    from repro.train.trainer import TrainState

    state = TrainState(params=_tree()["params"],
                       opt_state={"step": jnp.asarray(3, jnp.int32)},
                       ef={})
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, state)
    step, got = mgr.restore(state)
    assert step == 1 and isinstance(got, TrainState)
    assert got.ef == {}
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dataclass_roundtrip_ef_tree(tmp_path):
    """The EF residual tree — leaves with a leading (dp,) member axis —
    survives save/restore bit-exactly (compressed-resume correctness)."""
    from repro.train.trainer import TrainState

    rng = np.random.default_rng(0)
    ef = {"layers": [{"w": rng.standard_normal((4, 8, 8)).astype(np.float32)}
                     for _ in range(2)]}
    state = TrainState(params=_tree()["params"],
                       opt_state={"step": jnp.asarray(9, jnp.int32)},
                       ef=ef)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, state)
    _, got = mgr.restore(state)
    assert isinstance(got, TrainState)
    for lay_a, lay_b in zip(ef["layers"], got.ef["layers"]):
        np.testing.assert_array_equal(lay_a["w"], lay_b["w"])
        assert lay_b["w"].shape[0] == 4


def test_restore_with_shardings(tmp_path):
    """Elastic restore: restore onto explicit (1-device) shardings."""
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(3, tree)
    mesh = make_mesh((1,), ("data",))
    sh = jax.tree.map(
        lambda _: jax.NamedSharding(mesh, jax.sharding.PartitionSpec()), tree)
    step, got = mgr.restore(tree, shardings=sh)
    assert step == 3
    leaf = jax.tree.leaves(got)[0]
    assert leaf.sharding.mesh.shape == {"data": 1}
