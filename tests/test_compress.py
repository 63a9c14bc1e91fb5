"""1-bit error-feedback gradient compression (dist/compress.py)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import compress
from repro.launch.mesh import make_mesh


def test_compress_leaf_is_sign_times_scale():
    g = jnp.asarray([1.0, -2.0, 3.0, -4.0])
    e = jnp.zeros_like(g)
    c, e_new = compress.compress_leaf(g, e)
    scale = np.mean(np.abs(np.asarray(g)))
    np.testing.assert_allclose(np.asarray(c),
                               [scale, -scale, scale, -scale], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(c) + np.asarray(e_new),
                               np.asarray(g), rtol=1e-6)


def test_error_feedback_accumulates_residual():
    """EF property: running sum of compressed grads tracks the running sum
    of true grads to within one step's worth of error."""
    rng = np.random.default_rng(0)
    e = jnp.zeros((64,))
    total_true = np.zeros(64)
    total_comp = np.zeros(64)
    for i in range(200):
        g = jnp.asarray(rng.standard_normal(64) * (1 + 0.1 * i % 3))
        c, e = compress.compress_leaf(g, e)
        total_true += np.asarray(g)
        total_comp += np.asarray(c)
    # residual bounded by the error-feedback state, not growing with T
    resid = np.abs(total_true - total_comp)
    np.testing.assert_allclose(resid, np.abs(np.asarray(e)), rtol=1e-4,
                               atol=1e-4)
    assert resid.max() < 10.0  # bounded, not O(T)


def test_compress_tree_shapes():
    grads = {"a": jnp.ones((4, 4)), "b": {"c": jnp.ones((3,))}}
    ef = compress.ef_init(grads)
    comp, ef2 = compress.compress(grads, ef)
    assert jax.tree.structure(comp) == jax.tree.structure(grads)
    assert jax.tree.structure(ef2) == jax.tree.structure(grads)


def test_payload_accounting():
    grads = {"w": jnp.zeros((1024, 1024))}
    full = compress.payload_bytes(grads, compressed=False)
    packed = compress.payload_bytes(grads, compressed=True)
    assert full == 1024 * 1024 * 4
    assert packed == 1024 * 1024 // 8 + 4
    assert full / packed > 31  # ~32x, paper's compression on the wire


def test_compressed_psum_shard_map():
    """compressed_psum under shard_map on a 1-device 'pod' axis: with a
    single member the mean equals the compressed grad itself."""
    mesh = make_mesh((1,), ("pod",))
    grads = {"w": jnp.asarray([[1.0, -2.0], [0.5, -0.5]])}
    ef = compress.ef_init(grads)

    from jax import shard_map

    def f(g, e):
        return compress.compressed_psum(g, e, "pod")

    fn = shard_map(
        f, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(),) * 2,
        out_specs=(jax.sharding.PartitionSpec(),) * 2,
        check_vma=False,
    )
    summed, ef2 = fn(grads, ef)
    c, _ = compress.compress_leaf(grads["w"], ef["w"])
    np.testing.assert_allclose(np.asarray(summed["w"]), np.asarray(c),
                               rtol=1e-6)


def test_compress_tuple_structured_tree():
    """2-tuples in the gradient pytree STRUCTURE must survive compression
    (regression: a naive is_leaf on 2-tuples mistook structure for leaf
    pairs and dropped half the tree)."""
    grads = ({"a": jnp.ones((4,))}, {"b": 2.0 * jnp.ones((3,))})
    ef = compress.ef_init(grads)
    comp, ef2 = compress.compress(grads, ef)
    assert jax.tree.structure(comp) == jax.tree.structure(grads)
    assert jax.tree.structure(ef2) == jax.tree.structure(grads)
    np.testing.assert_allclose(np.asarray(comp[1]["b"]),
                               2.0 * np.ones(3), rtol=1e-6)
