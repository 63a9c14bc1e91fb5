"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run forces 512 host devices via XLA_FLAGS before any jax
import; tests and the CI benches force 8 virtual host devices the same
way — tests/conftest.py and ci.yml — so the mesh/shard_map paths run on
plain CPU).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """THE mesh constructor: every mesh in the repo comes from here.

    Axes are ``AxisType.Auto`` — GSPMD propagates shardings through the
    model, as the code was written for; ``jax.make_mesh`` alone defaults
    to ``Explicit`` axes, under which the embedding gather and the
    sharded cache updates refuse to trace."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 v5e chips) or 2x16x16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this process actually has — used by smoke training runs.
    data axis = all local devices, model axis = 1."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def make_elastic_mesh(model_parallel: int = 16):
    """Elastic restart: rebuild the mesh from the devices that are alive.
    The data axis absorbs whatever is left after reserving the model axis;
    checkpoints restore onto the new topology via ckpt.manager (host numpy
    is mesh-agnostic)."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    while n % mp:
        mp -= 1
    return make_mesh((n // mp, mp), ("data", "model"))
