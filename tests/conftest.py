"""Shared test fixtures.

Virtual multi-device CPU: this conftest sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` at import time —
BEFORE any test module imports jax (jax locks the device count on first
init) — so the mesh/shard_map tests (tests/test_shard_gemm.py, the
sharded-engine smoke in tests/test_serve.py) run on plain CPU CI with an
8-device host platform.  The session-scoped ``mesh_factory`` fixture
builds 1-D/2-D meshes from those devices and gracefully skips a test when
the flag did not take effect (jax already imported, or an XLA build that
ignores it).  An explicit device count in a pre-set XLA_FLAGS is
respected.
"""

import os
import sys

if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=8 " + _flags
        ).strip()

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def mesh_factory():
    """``make(shape, axes=("model",)) -> jax.Mesh`` over the virtual host
    devices; skips the requesting test when the device pool is too small
    (see module docstring)."""
    import jax

    from repro.launch.mesh import make_mesh

    n_dev = len(jax.devices())

    def make(shape, axes=("model",)):
        if isinstance(shape, int):
            shape = (shape,)
        need = 1
        for s in shape:
            need *= s
        if need > n_dev:
            pytest.skip(
                f"mesh {shape} needs {need} devices, have {n_dev} "
                "(XLA_FLAGS=--xla_force_host_platform_device_count "
                "unavailable?)"
            )
        return make_mesh(shape, axes)

    return make
