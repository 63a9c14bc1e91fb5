"""Sharding resolver unit tests (AbstractMesh — no devices needed)."""

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import Resolver


def _mesh(shape=(16, 16), axes=("data", "model")):
    return AbstractMesh(tuple(shape), tuple(axes))


def test_divisible_dims_shard():
    rs = Resolver(_mesh())
    got = rs.resolve((None, "model"), (4096, 11008), "mlp/up/w")
    assert got == P(None, "model")
    assert not rs.demotions


def test_non_divisible_demotes_to_replicated():
    rs = Resolver(_mesh())
    got = rs.resolve(("model",), (49155,), "embed/table")  # granite vocab
    assert got == P()
    assert len(rs.demotions) == 1
    assert "49155" in rs.demotion_log()


def test_multi_axis_partial_demotion():
    rs = Resolver(_mesh((2, 16, 16), ("pod", "data", "model")))
    # batch 16 divides data(16) but not pod*data(32): drop 'pod' only
    got = rs.resolve((("pod", "data"),), (16,), "batch")
    assert got == P("data")


def test_param_rules_paths():
    rs = Resolver(_mesh())
    params = {
        "embed": {"table": jax.ShapeDtypeStruct((102400, 4096), jnp.float32)},
        "layers": [{
            "attn": {
                "q": {"w": jax.ShapeDtypeStruct((4096, 4096), jnp.float32)},
                "o": {"w": jax.ShapeDtypeStruct((4096, 4096), jnp.float32)},
            },
            "pre_norm": {"scale": jax.ShapeDtypeStruct((4096,), jnp.float32)},
        }],
        "lm_head": {"w": jax.ShapeDtypeStruct((4096, 102400), jnp.float32)},
    }
    specs = rs.params_pspecs(params)
    assert specs["embed"]["table"] == P("model")
    assert specs["layers"][0]["attn"]["q"]["w"] == P(None, "model")
    assert specs["layers"][0]["attn"]["o"]["w"] == P("model")
    assert specs["layers"][0]["pre_norm"]["scale"] == P()
    assert specs["lm_head"]["w"] == P(None, "model")


def test_master_pspecs_adds_data_axis():
    rs = Resolver(_mesh())
    params = {
        "mlp": {"up": {"w": jax.ShapeDtypeStruct((4096, 11008), jnp.float32)}},
        "norm": {"scale": jax.ShapeDtypeStruct((4096,), jnp.float32)},
    }
    m = rs.master_pspecs(params)
    assert m["mlp"]["up"]["w"] == P("data", "model")
    assert m["norm"]["scale"] == P("data")  # 4096 % 16 == 0


def test_cache_pspecs_sequence_sharded():
    """Flash-decoding layout: cache sequence dim over 'model' for every
    arch (kv-head count irrelevant — see dist/sharding.py docstring)."""
    rs = Resolver(_mesh())
    cache = {
        "layers": [{
            "k": jax.ShapeDtypeStruct((128, 32768, 8, 128), jnp.bfloat16),
            "v": jax.ShapeDtypeStruct((128, 32768, 8, 128), jnp.bfloat16),
            "slot_pos": jax.ShapeDtypeStruct((128, 32768), jnp.int32),
        }]
    }
    specs = rs.cache_pspecs(cache)
    assert specs["layers"][0]["k"] == P("data", "model")
    assert specs["layers"][0]["slot_pos"] == P("data", "model")

    # local-attention ring (window 2048) still divides the model axis
    cache2 = {"k": jax.ShapeDtypeStruct((128, 2048, 1, 256), jnp.bfloat16)}
    assert rs.cache_pspecs(cache2)["k"] == P("data", "model")


def test_batch_pspec_b1_replicates():
    rs = Resolver(_mesh())
    specs = rs.batch_pspecs({"tokens": jax.ShapeDtypeStruct((1, 128),
                                                            jnp.int32)})
    assert specs["tokens"] == P()  # long_500k: batch 1 can't shard


def test_rwkv_state_pspec():
    rs = Resolver(_mesh())
    cache = {"S": jax.ShapeDtypeStruct((128, 64, 64, 64), jnp.float32)}
    assert rs.cache_pspecs(cache)["S"] == P("data", "model")


def test_gemm_pspecs_layouts():
    """Resolver.gemm_pspecs: the packed-GEMM operand layouts the shard-*
    dispatch backends shard_map over (validated against this mesh)."""
    import pytest

    from repro.dist.sharding import packed_gemm_pspecs

    rs = Resolver(_mesh())
    k = rs.gemm_pspecs("k")
    assert k.a == P(None, "model") and k.w == P(None, "model")
    assert k.out == P(None, None) and k.reduce_axis == "model"
    n = rs.gemm_pspecs("n")
    assert n.w == P("model", None) and n.out == P(None, "model")
    assert n.reduce_axis is None  # column-parallel: no collective
    g = rs.gemm_pspecs("k", grouped=True, expert_axis="data")
    assert g.a == P("data", None, "model") and g.out == P("data", None, None)
    p = rs.gemm_pspecs("k", planes=True)
    assert p.a == P(None, None, "model")

    # prologue form: the activation operand is the FLOAT (M, K) tensor,
    # quantized+packed inside the shard_map body — its spec is 2-D with
    # the K dim partitioned ("k") or replicated ("n"); w/out unchanged
    kp = rs.gemm_pspecs("k", prologue=True)
    assert kp.a == P(None, "model") and kp.w == k.w and kp.out == k.out
    kpp = rs.gemm_pspecs("k", planes=True, prologue=True)
    assert kpp.a == P(None, "model") and kpp.w == p.w
    np_ = rs.gemm_pspecs("n", planes=True, prologue=True)
    assert np_.a == P(None, None) and np_.reduce_axis is None

    # validation: unknown mesh axes / layouts raise at resolve time,
    # not deep inside shard_map
    with pytest.raises(ValueError, match="not on mesh"):
        rs.gemm_pspecs("k", axis="nope")
    with pytest.raises(ValueError, match="not on mesh"):
        rs.gemm_pspecs("k", grouped=True, expert_axis="nope")
    with pytest.raises(ValueError, match="layout"):
        packed_gemm_pspecs("zigzag", "model")
    with pytest.raises(ValueError, match="no 'n' layout"):
        packed_gemm_pspecs("n", "model", grouped=True)
    with pytest.raises(ValueError, match="no prologue"):
        packed_gemm_pspecs("k", "model", grouped=True, prologue=True)


def test_master_pspecs_does_not_double_log_demotions():
    """specs.py resolves compute AND master layouts on one Resolver; each
    real demotion must appear once in the operator-facing log."""
    rs = Resolver(_mesh())
    params = {"embed": {"table": jax.ShapeDtypeStruct((49155, 64),
                                                      jnp.float32)}}
    rs.params_pspecs(params)
    rs.master_pspecs(params)
    assert len(rs.demotions) == 1
