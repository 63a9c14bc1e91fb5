"""The unified quantized-GEMM dispatch layer (kernels/dispatch.py):
epilogue fusion, backend parity on odd shapes, tile heuristics, and the
grouped (MoE expert-stacked) packed path."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitpack, converter, quant
from repro.core.policy import QuantPolicy, QuantSpec
from repro.kernels import dispatch, ref
from repro.kernels.dispatch import EpilogueSpec, GemmConfig, QuantGemmCall

BACKENDS = ["vpu", "mxu", "xla"]
ODD_SHAPES = [(5, 33, 7), (17, 100, 39), (1, 1, 1), (130, 260, 120)]


def _mats(seed, m, k, n):
    key = jax.random.PRNGKey(seed)
    a = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    return a, w


# ---------------------------------------------------------------------------
# epilogue fusion: dispatch output == unfused reference for every
# combination of scale / xnor_range / bias
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "use_scale,use_range,use_bias",
    list(itertools.product([False, True], repeat=3)),
)
def test_epilogue_fusion_equivalence(use_scale, use_range, use_bias):
    m, k, n = 9, 70, 13
    a, w = _mats(0, m, k, n)
    scale = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (n,))) + 0.1
    bias = jax.random.normal(jax.random.PRNGKey(3), (n,))

    # unfused reference: exact ±1 dot, then each epilogue step by hand
    y = np.asarray(ref.sign_gemm_ref(a, w), np.float64)
    if use_scale:
        y = y * np.asarray(scale, np.float64)
    if use_range:
        y = np.asarray(quant.xnor_range_map(jnp.asarray(y), k))
    if use_bias:
        y = y + np.asarray(bias, np.float64)

    wp = bitpack.pack_sign(w.T)
    got = dispatch.quant_gemm(
        a, wp, k_true=k,
        epilogue=EpilogueSpec(scale=use_scale, xnor_range=use_range,
                              bias=use_bias, out_dtype=jnp.float32),
        scale=scale if use_scale else None,
        bias=bias if use_bias else None,
    )
    np.testing.assert_allclose(np.asarray(got), y, rtol=1e-6, atol=1e-6)


def test_quant_gemm_call_object():
    m, k, n = 4, 40, 6
    a, w = _mats(1, m, k, n)
    call = QuantGemmCall(k_true=k, config=GemmConfig(backend="vpu"),
                         epilogue=EpilogueSpec(out_dtype=jnp.bfloat16))
    got = call(a, bitpack.pack_sign(w.T))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(ref.sign_gemm_ref(a, w))
    )


# ---------------------------------------------------------------------------
# backend parity on odd (non-multiple) shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", ODD_SHAPES)
def test_backend_parity_odd_shapes(m, k, n):
    a, w = _mats(42, m, k, n)
    oracle = np.asarray(ref.sign_gemm_ref(a, w)).astype(np.int32)
    wp = bitpack.pack_sign(w.T)
    for backend in BACKENDS:
        got = dispatch.quant_gemm(
            a, wp, k_true=k, config=GemmConfig(backend=backend)
        )
        np.testing.assert_array_equal(np.asarray(got), oracle, err_msg=backend)


def test_packed_gemm_primitive_parity():
    m, k, n = 17, 100, 39
    a, w = _mats(7, m, k, n)
    ap, wp = bitpack.pack_sign(a), bitpack.pack_sign(w.T)
    oracle = np.asarray(ref.xnor_gemm_ref(ap, wp, k))
    for backend in BACKENDS:
        got = dispatch.packed_gemm(
            ap, wp, k_true=k, config=GemmConfig(backend=backend)
        )
        np.testing.assert_array_equal(np.asarray(got), oracle, err_msg=backend)


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown gemm backend"):
        dispatch.get_backend("tpu_v7")


def test_unknown_backend_raises_through_entry_points():
    """A typo'd base name must raise from every entry point, not fall back
    to some default kernel."""
    a = jnp.ones((2, 64), jnp.float32)
    wp = bitpack.pack_sign(jnp.ones((3, 64), jnp.float32))
    with pytest.raises(ValueError, match="unknown gemm backend"):
        dispatch.quant_gemm(a, wp, k_true=64,
                            config=GemmConfig(backend="vpuu"))
    with pytest.raises(ValueError, match="unknown gemm backend"):
        dispatch.quant_gemm(a, jnp.zeros((4, 3, 2), jnp.uint32), k_true=64,
                            config=GemmConfig(backend="vpuu"),
                            w_bits=4, a_bits=4)
    with pytest.raises(ValueError, match="unknown gemm backend"):
        dispatch.quant_gemm_grouped(
            a, jnp.zeros((2, 3, 2), jnp.uint32),
            jnp.asarray([1, 1], jnp.int32), k_true=64,
            config=GemmConfig(backend="shard-xla"))  # no such shard entry


def test_resolve_backend_down_resolution():
    """resolve_backend maps (base name, w_bits) onto the entry that runs
    it — including 1-bit down-resolution within each family and k-bit
    up-resolution onto the plane entries."""
    # 1-bit: plane backends down-resolve to their family's ±1 entry
    assert dispatch.resolve_backend("vpu-k4", 1) == "vpu"
    assert dispatch.resolve_backend("vpu-k8", 1) == "vpu"
    assert dispatch.resolve_backend("mxu-k4", 1) == "mxu"
    assert dispatch.resolve_backend("shard-vpu-k4", 1) == "shard-vpu"
    assert dispatch.resolve_backend("shard-mxu-k8", 1) == "shard-mxu"
    assert dispatch.resolve_backend("vpu", 1) == "vpu"
    assert dispatch.resolve_backend("shard-mxu", 1) == "shard-mxu"
    assert dispatch.resolve_backend("xla", 1) == "xla"
    # k-bit: base names resolve onto THEIR OWN family's plane entry
    assert dispatch.resolve_backend("vpu", 4) == "vpu-k4"
    assert dispatch.resolve_backend("mxu", 2) == "mxu-k2"
    assert dispatch.resolve_backend("mxu", 8) == "mxu-k8"
    assert dispatch.resolve_backend("shard-vpu", 8) == "shard-vpu-k8"
    assert dispatch.resolve_backend("shard-mxu", 4) == "shard-mxu-k4"
    # a k-bit entry asked for another width re-resolves within its family
    assert dispatch.resolve_backend("mxu-k2", 4) == "mxu-k4"
    # widths with no plane entry fall back to the xla dequant path
    assert dispatch.resolve_backend("vpu", 5) == "xla"
    assert dispatch.resolve_backend("shard-vpu", 3) == "xla"
    # xla handles every width itself (from_float_kbit)
    assert dispatch.resolve_backend("xla", 4) == "xla"


def test_tile_overrides_reach_kernel(monkeypatch):
    """GemmConfig tile overrides must reach the traced Pallas call — a
    spy on the kernel wrapper records the tile kwargs it was invoked
    with (unique shape so the jit cache cannot satisfy the call)."""
    seen = {}
    real = dispatch.xnor_mismatch_pallas

    def spy(ap, bp, **kw):
        seen.update(kw)
        return real(ap, bp, **kw)

    monkeypatch.setattr(dispatch, "xnor_mismatch_pallas", spy)
    m, k, n = 21, 6 * 32, 19
    a, w = _mats(23, m, k, n)
    cfg = GemmConfig(backend="vpu", bm=16, bn=8, bkw=3, chunk_words=3)
    got = dispatch.quant_gemm(a, bitpack.pack_sign(w.T), k_true=k,
                              config=cfg)
    assert (seen["bm"], seen["bn"], seen["bkw"], seen["chunk_words"]) == (
        16, 8, 3, 3)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.sign_gemm_ref(a, w)))


def test_tile_table_covers_and_divides():
    for m, n, kw in [(1, 1, 1), (5, 33, 3), (128, 128, 64), (1000, 7, 200)]:
        for backend in ("vpu", "mxu"):
            t = dispatch.select_tiles(m, n, kw, backend)
            assert t.bkw % t.chunk_words == 0
            assert t.bm <= 128 and t.bn <= 128
            # tiles never exceed the padded operand by more than one step
            assert t.bm >= min(m, 8) and t.bn >= min(n, 8)


def _mosaic_legal(t, m, n, kw):
    """Mosaic's block rule for the (bm, bkw) / (bn, bkw) / (bm, bn)
    blocks of operands padded up to the tile."""
    return ((t.bm >= m or t.bm % 8 == 0)
            and (t.bn >= n or t.bn % 128 == 0)
            and (t.bkw >= kw or t.bkw % 128 == 0)
            and t.bkw % t.chunk_words == 0
            and (t.chunk_words == t.bkw or t.chunk_words % 128 == 0))


@pytest.mark.parametrize("backend", ["vpu", "mxu", "vpu-k4", "mxu-k8"])
def test_select_tiles_mosaic_legal(backend, monkeypatch):
    """Every heuristic tile obeys Mosaic's block rule, and a cached winner
    that does not (measured under the interpreter) is screened before it
    can reach the compiler."""
    for m, n, kw in [(1, 2048, 64), (8, 8192, 64), (8, 2048, 256),
                     (256, 8192, 64), (3, 100, 200), (1000, 7, 1000)]:
        assert _mosaic_legal(dispatch.select_tiles(m, n, kw, backend),
                             m, n, kw)
    monkeypatch.setattr(dispatch, "_TUNED", {
        (16, 512, 256, backend): dispatch.TileConfig(4, 64, 16, 8)})
    dispatch.select_tiles.cache_clear()
    try:
        t = dispatch.select_tiles(16, 512, 256, backend)
        assert _mosaic_legal(t, 16, 512, 256)
        assert t == dispatch.TileConfig(8, 128, 128, 128)
    finally:
        dispatch.select_tiles.cache_clear()


def test_config_tile_overrides_win():
    cfg = GemmConfig(backend="vpu", bm=16, bkw=8, chunk_words=4)
    t = cfg.tiles(100, 100, 64)
    assert (t.bm, t.bkw, t.chunk_words) == (16, 8, 4)
    assert t.bn == 128  # unset override falls back to the table


def test_tile_override_chunk_divisibility():
    """A bkw override that the default chunk does not divide must still be
    exact (the kernel iterates bkw // chunk_words chunks — a non-divisor
    would silently skip K-tail words)."""
    m, k, n = 6, 12 * 32, 5  # Kw = 12, not a multiple of chunk 8
    a, w = _mats(11, m, k, n)
    oracle = np.asarray(ref.sign_gemm_ref(a, w)).astype(np.int32)
    for cfg in (GemmConfig(backend="vpu", bkw=12),
                GemmConfig(backend="vpu", bkw=12, chunk_words=8),
                GemmConfig(backend="vpu", chunk_words=5)):
        assert cfg.tiles(m, n, 12).bkw % cfg.tiles(m, n, 12).chunk_words == 0
        got = dispatch.quant_gemm(a, bitpack.pack_sign(w.T), k_true=k,
                                  config=cfg)
        np.testing.assert_array_equal(np.asarray(got), oracle)


# ---------------------------------------------------------------------------
# grouped (expert-stacked) packed GEMM
# ---------------------------------------------------------------------------


def _grouped_reference(x, w, gs):
    t = x.shape[0]
    e = w.shape[0]
    ends = np.cumsum(np.asarray(gs))
    out = np.zeros((t, w.shape[1]), np.float32)
    for i in range(t):
        g = int(np.searchsorted(ends, i, side="right"))
        if g < e:
            out[i] = np.asarray(
                ref.sign_gemm_ref(x[i:i + 1], np.asarray(w[g]).T)
            )[0]
    return out


@pytest.mark.parametrize("backend", BACKENDS)
def test_grouped_gemm_matches_per_group_reference(backend):
    t, k, e, n = 23, 45, 4, 13
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (t, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (e, n, k), jnp.float32)
    gs = jnp.asarray([5, 0, 11, 4], jnp.int32)  # ragged, sum < t
    got = dispatch.quant_gemm_grouped(
        x, bitpack.pack_sign(w), gs, k_true=k,
        config=GemmConfig(backend=backend),
    )
    np.testing.assert_array_equal(np.asarray(got),
                                  _grouped_reference(x, w, gs))


@pytest.mark.parametrize("backend", BACKENDS)
def test_grouped_gemm_capacity_drops_overflow(backend):
    """expert_capacity drops overflow rows identically on EVERY backend."""
    t, k, e, n = 12, 33, 3, 5
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (t, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (e, n, k), jnp.float32)
    gs = jnp.asarray([8, 2, 2], jnp.int32)
    got = dispatch.quant_gemm_grouped(
        x, bitpack.pack_sign(w), gs, k_true=k,
        config=GemmConfig(backend=backend), expert_capacity=4,
    )
    full = _grouped_reference(x, w, gs)
    got = np.asarray(got)
    # within capacity: exact; overflowed rows (4..7 of expert 0): zeros
    np.testing.assert_array_equal(got[:4], full[:4])
    np.testing.assert_array_equal(got[4:8], np.zeros((4, n), np.float32))
    np.testing.assert_array_equal(got[8:], full[8:])


@pytest.mark.parametrize("backend", BACKENDS)
def test_grouped_gemm_multi_stack(backend):
    """Tuple of weight stacks: one pack+bucket pass, per-stack outputs."""
    t, k, e, n = 17, 40, 3, 9
    key = jax.random.PRNGKey(13)
    x = jax.random.normal(key, (t, k), jnp.float32)
    w1 = jax.random.normal(jax.random.fold_in(key, 1), (e, n, k), jnp.float32)
    w2 = jax.random.normal(jax.random.fold_in(key, 2), (e, n, k), jnp.float32)
    gs = jnp.asarray([6, 7, 4], jnp.int32)
    y1, y2 = dispatch.quant_gemm_grouped(
        x, (bitpack.pack_sign(w1), bitpack.pack_sign(w2)), gs, k_true=k,
        config=GemmConfig(backend=backend),
    )
    np.testing.assert_array_equal(np.asarray(y1), _grouped_reference(x, w1, gs))
    np.testing.assert_array_equal(np.asarray(y2), _grouped_reference(x, w2, gs))


def test_qctx_replace_gemm_config_sticks():
    """dataclasses.replace(ctx, gemm_config=...) must not be reverted by a
    stale legacy xnor_backend alias."""
    import dataclasses as dc

    from repro.nn.common import QCtx

    ctx = QCtx(policy=QuantPolicy.binary(), xnor_backend="vpu")
    assert ctx.gemm_config.backend == "vpu"
    ctx2 = dc.replace(ctx, gemm_config=GemmConfig(backend="xla"))
    assert ctx2.gemm_config.backend == "xla"


# ---------------------------------------------------------------------------
# packed MoE == fake-quant MoE (end-to-end through nn/mlp.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["vpu", "xla"])
def test_moe_packed_matches_fakequant(backend):
    from repro.nn import mlp
    from repro.nn.common import QCtx

    cfg = mlp.MoEConfig(d_model=64, d_expert=48, n_routed=8, n_shared=1,
                        top_k=2)
    params = mlp.moe_init(jax.random.PRNGKey(0), cfg)
    policy = QuantPolicy.binary()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 64))

    ctx_fq = QCtx(policy=policy, compute_dtype=jnp.float32)
    y_fq, aux_fq = mlp.moe_apply(params, x, cfg, ctx_fq, "layers/0/moe")

    packed, rep = converter.convert(jax.tree.map(np.asarray, params), policy)
    assert rep.n_packed > 0
    packed = jax.tree.map(jnp.asarray, packed)
    # the packed expert stacks must flow to the GEMM still bit-packed
    assert "up_packed" in packed["experts"]

    ctx_pk = QCtx(policy=policy, compute_dtype=jnp.float32,
                  gemm_config=GemmConfig(backend=backend))
    y_pk, aux_pk = mlp.moe_apply(packed, x, cfg, ctx_pk, "layers/0/moe")
    np.testing.assert_array_equal(np.asarray(y_fq), np.asarray(y_pk))


def test_mlp_no_unpack_on_expert_weights():
    """The 32x HBM win: nn/mlp.py must not unpack packed expert weights
    in-graph (the dispatch layer owns the packed contraction)."""
    import inspect

    from repro.nn import mlp

    src = inspect.getsource(mlp)
    assert "unpack_sign" not in src


def test_qdense_packed_epilogue_matches_train():
    """Dense layer: both paths share dispatch.apply_epilogue — exact match
    with scale+xnor_range+bias all on."""
    from repro.core import qlayers

    key = jax.random.PRNGKey(0)
    p = qlayers.dense_init(key, 96, 24, bias=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (7, 96))
    spec = QuantSpec(w_bits=1, a_bits=1, scale=True, xnor_range=True)
    pol = QuantPolicy(w_bits=1, a_bits=1, scale=True, xnor_range=True)
    y_train = qlayers.qdense(p, x, spec, compute_dtype=jnp.float32)
    packed, _ = converter.convert({"l": p}, pol)
    y_packed = qlayers.qdense(packed["l"], x, spec,
                              compute_dtype=jnp.float32,
                              gemm_config=GemmConfig(backend="vpu"))
    np.testing.assert_allclose(np.asarray(y_train), np.asarray(y_packed),
                               rtol=1e-6, atol=1e-6)
