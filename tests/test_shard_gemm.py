"""Tensor-parallel packed GEMM (the `shard-*` dispatch backends): every
sharded result must be BIT-IDENTICAL (int32 accumulators and all) to its
single-device counterpart, across K-split widths, non-divisible Kw, both
operand layouts, k-bit plane stacks, and the grouped (MoE) path — plus a
pad-correction property sweep over odd k_true values.

Runs on the virtual 8-device CPU platform from tests/conftest.py
(``mesh_factory`` skips gracefully when the devices are unavailable)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import bitpack, quant
from repro.kernels import dispatch, ref
from repro.kernels.dispatch import EpilogueSpec, GemmConfig
from repro.launch.mesh import make_mesh

WAYS = [1, 2, 4, 8]


def _mats(seed, m, k, n):
    key = jax.random.PRNGKey(seed)
    a = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    return a, w


# ---------------------------------------------------------------------------
# 1-bit: shard-vpu / shard-mxu vs vpu / mxu
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ways", WAYS)
@pytest.mark.parametrize("inner", ["vpu", "mxu"])
def test_shard_1bit_matches_single_device(mesh_factory, inner, ways):
    """K-partitioned packed GEMM: bit-identical int32 dots at every split
    width, including Kw (=11 words) not divisible by the split."""
    mesh = mesh_factory(ways)
    m, k, n = 17, 10 * 32 + 3, 13  # Kw = 11: non-divisible for ways > 1
    a, w = _mats(0, m, k, n)
    ap, wp = bitpack.pack_sign(a), bitpack.pack_sign(w.T)
    want = np.asarray(dispatch.packed_gemm(
        ap, wp, k_true=k, config=GemmConfig(backend=inner)))
    got = np.asarray(dispatch.packed_gemm(
        ap, wp, k_true=k,
        config=GemmConfig(backend=f"shard-{inner}", mesh=mesh)))
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32


@pytest.mark.parametrize("inner", ["vpu", "mxu"])
def test_shard_1bit_n_layout(mesh_factory, inner):
    """The second (column-parallel) layout: N-partitioned weights with
    replicated activations, no collective — still bit-identical."""
    mesh = mesh_factory(4)
    m, k, n = 9, 100, 13  # N = 13: non-divisible by 4 shards
    a, w = _mats(1, m, k, n)
    ap, wp = bitpack.pack_sign(a), bitpack.pack_sign(w.T)
    want = np.asarray(dispatch.packed_gemm(
        ap, wp, k_true=k, config=GemmConfig(backend=inner)))
    got = np.asarray(dispatch.packed_gemm(
        ap, wp, k_true=k,
        config=GemmConfig(backend=f"shard-{inner}", mesh=mesh,
                          shard_layout="n")))
    np.testing.assert_array_equal(got, want)


def test_shard_quant_gemm_epilogue_end_to_end(mesh_factory):
    """Float activations -> pack -> sharded GEMM -> fused epilogue equals
    the single-device path with scale+range+bias all on."""
    mesh = mesh_factory(2)
    m, k, n = 7, 70, 11
    a, w = _mats(2, m, k, n)
    wp = bitpack.pack_sign(w.T)
    scale = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (n,))) + 0.1
    bias = jax.random.normal(jax.random.PRNGKey(4), (n,))
    epi = EpilogueSpec(scale=True, xnor_range=True, bias=True)
    want = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k, config=GemmConfig(backend="vpu"),
        epilogue=epi, scale=scale, bias=bias))
    got = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k,
        config=GemmConfig(backend="shard-vpu", mesh=mesh),
        epilogue=epi, scale=scale, bias=bias))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# k-bit plane stacks: shard-vpu-k* vs vpu-k*
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ways", [2, 4, 8])
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_shard_kbit_planes_match_single_device(mesh_factory, bits, ways):
    """Raw weighted-plane popcount S psums exactly over Kw shards."""
    mesh = mesh_factory(ways)
    m, k, n = 9, 5 * 32 + 17, 7  # Kw = 6: non-divisible for most splits
    a, w = _mats(bits, m, k, n)
    ap = bitpack.pack_planes(quant.act_codes(a, bits), bits)
    wp = bitpack.pack_planes(quant.weight_codes(w.T, bits), bits)
    want = np.asarray(dispatch.packed_kbit_gemm(
        ap, wp, config=GemmConfig(backend=f"vpu-k{bits}")))
    got = np.asarray(dispatch.packed_kbit_gemm(
        ap, wp,
        config=GemmConfig(backend=f"shard-vpu-k{bits}", mesh=mesh)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("layout", ["k", "n"])
def test_shard_kbit_quant_gemm(mesh_factory, layout):
    """w4a4 float-activation entry point through the shard plane backend
    (base name resolution included: 'shard-vpu' + w_bits=4)."""
    mesh = mesh_factory(4)
    m, k, n = 8, 90, 6
    a, w = _mats(7, m, k, n)
    wp = bitpack.pack_planes(quant.weight_codes(w.T, 4), 4)
    want = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k, config=GemmConfig(backend="vpu"),
        w_bits=4, a_bits=4))
    got = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k,
        config=GemmConfig(backend="shard-vpu", mesh=mesh,
                          shard_layout=layout),
        w_bits=4, a_bits=4))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# grouped (MoE expert-stacked): expert-parallel x Kw-parallel
# ---------------------------------------------------------------------------


def _grouped_case(seed=5, t=23, k=45, e=4, n=13):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (t, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (e, n, k), jnp.float32)
    gs = jnp.asarray([5, 0, 11, 4], jnp.int32)  # ragged, sum < t
    return x, w, gs


@pytest.mark.parametrize("ways", [2, 4])
@pytest.mark.parametrize("inner", ["vpu", "mxu"])
def test_shard_grouped_matches_single_device(mesh_factory, inner, ways):
    mesh = mesh_factory(ways)
    x, w, gs = _grouped_case()
    wp = bitpack.pack_sign(w)
    want = np.asarray(dispatch.quant_gemm_grouped(
        x, wp, gs, k_true=x.shape[1], config=GemmConfig(backend=inner)))
    got = np.asarray(dispatch.quant_gemm_grouped(
        x, wp, gs, k_true=x.shape[1],
        config=GemmConfig(backend=f"shard-{inner}", mesh=mesh)))
    np.testing.assert_array_equal(got, want)


def test_shard_grouped_expert_parallel_x_kw_parallel(mesh_factory):
    """2x2 mesh: expert stacks partition over 'expert' while each expert's
    contraction partitions over 'model' — still bit-identical."""
    mesh = mesh_factory((2, 2), axes=("expert", "model"))
    x, w, gs = _grouped_case()
    wp = bitpack.pack_sign(w)
    want = np.asarray(dispatch.quant_gemm_grouped(
        x, wp, gs, k_true=x.shape[1], config=GemmConfig(backend="vpu")))
    got = np.asarray(dispatch.quant_gemm_grouped(
        x, wp, gs, k_true=x.shape[1],
        config=GemmConfig(backend="shard-vpu", mesh=mesh,
                          expert_axis="expert")))
    np.testing.assert_array_equal(got, want)


def test_shard_grouped_kbit(mesh_factory):
    """Grouped k-bit plane stacks (w4a4 MoE) through shard-vpu-k4."""
    mesh = mesh_factory(2)
    x, w, gs = _grouped_case(seed=9)
    k = 4
    wp = jnp.moveaxis(bitpack.pack_planes(quant.weight_codes(w, k), k),
                      0, 1)  # (E, k, N, Kw)
    want = np.asarray(dispatch.quant_gemm_grouped(
        x, wp, gs, k_true=x.shape[1], config=GemmConfig(backend="vpu"),
        w_bits=k, a_bits=k))
    got = np.asarray(dispatch.quant_gemm_grouped(
        x, wp, gs, k_true=x.shape[1],
        config=GemmConfig(backend="shard-vpu", mesh=mesh),
        w_bits=k, a_bits=k))
    np.testing.assert_array_equal(got, want)


def test_shard_grouped_capacity_drops_match(mesh_factory):
    """expert_capacity semantics are backend-invariant on the shard path."""
    mesh = mesh_factory(2)
    x, w, gs = _grouped_case()
    wp = bitpack.pack_sign(w)
    want = np.asarray(dispatch.quant_gemm_grouped(
        x, wp, gs, k_true=x.shape[1], config=GemmConfig(backend="vpu"),
        expert_capacity=4))
    got = np.asarray(dispatch.quant_gemm_grouped(
        x, wp, gs, k_true=x.shape[1],
        config=GemmConfig(backend="shard-vpu", mesh=mesh),
        expert_capacity=4))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# fused prologue inside the shard_map body (the PR-4 refactor): float-
# activation entry points must NOT pack globally and reshard — each shard
# packs its own word-aligned K-slab
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("inner", ["vpu", "mxu"])
def test_shard_k_layout_packs_inside_body(mesh_factory, inner, monkeypatch):
    """On the "k" layout the pack kernel must only ever see LOCAL K-slabs
    (K/ways floats), never the global K — proof the quantize+pack stage
    moved inside the shard_map body."""
    widths = []
    real = dispatch.pack_activations

    def spy(x, **kw):
        widths.append(x.shape[-1])
        return real(x, **kw)

    monkeypatch.setattr(dispatch, "pack_activations", spy)
    ways = 4
    mesh = mesh_factory(ways)
    m, k, n = 6, 8 * 32, 10  # Kw = 8: 2 words (64 floats) per shard
    a, w = _mats(31, m, k, n)
    wp = bitpack.pack_sign(w.T)
    got = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k,
        config=GemmConfig(backend=f"shard-{inner}", mesh=mesh)))
    assert widths and max(widths) == k // ways  # local slabs only
    np.testing.assert_array_equal(
        got, np.asarray(ref.sign_gemm_ref(a, w)).astype(np.int32))


def test_shard_kbit_k_layout_packs_inside_body(mesh_factory, monkeypatch):
    """Same invariant for the fused k-bit plane prologue (S and the code
    row-sums T both psum from local slabs)."""
    ways = 2
    mesh = mesh_factory(ways)
    m, k, n = 5, 6 * 32, 7
    a, w = _mats(33, m, k, n)
    wp = bitpack.pack_planes(quant.weight_codes(w.T, 4), 4)
    want = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k, config=GemmConfig(backend="vpu"),
        w_bits=4, a_bits=4))

    widths = []
    real = dispatch.pack_act_planes

    def spy(x, a_bits, **kw):
        widths.append(x.shape[-1])
        return real(x, a_bits, **kw)

    monkeypatch.setattr(dispatch, "pack_act_planes", spy)
    got = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k,
        config=GemmConfig(backend="shard-vpu", mesh=mesh),
        w_bits=4, a_bits=4))
    assert widths and max(widths) == k // ways
    np.testing.assert_array_equal(got, want)


@settings(max_examples=10, deadline=None)
@given(k_true=st.integers(min_value=1, max_value=150),
       ways=st.sampled_from([2, 4]),
       inner=st.sampled_from(["vpu", "mxu"]))
def test_shard_prologue_property(k_true, ways, inner):
    """For ANY k_true (odd word tails, K smaller than the split, word
    counts not divisible by ways) the float-activation shard path — local
    word-aligned quantize+pack inside the body — returns the exact ±1 dot
    (pad bits are 0 in both operands on every shard)."""
    if len(jax.devices()) < ways:
        pytest.skip(f"{ways}-way mesh needs virtual host devices")
    mesh = make_mesh((ways,), ("model",))
    m, n = 3, 5
    a, w = _mats(k_true * 5 + ways, m, k_true, n)
    oracle = np.asarray(ref.sign_gemm_ref(a, w)).astype(np.int32)
    wp = bitpack.pack_sign(w.T)
    got = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k_true,
        config=GemmConfig(backend=f"shard-{inner}", mesh=mesh)))
    np.testing.assert_array_equal(got, oracle)


# ---------------------------------------------------------------------------
# pad-correction property sweep (hypothesis; odd k_true on both paths)
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(k_true=st.integers(min_value=1, max_value=150),
       ways=st.sampled_from([1, 2, 4]),
       inner=st.sampled_from(["vpu", "mxu"]))
def test_pad_correction_property(k_true, ways, inner):
    """For ANY k_true (odd word tails, tiny K, K < split width) the exact
    ±1 dot comes back from both the sharded and unsharded paths — the pad
    correction is applied once and only once on each.  (Builds its mesh
    inline: the conftest hypothesis fallback wraps the signature, hiding
    fixture params from pytest.)"""
    if len(jax.devices()) < ways:
        pytest.skip(f"{ways}-way mesh needs virtual host devices")
    mesh = make_mesh((ways,), ("model",))
    m, n = 3, 5
    a, w = _mats(k_true * 7 + ways, m, k_true, n)
    oracle = np.asarray(ref.sign_gemm_ref(a, w)).astype(np.int32)
    ap, wp = bitpack.pack_sign(a), bitpack.pack_sign(w.T)
    single = np.asarray(dispatch.packed_gemm(
        ap, wp, k_true=k_true, config=GemmConfig(backend=inner)))
    sharded = np.asarray(dispatch.packed_gemm(
        ap, wp, k_true=k_true,
        config=GemmConfig(backend=f"shard-{inner}", mesh=mesh)))
    np.testing.assert_array_equal(single, oracle)
    np.testing.assert_array_equal(sharded, oracle)


# ---------------------------------------------------------------------------
# negative paths
# ---------------------------------------------------------------------------


def test_shard_backend_without_mesh_raises():
    ap = jnp.zeros((4, 2), jnp.uint32)
    wp = jnp.zeros((4, 2), jnp.uint32)
    with pytest.raises(ValueError, match="needs GemmConfig.mesh"):
        dispatch.packed_gemm(ap, wp, k_true=64,
                             config=GemmConfig(backend="shard-vpu"))


def test_shard_axis_not_on_mesh_raises(mesh_factory):
    mesh = mesh_factory(2)
    ap = jnp.zeros((4, 2), jnp.uint32)
    with pytest.raises(ValueError, match="shard_axis"):
        dispatch.packed_gemm(
            ap, ap, k_true=64,
            config=GemmConfig(backend="shard-vpu", mesh=mesh,
                              shard_axis="nope"))


def test_unknown_shard_layout_raises(mesh_factory):
    mesh = mesh_factory(2)
    ap = jnp.zeros((4, 2), jnp.uint32)
    with pytest.raises(ValueError, match="layout"):
        dispatch.packed_gemm(
            ap, ap, k_true=64,
            config=GemmConfig(backend="shard-vpu", mesh=mesh,
                              shard_layout="zigzag"))


def test_unsharded_strips_family():
    cfg = GemmConfig(backend="shard-mxu", mesh=object())
    down = dispatch.unsharded(cfg)
    assert down.backend == "mxu" and down.mesh is None
    assert dispatch.unsharded(down) is down  # non-shard configs untouched
