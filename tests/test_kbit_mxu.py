"""int8 code-lane MXU k-bit backends (kernels/kbit_mxu.py, `mxu-k*` /
`shard-mxu-k*`) and the `overlap_collective` ring reduction.

The MXU path must be BIT-IDENTICAL to the plane popcount path — both
compute the same integer S, one via ka*kb weighted popcount passes, the
other via one offset int8 dot per tile — so every equality here is exact
(`assert_array_equal`), not tolerance-based.  The property sweeps run over
odd k_true values (word-unaligned tails) since pad handling is where the
offset trick could silently break.

Runs on the virtual 8-device CPU platform from tests/conftest.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import bitpack, quant
from repro.kernels import dispatch, ref
from repro.kernels.dispatch import GemmConfig
from repro.launch.mesh import make_mesh

BITS = [2, 4, 8]
WAYS = [1, 2, 4, 8]
# fake-quant train path vs integer path differ only by fp32 rounding
TOL = dict(rtol=1e-4, atol=2e-4)


def _plane_operands(seed, m, k, n, bits):
    key = jax.random.PRNGKey(seed)
    a = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    ap = bitpack.pack_planes(quant.act_codes(a, bits), bits)
    wp = bitpack.pack_planes(quant.weight_codes(w.T, bits), bits)
    return a, w, ap, wp


# ---------------------------------------------------------------------------
# single device: mxu-k* == vpu-k* == jnp oracle (bit-exact)
# ---------------------------------------------------------------------------


@settings(max_examples=24, deadline=None)
@given(
    bits=st.sampled_from(BITS),
    m=st.integers(min_value=1, max_value=17),
    n=st.integers(min_value=1, max_value=19),
    kw=st.integers(min_value=1, max_value=6),
    tail=st.integers(min_value=1, max_value=31),  # odd k_true: ragged tail
)
def test_mxu_kbit_matches_vpu_and_oracle(bits, m, n, kw, tail):
    """Property sweep over word-unaligned shapes: the int8 code-lane S
    equals the plane popcount S equals the integer-code oracle, exactly."""
    k = (kw - 1) * 32 + tail
    _, _, ap, wp = _plane_operands(bits * 1000 + k, m, k, n, bits)
    want = np.asarray(ref.kbit_gemm_ref(ap, wp))
    for backend in (f"vpu-k{bits}", f"mxu-k{bits}"):
        got = np.asarray(dispatch.packed_kbit_gemm(
            ap, wp, config=GemmConfig(backend=backend)))
        np.testing.assert_array_equal(got, want, err_msg=backend)
        assert got.dtype == np.int32


@pytest.mark.parametrize("bits", BITS)
def test_mxu_kbit_quant_gemm_matches_fakequant(bits):
    """Float-activation entry point through the mxu-k* backends (base-name
    resolution 'mxu' + w_bits included) equals the DoReFa fake-quant
    oracle within fp32 rounding."""
    m, k, n = 5, 3 * 32 + 7, 9
    a, w, _, wp = _plane_operands(bits, m, k, n, bits)
    want = np.asarray(ref.dorefa_gemm_ref(a, w, bits, bits))
    for base in ("mxu", f"mxu-k{bits}"):
        got = np.asarray(dispatch.quant_gemm(
            a, wp, k_true=k, w_bits=bits, a_bits=bits,
            config=GemmConfig(backend=base)))
        np.testing.assert_allclose(got, want, err_msg=base, **TOL)


def test_mxu_kbit_asymmetric_widths():
    """ka != kb plane stacks (w4a8): the offset trick uses per-operand
    offsets, so asymmetric widths must stay exact too."""
    m, k, n = 4, 70, 6
    key = jax.random.PRNGKey(42)
    a = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    ap = bitpack.pack_planes(quant.act_codes(a, 8), 8)
    wp = bitpack.pack_planes(quant.weight_codes(w.T, 4), 4)
    want = np.asarray(ref.kbit_gemm_ref(ap, wp))
    got = np.asarray(dispatch.packed_kbit_gemm(
        ap, wp, config=GemmConfig(backend="mxu-k4")))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("bits", BITS)
def test_mxu_kbit_grouped_matches_vpu(bits):
    """Expert-batched int8 code-lane kernel == expert-batched popcount."""
    e, m, k, n = 3, 6, 50, 5
    key = jax.random.PRNGKey(bits)
    xs = jax.random.normal(key, (e, m, k), jnp.float32)
    ws = jax.random.normal(jax.random.fold_in(key, 1), (e, k, n),
                           jnp.float32)
    buckets = jnp.stack([
        bitpack.pack_planes(quant.act_codes(xs[i], bits), bits)
        for i in range(e)])
    w_stack = jnp.stack([
        bitpack.pack_planes(quant.weight_codes(ws[i].T, bits), bits)
        for i in range(e)])
    cfg_v = GemmConfig(backend=f"vpu-k{bits}")
    cfg_m = GemmConfig(backend=f"mxu-k{bits}")
    t = cfg_v.tiles(m, n, buckets.shape[-1], backend=f"vpu-k{bits}")
    tm = cfg_m.tiles(m, n, buckets.shape[-1], backend=f"mxu-k{bits}")
    want = dispatch.get_backend(f"vpu-k{bits}").gemm_kbit_grouped(
        buckets, w_stack, t, cfg_v)
    got = dispatch.get_backend(f"mxu-k{bits}").gemm_kbit_grouped(
        buckets, w_stack, tm, cfg_m)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# trace-time int32 bound: the re-derived mxu-path check
# ---------------------------------------------------------------------------


def test_mxu_kbit_accumulator_bound_rejected():
    """The int8 code-lane path accumulates the FULL code dot in one int32
    partial; dispatch must reject an overflowing K at trace time with the
    MXU-specific message (not the plane-pair one)."""
    big_k = 20_000  # w8a8 bound: 2*K*255*255 >= 2^31 at K ~ 16.5k
    xb = jnp.zeros((1, big_k), jnp.float32)
    wb = jnp.zeros((8, 1, bitpack.packed_width(big_k)), jnp.uint32)
    with pytest.raises(ValueError, match="k-bit MXU GEMM overflows"):
        dispatch.quant_gemm(xb, wb, k_true=big_k,
                            config=GemmConfig(backend="mxu"),
                            w_bits=8, a_bits=8)
    # packed-operand entry point checks the same bound
    ap = jnp.zeros((8, 1, bitpack.packed_width(big_k)), jnp.uint32)
    with pytest.raises(ValueError, match="ONE int32 partial"):
        dispatch.packed_kbit_gemm(ap, wb,
                                  config=GemmConfig(backend="mxu-k8"))
    # the plane popcount family keeps its own message
    with pytest.raises(ValueError, match="k-bit GEMM overflows"):
        dispatch.packed_kbit_gemm(ap, wb,
                                  config=GemmConfig(backend="vpu-k8"))


def test_mxu_kbit_bound_not_overtight():
    """K just under the ceiling must trace (the check may not be MORE
    conservative than 2*K*Na*Nw < 2^31): w2a2 at K = 16k is fine."""
    k = 16 * 1024
    x = jnp.zeros((1, k), jnp.float32)
    w = jnp.zeros((2, 1, bitpack.packed_width(k)), jnp.uint32)
    out = dispatch.quant_gemm(x, w, k_true=k, w_bits=2, a_bits=2,
                              config=GemmConfig(backend="mxu"))
    assert out.shape == (1, 1)


# ---------------------------------------------------------------------------
# shard-mxu-k*: 1/2/4/8-way splits, bit-identical to single device
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ways", WAYS)
@pytest.mark.parametrize("bits", BITS)
def test_shard_mxu_kbit_matches_single_device(mesh_factory, bits, ways):
    """Raw S psums exactly over Kw shards on the int8 code-lane path too
    (pad words unpack to code 0 -> offset identity cancels per lane)."""
    mesh = mesh_factory(ways)
    m, k, n = 9, 5 * 32 + 17, 7  # Kw = 6: non-divisible for most splits
    _, _, ap, wp = _plane_operands(bits + 100, m, k, n, bits)
    want = np.asarray(dispatch.packed_kbit_gemm(
        ap, wp, config=GemmConfig(backend=f"mxu-k{bits}")))
    got = np.asarray(dispatch.packed_kbit_gemm(
        ap, wp,
        config=GemmConfig(backend=f"shard-mxu-k{bits}", mesh=mesh)))
    np.testing.assert_array_equal(got, want)


@settings(max_examples=6, deadline=None)
@given(
    tail=st.integers(min_value=1, max_value=31),
    bits=st.sampled_from(BITS),
    ways=st.sampled_from([2, 4, 8]),
)
def test_shard_mxu_kbit_from_float_property(tail, bits, ways):
    """Property sweep over odd k_true: the float-activation shard path
    (fused pack inside the body) matches the single-device mxu-k* dot
    bit-for-bit after the shared dequant.  (Builds its mesh inline: the
    conftest hypothesis fallback wraps the signature, hiding fixture
    params from pytest.)"""
    if len(jax.devices()) < ways:
        pytest.skip(f"{ways}-way mesh needs virtual host devices")
    mesh = make_mesh((ways,), ("model",))
    k = 3 * 32 + tail
    m, n = 5, 6
    a, _, _, wp = _plane_operands(tail * 7 + bits, m, k, n, bits)
    want = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k, w_bits=bits, a_bits=bits,
        config=GemmConfig(backend=f"mxu-k{bits}")))
    got = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k, w_bits=bits, a_bits=bits,
        config=GemmConfig(backend="shard-mxu", mesh=mesh)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("layout", ["k", "n"])
def test_shard_mxu_kbit_layouts(mesh_factory, layout):
    """Both operand layouts of the shard-mxu-k* family stay bit-identical
    (the "n" layout runs the full contraction per weight slice)."""
    mesh = mesh_factory(4)
    m, k, n = 8, 90, 6
    a, _, _, wp = _plane_operands(11, m, k, n, 4)
    want = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k, w_bits=4, a_bits=4,
        config=GemmConfig(backend="mxu-k4")))
    got = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k, w_bits=4, a_bits=4,
        config=GemmConfig(backend="shard-mxu", mesh=mesh,
                          shard_layout=layout)))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# overlap_collective: ring reduction must be bit-identical to the psum
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ways", WAYS)
@pytest.mark.parametrize("family", ["vpu", "mxu"])
def test_overlap_collective_kbit_bit_identity(mesh_factory, family, ways):
    """overlap_collective=True (chunked ppermute ring) vs False (psum):
    int32 partials add exactly in any order, so outputs must be EQUAL —
    including N (=7) not divisible by the shard count."""
    mesh = mesh_factory(ways)
    m, k, n = 5, 4 * 32 + 9, 7
    a, _, _, wp = _plane_operands(ways + 13, m, k, n, 4)
    base = GemmConfig(backend=f"shard-{family}", mesh=mesh)
    seq = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k, w_bits=4, a_bits=4, config=base))
    ring = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k, w_bits=4, a_bits=4,
        config=GemmConfig(backend=f"shard-{family}", mesh=mesh,
                          overlap_collective=True)))
    np.testing.assert_array_equal(ring, seq)


@pytest.mark.parametrize("family", ["vpu", "mxu"])
def test_overlap_collective_1bit_bit_identity(mesh_factory, family):
    """The 1-bit from_float shard path honors the flag too (mismatch
    counts / padded dots ride the same ring)."""
    mesh = mesh_factory(4)
    m, k, n = 6, 100, 9
    key = jax.random.PRNGKey(3)
    a = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    wp = bitpack.pack_sign(w.T)
    seq = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k,
        config=GemmConfig(backend=f"shard-{family}", mesh=mesh)))
    ring = np.asarray(dispatch.quant_gemm(
        a, wp, k_true=k,
        config=GemmConfig(backend=f"shard-{family}", mesh=mesh,
                          overlap_collective=True)))
    np.testing.assert_array_equal(ring, seq)


def test_overlap_collective_default_off():
    """The safe sequential psum stays the default (the flag is opt-in)."""
    assert GemmConfig().overlap_collective is False


@pytest.mark.parametrize("family", ["vpu", "mxu"])
def test_overlap_collective_packed_operand_bit_identity(mesh_factory,
                                                        family):
    """The packed-operand entry points (packed_gemm / packed_kbit_gemm)
    ride the same ring now — raw int32 partials, exact in any order."""
    mesh = mesh_factory(4)
    m, k, n = 6, 100, 9
    rng = np.random.default_rng(5)
    ap = bitpack.pack_sign(jnp.asarray(
        np.sign(rng.standard_normal((m, k))), jnp.float32))
    wp = bitpack.pack_sign(jnp.asarray(
        np.sign(rng.standard_normal((n, k))), jnp.float32))
    seq = np.asarray(dispatch.packed_gemm(
        ap, wp, k_true=k,
        config=GemmConfig(backend=f"shard-{family}", mesh=mesh)))
    ring = np.asarray(dispatch.packed_gemm(
        ap, wp, k_true=k,
        config=GemmConfig(backend=f"shard-{family}", mesh=mesh,
                          overlap_collective=True)))
    np.testing.assert_array_equal(ring, seq)
    a4, _, ap4, wp4 = _plane_operands(7, m, k, n, 4)
    seq4 = np.asarray(dispatch.packed_kbit_gemm(
        ap4, wp4, config=GemmConfig(backend=f"shard-{family}", mesh=mesh)))
    ring4 = np.asarray(dispatch.packed_kbit_gemm(
        ap4, wp4, config=GemmConfig(backend=f"shard-{family}", mesh=mesh,
                                    overlap_collective=True)))
    np.testing.assert_array_equal(ring4, seq4)


@pytest.mark.parametrize("family", ["vpu", "mxu"])
def test_overlap_collective_grouped_bit_identity(mesh_factory, family):
    """The grouped (MoE expert-stacked) shard path honors the flag too —
    the ring runs inside each expert-axis group (1-bit and k-bit)."""
    mesh = mesh_factory(4)
    t_rows, k, n, e = 10, 90, 7, 3
    rng = np.random.default_rng(9)
    xs = jnp.asarray(rng.standard_normal((t_rows, k)), jnp.float32)
    gs = jnp.asarray([4, 3, 3], jnp.int32)
    w1p = jnp.stack([bitpack.pack_sign(jnp.asarray(
        np.sign(rng.standard_normal((n, k))), jnp.float32))
        for _ in range(e)])
    for kw in ({}, {"w_bits": 4, "a_bits": 4}):
        wstack = w1p if not kw else jnp.stack(
            [_plane_operands(e * 31 + i, 2, k, n, 4)[3] for i in range(e)])
        seq = np.asarray(dispatch.quant_gemm_grouped(
            xs, wstack, gs, k_true=k,
            config=GemmConfig(backend=f"shard-{family}", mesh=mesh), **kw))
        ring = np.asarray(dispatch.quant_gemm_grouped(
            xs, wstack, gs, k_true=k,
            config=GemmConfig(backend=f"shard-{family}", mesh=mesh,
                              overlap_collective=True), **kw))
        np.testing.assert_array_equal(ring, seq)


# ---------------------------------------------------------------------------
# decode-shape tile clamp (satellite): bm follows next-pow2(M) below 8
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["vpu", "mxu", "vpu-k8", "mxu-k8"])
def test_decode_tile_rows_clamp(backend):
    """M in 1..7 must clamp bm to next-pow2(M) instead of padding to 8."""
    for m, want_bm in [(1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (8, 8),
                       (64, 64)]:
        t = dispatch.select_tiles(m, 256, 16, backend)
        assert t.bm == want_bm, (backend, m, t)
    # N rows use the same ladder; serving N stays on the big tiles
    assert dispatch.select_tiles(1, 256, 16, backend).bn == 128
