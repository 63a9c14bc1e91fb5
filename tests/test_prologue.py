"""The dispatch-owned fused activation prologue (PrologueSpec + the
quantize->pack Pallas kernel family in kernels/pack_bits.py):

* bit-identity of the fused kernels against the jnp reference
  (``bitpack.pack_sign`` / ``quant.act_codes`` -> ``bitpack.pack_planes``),
  hypothesis-swept over odd ``k_true`` values,
* pad bits zero in both operands (the exactness precondition),
* GemmConfig.interpret reaching the pack kernels (the kernels used to
  hard-default to interpret mode),
* prologue resolution per backend (``Backend.prologue`` declarations),
* the grouped route-first rule (capacity-dropped rows are never packed),
* GemmConfig.capacity_factor reaching the MoE EP path,
* the select_tiles autotuning cache.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import bitpack, converter, quant
from repro.core.policy import QuantPolicy, QuantSpec
from repro.kernels import dispatch, ref
from repro.kernels.dispatch import GemmConfig, PrologueSpec


def _acts(seed, m, k):
    return jax.random.normal(jax.random.PRNGKey(seed), (m, k), jnp.float32)


# ---------------------------------------------------------------------------
# fused == jnp reference, bit for bit
# ---------------------------------------------------------------------------


@settings(max_examples=16, deadline=None)
@given(k_true=st.integers(min_value=1, max_value=300),
       m=st.integers(min_value=1, max_value=40))
def test_fused_sign_pack_matches_jnp(k_true, m):
    """Odd shapes, word tails, tiny K: the fused 1-bit pack is
    bit-identical to bitpack.pack_sign."""
    x = _acts(k_true * 31 + m, m, k_true)
    want = np.asarray(bitpack.pack_sign(x))
    got = np.asarray(dispatch.pack_activations(x, use_pallas=True))
    np.testing.assert_array_equal(got, want)


@settings(max_examples=12, deadline=None)
@given(k_true=st.integers(min_value=1, max_value=300),
       a_bits=st.sampled_from([2, 4, 8]))
def test_fused_plane_pack_matches_jnp(k_true, a_bits):
    """The fused DoReFa quantize->plane-pack emits the SAME plane stack
    AND the same code row-sums as the jnp act_codes -> pack_planes round
    trip, at any odd k_true."""
    x = _acts(k_true * 13 + a_bits, 7, k_true)
    codes = quant.act_codes(x, a_bits)
    want_p = np.asarray(bitpack.pack_planes(codes, a_bits))
    want_t = np.asarray(codes.astype(jnp.int32).sum(-1))
    got_p, got_t = dispatch.pack_act_planes(x, a_bits, fused=True)
    np.testing.assert_array_equal(np.asarray(got_p), want_p)
    np.testing.assert_array_equal(np.asarray(got_t)[:, 0], want_t)


def test_pad_bits_zero_in_packed_tail():
    """K tails beyond k_true must pack to 0 bits in every output word of
    both prologue forms — the precondition for exactness without pad
    correction (1-bit pads match; k-bit pads AND to nothing)."""
    k_true = 40  # Kw = 2, 24 tail bits in the last word
    x = jnp.abs(_acts(3, 5, k_true)) + 1.0  # all positive: every bit 1
    packed = np.asarray(dispatch.pack_activations(x, use_pallas=True))
    assert (packed[:, -1] >> 8 == 0).all()  # bits 8..31 of word 1 are pad
    planes, _ = dispatch.pack_act_planes(x, 4, fused=True)
    planes = np.asarray(planes)
    assert (planes[:, :, -1] >> 8 == 0).all()
    # and the valid region is NOT all zero (the mask is real)
    assert packed.any() and planes.any()


@pytest.mark.parametrize("use_fused", [True, False])
def test_quant_gemm_identical_across_prologues(use_fused):
    """quant_gemm output is invariant to PrologueSpec.fused (1-bit and
    k-bit) — the fused kernels are drop-in."""
    m, k, n = 9, 70, 11
    x = _acts(0, m, k)
    w = _acts(1, n, k).T
    wp = bitpack.pack_sign(w.T)
    cfg = GemmConfig(backend="vpu", fused_prologue=use_fused)
    got = dispatch.quant_gemm(x, wp, k_true=k, config=cfg)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.sign_gemm_ref(x, w)))
    wkp = bitpack.pack_planes(quant.weight_codes(w.T, 4), 4)
    got4 = dispatch.quant_gemm(x, wkp, k_true=k, config=cfg,
                               w_bits=4, a_bits=4)
    want4 = dispatch.quant_gemm(
        x, wkp, k_true=k, config=GemmConfig(backend="vpu"),
        w_bits=4, a_bits=4)
    np.testing.assert_array_equal(np.asarray(got4), np.asarray(want4))


def test_prologue_spec_overrides_config():
    """An explicit PrologueSpec wins over GemmConfig.fused_prologue and
    still produces identical results (it is threaded into the config so
    shard bodies see it too)."""
    m, k, n = 5, 45, 7
    x = _acts(5, m, k)
    wp = bitpack.pack_sign(_acts(6, n, k))
    base = np.asarray(dispatch.quant_gemm(x, wp, k_true=k))
    got = np.asarray(dispatch.quant_gemm(
        x, wp, k_true=k,
        prologue=PrologueSpec(kind="pack_sign", fused=False)))
    np.testing.assert_array_equal(got, base)


# ---------------------------------------------------------------------------
# interpret threading: the pack kernels honor GemmConfig.interpret
# ---------------------------------------------------------------------------


def test_pack_kernels_honor_interpret_flag(monkeypatch):
    """GemmConfig.interpret must reach the prologue pallas_call like it
    reaches the GEMM kernels: an explicit config wins over the platform
    default (the pack kernels used to hard-default to interpret=True)."""
    seen = {}
    real = dispatch.pack_sign_pallas

    def spy(x, **kw):
        seen["interpret"] = kw.get("interpret")
        return real(x, **kw)

    monkeypatch.setattr(dispatch, "pack_sign_pallas", spy)
    m, k, n = 3, 51, 4  # unique shape: the jit caches cannot satisfy this
    x = _acts(7, m, k)
    wp = bitpack.pack_sign(_acts(8, n, k))
    got = dispatch.quant_gemm(
        x, wp, k_true=k,
        config=GemmConfig(backend="vpu", interpret=True))
    assert seen["interpret"] is True
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.xnor_gemm_ref(bitpack.pack_sign(x), wp, k)))


def test_interpret_follows_platform(monkeypatch):
    """interpret=None: the interpreter on the CPU backend, Mosaic on TPU;
    an explicit bool always wins."""
    from repro.kernels import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    assert resolve_interpret(True) is True
    assert GemmConfig(backend="vpu")._interpret is False


def test_interpret_refuses_other_platforms(monkeypatch):
    """A platform with no Pallas mode raises instead of falling back to
    timing the interpreter."""
    from repro.kernels import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        resolve_interpret()
    with pytest.raises(RuntimeError, match="gpu"):
        GemmConfig(backend="vpu")._interpret
    assert resolve_interpret(False) is False


# ---------------------------------------------------------------------------
# PrologueSpec resolution (Backend.prologue declarations)
# ---------------------------------------------------------------------------


def test_resolve_prologue_per_backend():
    assert dispatch.resolve_prologue("vpu", 1, 1).kind == "pack_sign"
    assert dispatch.resolve_prologue("mxu", 1, 1).kind == "pack_sign"
    assert dispatch.resolve_prologue("xla", 1, 1).kind == "float"
    assert dispatch.resolve_prologue("vpu", 4, 4).kind == "pack_planes"
    assert dispatch.resolve_prologue("xla", 4, 4).kind == "float"
    assert dispatch.resolve_prologue("vpu", 5, 5).kind == "float"  # xla fb
    sh = dispatch.resolve_prologue("shard-vpu", 1, 1)
    assert sh.kind == "pack_sign" and sh.local  # packs inside shard_map
    shn = dispatch.resolve_prologue(
        "shard-vpu", 1, 1, GemmConfig(backend="shard-vpu",
                                      shard_layout="n"))
    assert not shn.local  # "n" packs once and broadcasts
    shk = dispatch.resolve_prologue("shard-vpu", 4, 4)
    assert shk.kind == "pack_planes" and shk.local


def test_prologue_from_spec_layer_path():
    spec = QuantSpec(w_bits=4, a_bits=4)
    p = dispatch.prologue_from_spec(spec, config=GemmConfig(backend="vpu"))
    assert p == PrologueSpec(kind="pack_planes", a_bits=4, fused=True,
                             local=False)
    p2 = dispatch.prologue_from_spec(
        spec, config=GemmConfig(backend="vpu", fused_prologue=False))
    assert not p2.fused


def test_qdense_packed_builds_prologue():
    """The layer path threads a PrologueSpec through QuantGemmCall and
    stays bit-exact with the train path."""
    from repro.core import qlayers

    key = jax.random.PRNGKey(0)
    p = qlayers.dense_init(key, 96, 24)
    x = jax.random.normal(jax.random.PRNGKey(1), (7, 96))
    spec = QuantSpec(w_bits=1, a_bits=1)
    pol = QuantPolicy(w_bits=1, a_bits=1)
    y_train = qlayers.qdense(p, x, spec, compute_dtype=jnp.float32)
    packed, _ = converter.convert({"l": p}, pol)
    for fused in (True, False):
        y_packed = qlayers.qdense(
            packed["l"], x, spec, compute_dtype=jnp.float32,
            gemm_config=GemmConfig(backend="vpu", fused_prologue=fused))
        np.testing.assert_allclose(np.asarray(y_train),
                                   np.asarray(y_packed),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# grouped route-first rule: capacity-dropped rows are never packed
# ---------------------------------------------------------------------------


def test_grouped_capacity_packs_only_bucket_rows(monkeypatch):
    """With a bounding expert_capacity the prologue packs the (E, ec)
    bucket rows — NOT the T sorted rows — so dropped rows never reach the
    pack kernel; without a bound the T rows pack once."""
    rows_seen = []
    real = dispatch.pack_activations

    def spy(x, **kw):
        rows_seen.append(x.shape[0])
        return real(x, **kw)

    monkeypatch.setattr(dispatch, "pack_activations", spy)
    t, k, e, n, ec = 12, 40, 3, 5, 2
    x = _acts(11, t, k)
    w = jax.random.normal(jax.random.PRNGKey(12), (e, n, k), jnp.float32)
    gs = jnp.asarray([6, 3, 3], jnp.int32)
    got = dispatch.quant_gemm_grouped(
        x, bitpack.pack_sign(w), gs, k_true=k,
        config=GemmConfig(backend="vpu"), expert_capacity=ec)
    assert rows_seen == [e * ec]
    rows_seen.clear()
    dispatch.quant_gemm_grouped(
        x, bitpack.pack_sign(w), gs, k_true=k,
        config=GemmConfig(backend="vpu"))
    assert rows_seen == [t]
    # and capacity semantics are unchanged (matches the xla oracle)
    want = dispatch.quant_gemm_grouped(
        x, bitpack.pack_sign(w), gs, k_true=k,
        config=GemmConfig(backend="xla"), expert_capacity=ec)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_grouped_kbit_capacity_matches_oracle():
    """k-bit bucket-packed prologue (route first) against the xla dequant
    oracle, with drops."""
    t, k, e, n, ec, bits = 10, 33, 3, 4, 2, 4
    x = _acts(13, t, k)
    w = jax.random.normal(jax.random.PRNGKey(14), (e, n, k), jnp.float32)
    wp = jnp.moveaxis(
        bitpack.pack_planes(quant.weight_codes(w, bits), bits), 0, 1)
    gs = jnp.asarray([5, 2, 3], jnp.int32)
    got = dispatch.quant_gemm_grouped(
        x, wp, gs, k_true=k, config=GemmConfig(backend="vpu"),
        w_bits=bits, a_bits=bits, expert_capacity=ec)
    want = dispatch.quant_gemm_grouped(
        x, wp, gs, k_true=k, config=GemmConfig(backend="xla"),
        w_bits=bits, a_bits=bits, expert_capacity=ec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# capacity_factor wiring (MoE EP path)
# ---------------------------------------------------------------------------


def test_capacity_factor_reaches_ep_path(mesh_factory, monkeypatch):
    from repro.nn import mlp
    from repro.nn.common import QCtx

    mesh = mesh_factory(2)
    caps = []
    real = mlp._moe_compute_local

    def spy(*args):
        caps.append(args[-1])
        return real(*args)

    monkeypatch.setattr(mlp, "_moe_compute_local", spy)
    cfg = mlp.MoEConfig(d_model=32, d_expert=16, n_routed=4, n_shared=0,
                        top_k=2)
    params = mlp.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    t = 2 * 32  # t * top_k = 128 > the 64-row floor: the factor is visible

    def run(gc):
        ctx = QCtx(policy=QuantPolicy.binary(), compute_dtype=jnp.float32,
                   gemm_config=gc, mesh=mesh)
        return mlp.moe_apply(params, x, cfg, ctx, "layers/0/moe")

    y_def, _ = run(GemmConfig(backend="vpu"))
    assert caps[-1] == min(max(2 * t * cfg.top_k // 2, 64), t * cfg.top_k)
    y_2x, _ = run(GemmConfig(backend="vpu", capacity_factor=2.0))
    assert caps[-1] == caps[0]  # explicit 2.0 == the default
    np.testing.assert_array_equal(np.asarray(y_def), np.asarray(y_2x))
    run(GemmConfig(backend="vpu", capacity_factor=0.5))
    assert caps[-1] == min(max(int(0.5 * t * cfg.top_k) // 2, 64),
                           t * cfg.top_k)
    assert caps[-1] < caps[0]  # a tighter factor shrinks the bucket


# ---------------------------------------------------------------------------
# autotune cache over select_tiles
# ---------------------------------------------------------------------------


def test_autotune_cache_wins_over_heuristic(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TILE_CACHE", str(tmp_path / "tiles.json"))
    monkeypatch.setattr(dispatch, "_TUNED", None)  # fresh cache
    dispatch.select_tiles.cache_clear()
    m, n, kw = 6, 5, 3
    heur = dispatch.select_tiles(m, n, kw, "vpu")
    won = dispatch.autotune_tiles(m, n, kw, "vpu", iters=1)
    assert dispatch.select_tiles(m, n, kw, "vpu") == won
    # other shapes keep the heuristic table
    assert dispatch.select_tiles(64, 64, 64, "vpu") == dispatch.TileConfig(
        64, 64, 64, 64)
    # plane backends tune their OWN kernel (not the 1-bit down-resolution)
    import dataclasses as dc

    be4 = dispatch.get_backend("vpu-k4")
    spied = []

    def spy_kbit(a, b, tiles, cfg):
        spied.append(a.shape[0])
        return dispatch._vpu_kbit_gemm(a, b, tiles, cfg)

    monkeypatch.setitem(dispatch._REGISTRY, "vpu-k4",
                        dc.replace(be4, gemm_kbit=spy_kbit))
    won4 = dispatch.autotune_tiles(4, 4, 2, "vpu-k4", iters=1)
    assert spied and spied[0] == 4  # timed the 4-plane stacks
    assert dispatch.select_tiles(4, 4, 2, "vpu-k4") == won4
    # shard names are rejected (tiles are selected per shard)
    with pytest.raises(ValueError, match="PER-SHARD"):
        dispatch.autotune_tiles(m, n, kw, "shard-vpu")
    # persisted winners reload into a fresh process-level cache
    monkeypatch.setattr(dispatch, "_TUNED", None)
    dispatch.select_tiles.cache_clear()
    assert dispatch.select_tiles(m, n, kw, "vpu") == won
    # GEMMs through an autotuned shape stay exact
    x = _acts(21, m, kw * 32)
    w = jax.random.normal(jax.random.PRNGKey(22), (kw * 32, n), jnp.float32)
    got = dispatch.quant_gemm(x, bitpack.pack_sign(w.T), k_true=kw * 32,
                              config=GemmConfig(backend="vpu"))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.sign_gemm_ref(x, w)))
    del heur
    monkeypatch.setattr(dispatch, "_TUNED", None)
    dispatch.select_tiles.cache_clear()
