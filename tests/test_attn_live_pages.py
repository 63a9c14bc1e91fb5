"""The paged flash-decode kernel's live bound (``live_pages``): a causal row
attends only over the leading table entries its queries can see.

Skipped entries would each have added ``p = 0`` at ``alpha = 1``, so every
row that sees a key is bit-identical to the unbounded call, over ragged
decode and window query tiles, every KV storage tier and split-KV widths
that do and do not divide the table; a row with bound 0 emits zeros; the
bound derived by ``PagedKVCache.attend`` keeps a truncated (speculative
rollback) cache on the gather oracle; a paged scheduler serves the same
tokens with the bound and counts it; the contiguous layout is untouched."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import QuantPolicy
from repro.kernels import attn_decode as AK
from repro.models import lm, registry
from repro.nn import attention as attn_lib
from repro.nn.common import QCtx
from repro.serve.engine import Engine, EngineConfig, Request, Scheduler

KVH, G, DH = 2, 2, 8
SM = DH ** -0.5


def _pool(rng, b, bs, bps, lens, kv_bits):
    """A shuffled pool holding row ``r``'s positions ``[0, lens[r])`` in
    the pages its table maps (the last row's tail pages unmapped), with
    every pool leaf the kernel reads for ``kv_bits``."""
    nb = b * bps
    table = rng.permutation(nb).astype(np.int32).reshape(b, bps)
    table[-1, bps // 2 + 1:] = -1
    pos = np.full((nb, bs), -1, np.int32)
    for r, ln in enumerate(lens):
        for j in range(bps):
            if table[r, j] >= 0:
                p = np.arange(j * bs, (j + 1) * bs)
                pos[table[r, j]] = np.where(p < ln, p, -1)
    k = jnp.asarray(rng.standard_normal((nb, bs, KVH, DH)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((nb, bs, KVH, DH)), jnp.float32)
    leaves = {}
    for name, x in (("k", k), ("v", v)):
        if kv_bits is None:
            leaves[name], leaves[name + "s"] = x.swapaxes(1, 2), None
        else:
            codes, scale = AK.kv_quantize(kv_bits, x)
            leaves[name] = codes.swapaxes(1, 2)
            leaves[name + "s"] = scale.swapaxes(1, 2)
    return jnp.asarray(table), jnp.asarray(pos), leaves


def _visible(table, pool_pos, q_pos):
    """(B, C) whether query ``c`` of row ``b`` sees any key (the causal
    mask over the mapped pages)."""
    kp = np.where(np.asarray(table)[:, :, None] >= 0,
                  np.asarray(pool_pos)[np.maximum(np.asarray(table), 0)], -1)
    kp = kp.reshape(kp.shape[0], -1)
    qp = np.asarray(q_pos)[:, :, None]
    return ((kp[:, None, :] >= 0) & (kp[:, None, :] <= qp)).any(-1)


def _call(table, q, q_pos, pool_pos, leaves, bs, kv_bits, spb, live):
    return AK.flash_decode_paged(
        table, q, q_pos, leaves["k"], leaves["v"], pool_pos, leaves["ks"],
        leaves["vs"], block_size=bs, kv_bits=kv_bits, sm_scale=SM,
        blocks_per_step=spb, live_pages=live)


@pytest.mark.parametrize("kv_bits", [None, 8, 1])
@pytest.mark.parametrize("spb", [1, 4, 3])
@pytest.mark.parametrize("c", [1, 16, 128])
def test_bound_is_bit_identical_on_rows_that_see_a_key(kv_bits, spb, c):
    """Ragged query positions, some rows bounded far below their table:
    every row that sees a key equals the unbounded call bit for bit."""
    bs, bps = (4, 8) if c < 128 else (16, 12)
    cache_len = bs * bps
    lens = [cache_len - 3, 5, cache_len // 2 - 1]
    rng = np.random.default_rng([kv_bits or 0, spb, c])
    table, pool_pos, leaves = _pool(rng, 3, bs, bps, lens, kv_bits)
    starts = [max(0, ln - c) for ln in lens]
    starts[1] = 0  # a window running past the row's content
    q_pos = jnp.asarray([[s + j for j in range(c)] for s in starts],
                        jnp.int32)
    q = jnp.asarray(rng.standard_normal((3, c, KVH, G, DH)), jnp.float32)
    live = attn_lib.live_pages(q_pos, bs, bps)
    assert int(live.min()) < bps  # the bound cuts some row
    bounded = _call(table, q, q_pos, pool_pos, leaves, bs, kv_bits, spb,
                    live)
    full = _call(table, q, q_pos, pool_pos, leaves, bs, kv_bits, spb, None)
    vis = _visible(table, pool_pos, q_pos)
    assert vis.any()
    np.testing.assert_array_equal(np.asarray(bounded)[vis],
                                  np.asarray(full)[vis])


@pytest.mark.parametrize("spb", [1, 4])
def test_row_with_bound_zero_emits_zeros(spb):
    bs, bps = 4, 8
    rng = np.random.default_rng(3 + spb)
    table, pool_pos, leaves = _pool(rng, 3, bs, bps, [20, 9, 13], None)
    q = jnp.asarray(rng.standard_normal((3, 2, KVH, G, DH)), jnp.float32)
    q_pos = jnp.asarray([[-1, -1], [7, 8], [11, 12]], jnp.int32)
    live = attn_lib.live_pages(q_pos, bs, bps)
    np.testing.assert_array_equal(np.asarray(live), [0, 3, 4])
    out = np.asarray(_call(table, q, q_pos, pool_pos, leaves, bs, None, spb,
                           live))
    np.testing.assert_array_equal(out[0], 0.0)
    assert np.abs(out[1]).max() > 0
    # an explicit bound of 0 silences a row that would see keys
    out0 = np.asarray(_call(table, q, q_pos, pool_pos, leaves, bs, None, spb,
                            jnp.asarray([3, 0, 4], jnp.int32)))
    np.testing.assert_array_equal(out0[1], 0.0)
    np.testing.assert_array_equal(out0[2], out[2])


def test_live_pages_formula():
    q_pos = np.array([[0, 3], [4, 4], [-1, -1], [5, 200], [15, 16]])
    np.testing.assert_array_equal(attn_lib.live_pages(q_pos, 4, 8),
                                  [1, 2, 0, 8, 5])
    np.testing.assert_array_equal(
        np.asarray(attn_lib.live_pages(jnp.asarray(q_pos), 4, 8)),
        [1, 2, 0, 8, 5])


@pytest.mark.parametrize("kv_bits", [None, 8, 1])
def test_truncated_cache_matches_gather_oracle(kv_bits):
    """Speculative rollback: truncated rows keep ``pool_pos = -1`` inside
    their live range, so the bounded kernel still agrees with the gather
    oracle (and the bound really cuts the table here)."""
    bs, cache_len, lens = 4, 32, [20, 11]
    cfg = attn_lib.AttnConfig(d_model=4 * DH, n_heads=4, n_kv_heads=KVH,
                              d_head=DH, fused_attn=True)
    kv = attn_lib.PagedKVCache(block_size=bs, kv_bits=kv_bits)
    rng = np.random.default_rng([kv_bits or 0, 7])
    cache = kv.init(2, cfg, cache_len, jnp.float32)
    bps = cache_len // bs
    cache["table"] = jnp.asarray(
        rng.permutation(2 * bps).astype(np.int32).reshape(2, bps))
    for ln in sorted(set(lens)):
        k = jnp.asarray(rng.standard_normal((2, ln, KVH, DH)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, ln, KVH, DH)), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(ln, dtype=jnp.int32), (2, ln))
        cache = kv.fill_window(cache, k, v, pos,
                               jnp.asarray([x == ln for x in lens]))
    keep = jnp.asarray([9, 6], jnp.int32)
    cache = kv.truncate(cache, keep)
    # a verify window of 3 at the rolled-back frontier
    k = jnp.asarray(rng.standard_normal((2, 3, KVH, DH)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 3, KVH, DH)), jnp.float32)
    q_pos = keep[:, None] + jnp.arange(3, dtype=jnp.int32)[None, :]
    cache = kv.fill_window(cache, k, v, q_pos)
    q = jnp.asarray(rng.standard_normal((2, 3, KVH, G, DH)), jnp.float32)
    assert int(attn_lib.live_pages(q_pos, bs, bps).max()) < bps
    fused = kv.attend(cache, q, q_pos, cfg)
    kk, vv, spos = kv.gather(cache)
    mask = attn_lib._mask(cfg, q_pos, spos)
    ref = attn_lib._sdpa(cfg, q, kk, vv, mask)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=0, atol=2e-5)


def _smoke_engine():
    spec = registry.get("granite-3-2b")
    ctx = QCtx(policy=QuantPolicy.full_precision(), compute_dtype=jnp.float32)
    params = lm.init(jax.random.PRNGKey(0), spec.smoke)
    return Engine(spec, spec.smoke, ctx, params, EngineConfig(
        batch=2, cache_len=32, max_new_tokens=4, kv_block_size=4,
        prefill_chunk=5, fused_attn=True))


def _serve(eng, prompts):
    sched = Scheduler(eng)
    for p in prompts:
        sched.submit(Request(prompt=p))
    return sched.run(), sched


def _spied():
    """A smoke engine whose window and decode programs record, per call,
    the query positions and the logits of the rows they serve."""
    eng, calls = _smoke_engine(), []
    window, decode = eng._window, eng._decode

    def record(q_pos, wm, out):
        calls.append((q_pos, np.asarray(out[0])[np.asarray(wm)]))
        return out

    eng._window = lambda params, cache, tokens, pos_start, wm: record(
        np.asarray(pos_start)[:, None] + np.arange(tokens.shape[1]), wm,
        window(params, cache, tokens, pos_start, wm))
    eng._decode = lambda params, cache, tokens, pos, wm: record(
        np.asarray(pos)[:, None], wm, decode(params, cache, tokens, pos, wm))
    return eng, calls


def test_scheduler_tokens_unchanged_and_pages_counted(monkeypatch, tmp_path):
    """The bounded kernel serves the tokens, and the logits of every served
    row, that the unbounded one does; the scheduler's counters equal the
    bound summed over the programs it dispatched, and the window and
    decode spans carry the same args."""
    from jax.profiler import ProfileData, ProfileOptions

    rng = np.random.default_rng(5)
    eng, calls = _spied()
    prompts = [rng.integers(0, eng.cfg.vocab_size, (n,)).astype(np.int32)
               for n in (7, 3, 12)]
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        bounded, sched = _serve(eng, prompts)
    finally:
        jax.profiler.stop_trace()
    st, bps = sched.stats, sched.bps
    assert len(calls) == st.prefills + st.steps
    want = sum(int(attn_lib.live_pages(q, 4, bps).sum()) for q, _ in calls)
    assert st.attn_pages_live == want
    assert st.attn_pages_table == 2 * bps * len(calls)
    assert 0 < st.attn_pages_live < st.attn_pages_table

    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    args = [dict(e.stats) for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host")
            for line in plane.lines for e in line.events
            if e.name in ("sched.window", "sched.decode")]
    assert len(args) == len(calls)
    assert sum(a["live_pages"] for a in args) == st.attn_pages_live
    assert sum(a["table_pages"] for a in args) == st.attn_pages_table

    # the unbounded kernel: every table entry of every row
    monkeypatch.setattr(attn_lib, "live_pages",
                        lambda q_pos, bs, bps: jnp.full(
                            (q_pos.shape[0],), bps, jnp.int32))
    eng_full, calls_full = _spied()
    full, _ = _serve(eng_full, prompts)
    assert bounded.keys() == full.keys()
    for rid in full:
        np.testing.assert_array_equal(bounded[rid], full[rid])
    assert len(calls_full) == len(calls)
    for (_, a), (_, b) in zip(calls, calls_full):
        np.testing.assert_array_equal(a, b)


def test_contiguous_layout_passes_no_bound(monkeypatch):
    """``flash_decode_contig`` hands the kernel no bound (ring and local
    slabs do not hold positions in page order), and its output equals the
    paged kernel over the same slab as an arange-table pool."""
    rng = np.random.default_rng(9)
    b, l, t = 2, 16, 4
    q = jnp.asarray(rng.standard_normal((b, 1, KVH, G, DH)), jnp.float32)
    q_pos = jnp.asarray([[5], [12]], jnp.int32)
    k = jnp.asarray(rng.standard_normal((b, l, KVH, DH)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, KVH, DH)), jnp.float32)
    pos = jnp.asarray(np.where(np.arange(l) < np.array([[9], [14]]),
                               np.arange(l), -1), jnp.int32)
    seen = []
    paged = AK.flash_decode_paged

    def spy(*a, **kw):
        seen.append(kw.get("live_pages"))
        return paged(*a, **kw)

    monkeypatch.setattr(AK, "flash_decode_paged", spy)
    out = AK.flash_decode_contig(q, q_pos, k, v, pos, sm_scale=SM,
                                 kv_tile=t)
    assert seen == [None]
    nt = l // t
    table = jnp.arange(b * nt, dtype=jnp.int32).reshape(b, nt)
    ref = paged(table, q, q_pos,
                k.reshape(b * nt, t, KVH, DH).swapaxes(1, 2),
                v.reshape(b * nt, t, KVH, DH).swapaxes(1, 2),
                pos.reshape(b * nt, t), block_size=t, sm_scale=SM,
                blocks_per_step=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
