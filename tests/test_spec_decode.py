"""Speculative decoding (PR 9): greedy spec-mode serving must be
TOKEN-IDENTICAL to target-only greedy serving — for ANY draft, any
spec_len, both KV layouts — because the target's own windowed greedy
picks gate every emission (serve/engine.py module docstring has the
invariants).  Plus units for the rollback primitives it rides on:
``KVCache.truncate`` on both layouts, ``ContiguousKVCache.fill_window``
(the one-hot scatter-free window write), and ``BlockAllocator.trim``
(tail release drains back, double release stays loud)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import converter
from repro.core.policy import QuantPolicy
from repro.models import lm, registry
from repro.nn import attention as attn_lib
from repro.nn.common import QCtx
from repro.serve.engine import (BlockAllocator, DraftModel, Engine,
                                EngineConfig, Request, Scheduler)

SPEC = registry.get("granite-3-2b")
CFG = SPEC.smoke
CTX = QCtx(policy=QuantPolicy.full_precision(), compute_dtype=jnp.float32)

_cache: dict = {}


def _params():
    if "params" not in _cache:
        _cache["params"] = lm.init(jax.random.PRNGKey(0), CFG)
    return _cache["params"]


def _draft(kind: str) -> DraftModel:
    """'slice': 1-layer float slice of the target (high agreement);
    'same': the target itself (forced accept — proposals ARE the target's
    greedy picks); 'doomed': embed-table-zeroed slice (constant logits ->
    always proposes token 0: forced reject almost every round)."""
    key = ("draft", kind)
    if key in _cache:
        return _cache[key]
    if kind == "same":
        dm = DraftModel(cfg=CFG, params=_params(), ctx=CTX)
    else:
        host = jax.tree.map(np.asarray, _params())
        dp, dcfg, _ = converter.derive_draft(
            host, CFG, n_layers=1, policy=QuantPolicy.full_precision(),
            keep_float=True)
        dp = jax.tree.map(jnp.asarray, dp)
        if kind == "doomed":
            # a zero (tied) embedding table makes every logit identical,
            # so greedy always proposes token 0 — maximally wrong against
            # a target whose picks are almost never 0
            dp = dict(dp, embed=jax.tree.map(lambda a: a * 0, dp["embed"]))
        dm = DraftModel(cfg=dcfg, params=dp, ctx=CTX)
    _cache[key] = dm
    return dm


def _engine(draft=None, spec_len=2, paged=False, batch=2, new_tokens=6):
    key = ("eng", id(draft), spec_len, paged, batch, new_tokens)
    if key not in _cache:
        kw = dict(batch=batch, cache_len=64, max_new_tokens=new_tokens)
        if paged:
            kw.update(kv_block_size=8, prefill_chunk=4)
        _cache[key] = Engine(SPEC, CFG, CTX, _params(),
                             EngineConfig(**kw, draft=draft,
                                          spec_len=spec_len))
    return _cache[key]


def _run(eng, lens, seed=0):
    rng = np.random.default_rng(seed)
    sched = Scheduler(eng)
    for i, ln in enumerate(lens):
        sched.submit(Request(prompt=rng.integers(
            0, CFG.vocab_size, (ln,)).astype(np.int32), rid=i))
    return sched.run(), sched.stats


# ---------------------------------------------------------------------------
# scheduler-level identity
# ---------------------------------------------------------------------------


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10 ** 6),
       spec_len=st.integers(min_value=1, max_value=4))
def test_spec_greedy_identical_ragged(seed, spec_len):
    """Property sweep: ragged prompt lengths through slot recycling, any
    spec_len — the speculative stream equals the target-only stream
    exactly."""
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(2, 9)) for _ in range(4)]
    ref, _ = _run(_engine(), lens, seed=seed)
    got, stats = _run(_engine(draft=_draft("slice"), spec_len=spec_len),
                      lens, seed=seed)
    assert set(got) == set(ref)
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])
    assert stats.spec_rounds > 0
    assert stats.spec_proposed == spec_len * stats.spec_rounds


@pytest.mark.parametrize("spec_len", [1, 3])
def test_spec_greedy_identical_paged(spec_len):
    """Paged engine (block tables + chunked prefill): same identity; the
    per-row rollback releases visibility through pool_pos, never blocks
    (allocation stays full-table for the slot's lifetime)."""
    lens = [3, 7, 5, 6]
    ref, _ = _run(_engine(paged=True), lens)
    got, stats = _run(_engine(draft=_draft("slice"), spec_len=spec_len,
                              paged=True), lens)
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])
    assert stats.spec_rounds > 0


def test_spec_forced_accept_all():
    """Draft == target: every proposal matches the target's greedy pick,
    acceptance is exactly 1.0, and each round emits spec_len + 1 tokens
    (the free rides show up as fewer verify steps than target-only decode
    steps)."""
    lens = [5, 5]
    ref, ref_stats = _run(_engine(), lens)
    got, stats = _run(_engine(draft=_draft("same"), spec_len=3), lens)
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])
    assert stats.acceptance_rate == 1.0
    assert stats.steps < ref_stats.steps


def test_spec_forced_reject_rolls_back():
    """A doomed draft (constant logits -> always proposes token 0) forces
    a rollback nearly every round; the output must STILL be identical —
    the target's pick after the first rejection rides along, so progress
    is one token per round, never zero."""
    lens = [4, 6]
    ref, _ = _run(_engine(), lens)
    got, stats = _run(_engine(draft=_draft("doomed"), spec_len=2), lens)
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])
    assert stats.acceptance_rate < 0.5
    # token 0 can legitimately be the target's pick sometimes, but a
    # constant proposer must not look like a good one
    assert stats.spec_accepted < stats.spec_proposed


def test_spec_telemetry_per_token_times():
    """Satellite: per-request TTFT/TPOT lists cover every emitted token
    (t_tokens has one stamp per token, TPOT count = tokens - requests)."""
    lens = [4, 5, 6]
    got, stats = _run(_engine(draft=_draft("slice"), spec_len=2,
                              batch=2), lens)
    n_tok = sum(len(v) for v in got.values())
    assert sum(len(v) for v in stats.t_tokens.values()) == n_tok
    assert len(stats.ttfts()) == len(lens)
    assert len(stats.tpots()) == n_tok - len(lens)
    assert all(b >= a for v in stats.t_tokens.values()
               for a, b in zip(v, v[1:]))


def test_spec_validation():
    """Greedy-only, cache headroom, spec_len >= 1 — all loud."""
    dm = _draft("slice")
    with pytest.raises(ValueError, match="greedy-only"):
        Engine(SPEC, CFG, CTX, _params(),
               EngineConfig(batch=2, cache_len=64, max_new_tokens=4,
                            temperature=0.7, draft=dm))
    with pytest.raises(ValueError, match="spec_len"):
        Engine(SPEC, CFG, CTX, _params(),
               EngineConfig(batch=2, cache_len=64, max_new_tokens=4,
                            draft=dm, spec_len=0))
    eng = _engine(draft=dm, spec_len=2)
    sched = Scheduler(eng)
    sched.submit(Request(prompt=np.zeros((60,), np.int32), rid=0))
    with pytest.raises(ValueError, match="cache_len"):
        sched.run()  # 60 + 6 + 2 > 64: the verify window would overflow


def test_derive_draft_bounds():
    host = jax.tree.map(np.asarray, _params())
    dp, dcfg, report = converter.derive_draft(host, CFG)
    assert dcfg.n_layers == max(1, CFG.n_layers // 4)
    assert len(dp["layers"]) == dcfg.n_layers
    assert report.n_packed > 0  # the default policy binarizes the slice
    with pytest.raises(ValueError, match="n_layers"):
        converter.derive_draft(host, CFG, n_layers=CFG.n_layers + 1)
    with pytest.raises(ValueError, match="n_layers"):
        converter.derive_draft(host, CFG, n_layers=0)


# ---------------------------------------------------------------------------
# rollback / window-write primitives
# ---------------------------------------------------------------------------

_ACFG = attn_lib.AttnConfig(d_model=16, n_heads=2, n_kv_heads=2, d_head=8)


def _rand_kv(rng, b, s):
    k = jnp.asarray(rng.standard_normal((b, s, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, 2, 8)), jnp.float32)
    return k, v


def test_contiguous_fill_window_matches_sequential_fill():
    """fill_window (one-hot 0/1-coefficient einsum write) == one fill per
    position, bit-for-bit, at per-row window starts."""
    rng = np.random.default_rng(0)
    kv = attn_lib.CONTIGUOUS
    b, s, cache_len = 3, 4, 16
    k, v = _rand_kv(rng, b, s)
    starts = np.asarray([0, 5, 11], np.int32)
    positions = jnp.asarray(starts[:, None] + np.arange(s)[None, :])
    wm = jnp.asarray([True, True, False])
    base = kv.init(b, _ACFG, cache_len, jnp.float32)
    got = kv.fill_window(base, k, v, positions, write_mask=wm)
    want = base
    for c in range(s):
        want = kv.fill(want, k[:, c:c + 1], v[:, c:c + 1],
                       positions[:, c:c + 1], write_mask=wm)
    for key in ("k", "v", "slot_pos"):
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]))
    # masked row wrote nothing
    assert (np.asarray(got["slot_pos"][2]) == -1).all()


def test_contiguous_truncate():
    """truncate flips slot_pos >= lengths back to empty (-1); the stale
    k/v bytes are unreachable (attention masks on slot_pos) and the next
    fill overwrites them."""
    rng = np.random.default_rng(1)
    kv = attn_lib.CONTIGUOUS
    b, s = 2, 6
    k, v = _rand_kv(rng, b, s)
    cache = kv.init(b, _ACFG, 16, jnp.float32)
    cache = kv.fill_window(
        cache, k, v, jnp.asarray(np.tile(np.arange(s), (b, 1))))
    out = kv.truncate(cache, jnp.asarray([4, 1 << 30], jnp.int32))
    sp = np.asarray(out["slot_pos"])
    assert set(sp[0][sp[0] >= 0]) == {0, 1, 2, 3}
    assert set(sp[1][sp[1] >= 0]) == set(range(s))  # NO_TRUNC row intact
    np.testing.assert_array_equal(np.asarray(out["k"]),
                                  np.asarray(cache["k"]))


def test_paged_truncate_shared_block_safe():
    """Paged truncate is visibility-only (pool_pos), and a block SHARED
    by two slots survives one holder's rollback: both holders' lengths
    exceed every shared position, so the scatter writes back identical
    bytes."""
    rng = np.random.default_rng(2)
    kv = attn_lib.PagedKVCache(block_size=4)
    b, cache_len = 2, 16
    cache = kv.init(b, _ACFG, cache_len, jnp.float32)
    # slot 0 -> blocks [0,1,2,3]; slot 1 -> [0,5,6,7] (block 0 shared)
    cache["table"] = jnp.asarray([[0, 1, 2, 3], [0, 5, 6, 7]], jnp.int32)
    s = 10
    k, v = _rand_kv(rng, b, s)
    pos = jnp.asarray(np.tile(np.arange(s), (b, 1)))
    cache = kv.fill(cache, k, v, pos, write_mask=jnp.asarray([True, True]))
    out = kv.truncate(cache, jnp.asarray([6, 1 << 30], jnp.int32))
    pool = np.asarray(out["pool_pos"])
    # slot 0's tail (pos 6..9, blocks 1-2) is released to -1 ...
    assert (pool[1][2:] == -1).all() and (pool[2][:2] == -1).all()
    # ... the shared block 0 (pos 0..3, < both lengths) is untouched ...
    np.testing.assert_array_equal(pool[0], np.arange(4))
    # ... and slot 1's view (through blocks 5,6) is fully intact
    np.testing.assert_array_equal(pool[5], np.arange(4, 8))
    np.testing.assert_array_equal(pool[6], np.asarray([8, 9, -1, -1]))


def test_paged_truncate_then_refill_bit_identical():
    """Rolling back and re-writing the same tokens reproduces the exact
    cache bytes — the property the spec rollback relies on."""
    rng = np.random.default_rng(3)
    kv = attn_lib.PagedKVCache(block_size=4)
    cache = kv.init(1, _ACFG, 12, jnp.float32)
    cache = {**cache, "table": jnp.asarray([[0, 1, 2]], jnp.int32)}
    k, v = _rand_kv(rng, 1, 8)
    pos = jnp.arange(8)[None, :]
    wm = jnp.asarray([True])
    full = kv.fill(cache, k, v, pos, write_mask=wm)
    rolled = kv.truncate(full, jnp.asarray([5], jnp.int32))
    refill = kv.fill(rolled, k[:, 5:], v[:, 5:], pos[:, 5:],
                     write_mask=wm)
    for key in ("pool_k", "pool_v", "pool_pos"):
        np.testing.assert_array_equal(np.asarray(refill[key]),
                                      np.asarray(full[key]))


def test_block_allocator_trim():
    """trim releases exactly the tail references: freed blocks drain back
    to the pool, kept blocks stay live, and releasing the same tail twice
    is a loud error (the caller adopted the kept prefix)."""
    alloc = BlockAllocator(num_blocks=6, block_size=4)
    blocks = [alloc.alloc() for _ in range(4)]
    assert alloc.live_blocks == 4
    kept = alloc.trim(blocks, 2)
    assert kept == blocks[:2]
    assert alloc.live_blocks == 2
    with pytest.raises(RuntimeError, match="double release"):
        alloc.release(blocks[2])  # tail ref already dropped by trim
    # the freed tail is allocatable again
    again = [alloc.alloc() for _ in range(4)]
    assert set(again) >= set(blocks[2:])
    # shared-tail trim: a refcounted block survives the first holder
    alloc2 = BlockAllocator(num_blocks=4, block_size=4)
    blk = alloc2.alloc()
    alloc2.refs[blk] += 1  # second holder (prefix sharing)
    assert alloc2.trim([blk], 0) == []
    assert alloc2.live_blocks == 1  # still held by the survivor
    alloc2.release(blk)
    assert alloc2.live_blocks == 0
