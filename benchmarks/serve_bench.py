"""Continuous-batching serving benchmark: the scheduler vs the fixed-batch
decode loop over the same jitted prefill/decode steps.

Scenario (the daBNN-style serving-framework argument): a queue of
mixed-prompt-length requests where HALF finish early — eos at 25% of
``max_new_tokens`` (per-request ``eos_id`` + ``min_tokens`` pins the stop
deterministically).  Two execution modes:

* **fixed-batch** — the legacy engine semantics: rectangular batches only,
  so requests group by prompt length (batch width = group size), and every
  batch decodes the full ``max_new_tokens`` horizon regardless of eos.
  Useful tokens are truncated at eos after the fact.
* **continuous** — ``Scheduler.run``: one shape-static decode batch, slots
  recycle the step a request hits eos/budget, queued requests admit into
  freed slots, and the loop exits when queue+batch drain.

Rows:

* ``equivalence`` — continuous greedy tokens are IDENTICAL per request to
  the per-request fixed-batch engine (batch=1 ``Engine.generate``,
  truncated by the same eos/min_tokens rule).  Carries ``exact_match`` —
  the CI bench-smoke job gates on it (--fail-on-mismatch).  One row runs
  float, one runs a BMXNet-converted packed checkpoint (xla backend:
  packed weights, in-graph dequant — CPU-fast) so the gate covers the
  packed serving path end-to-end.
* ``equivalence`` / ``engine=paged`` — the SAME request set served on the
  block-table paged KV pool with chunked prefill + prefix sharing
  (``EngineConfig.kv_block_size``): greedy streams must stay bit-identical
  to the per-request reference.  Also CI-gated via ``exact_match``.
* ``equivalence`` / ``engine=fused-attn[-paged]`` — the same request set
  decoded through the Pallas flash-decode kernel
  (``EngineConfig.fused_attn``; kernels/attn_decode.py) on both KV
  layouts, fp KV storage: greedy streams must be IDENTICAL per request
  to the reference.  CI-gated via ``exact_match`` — the serve half of
  the fused kernel's gate (the ``attn`` family gates numeric allclose).
* ``shared-prefix`` — an identical-prefix request stream on the paged
  engine with sharing off vs on: prefill work must drop by EXACTLY
  ``(requests - batch) * prefix_len`` tokens (every request after the
  first admission wave reuses the registered prefix blocks) with
  bit-identical streams; both checks fold into the gated ``exact_match``.
* ``throughput`` — useful tokens/sec both modes, speedup, decode-step
  counts, and TTFT/TPOT telemetry (mean + p50/p95 from the scheduler's
  per-token emission timestamps).  Fixed-batch TTFT is measured at
  group START (a lower bound, i.e. favouring the baseline).  The ISSUE
  acceptance bar: speedup >= 1.5x with half the requests stopping at 25%.
* ``spec-equivalence`` — the speculative scheduler (w1a1 packed draft from
  ``converter.derive_draft`` over a deeper float target) must stream
  tokens BIT-IDENTICAL to the per-request reference: greedy spec output
  never depends on draft quality, only the acceptance rate does.
  CI-gated via ``exact_match``.
* ``spec-throughput`` — useful tok/s of speculative vs plain continuous
  batching on the same request set, plus acceptance rate, verify-call
  counts, and p50/p95 TPOT both modes.  The draft here is the float
  depth-slice (high agreement on the random-init smoke checkpoint —
  a random-weight w1a1 draft proposes near-noise, which costs rounds
  without accepted runs); with spec_len=2 the measured useful-tok/s
  beats non-spec continuous batching.  Identity vs the non-spec streams
  folds into the gated ``exact_match``.

Timing notes: both modes are warmed (jit) before the timed pass; the fp
smoke model is tiny so CPU numbers are call-count dominated — which is
exactly what the scheduler improves (fewer, fuller decode steps).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import converter
from repro.core.policy import QuantPolicy
from repro.kernels.dispatch import GemmConfig
from repro.models import lm, registry
from repro.nn.common import QCtx
from repro.serve.engine import (DraftModel, Engine, EngineConfig, Request,
                                Scheduler)


def _pct(xs, q) -> float:
    """Percentile in milliseconds, 0.0 for an empty sample."""
    return round(float(np.percentile(xs, q)) * 1e3, 2) if len(xs) else 0.0


def _expected_stream(full: np.ndarray, eos_id: int | None,
                     min_tokens: int) -> np.ndarray:
    """Apply the scheduler's retirement rule to a full-horizon stream."""
    if eos_id is None:
        return full
    for idx, t in enumerate(full):
        if idx + 1 >= min_tokens and int(t) == int(eos_id):
            return full[:idx + 1]
    return full


def _build(arch: str, policy, batch: int, cache_len: int, max_new: int,
           backend: str | None = None, packed: bool = False):
    spec = registry.get(arch)
    cfg = spec.smoke
    gc = GemmConfig(backend=backend) if backend else GemmConfig()
    ctx = QCtx(policy=policy, compute_dtype=jnp.float32, gemm_config=gc)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    if packed:
        host = jax.tree.map(np.asarray, params)
        params, rep = converter.convert(host, policy)
        assert rep.n_packed > 0
        params = jax.tree.map(jnp.asarray, params)
    ecfg = EngineConfig(batch=batch, cache_len=cache_len,
                        max_new_tokens=max_new)
    return spec, cfg, ctx, params, Engine(spec, cfg, ctx, params, ecfg)


def _requests(cfg, lens, max_new, ref_engine, rng):
    """One (early, late) request pair per prompt length.  Early requests
    stop via eos at 25% of max_new (eos_id = the reference stream's token
    there, min_tokens pins the trigger position); late requests run the
    full budget.  Returns (requests in interleaved submission order,
    {rid: expected tokens})."""
    k = max(1, max_new // 4)
    reqs, expected = [], {}
    rid = 0
    for length in lens:
        for early in (False, True):
            prompt = rng.integers(0, cfg.vocab_size, (length,)).astype(
                np.int32)
            full = ref_engine.generate(prompt[None])[0]
            eos = int(full[k - 1]) if early else None
            min_tok = k if early else 0
            reqs.append(Request(prompt=prompt, rid=rid, eos_id=eos,
                                min_tokens=min_tok))
            expected[rid] = _expected_stream(full, eos, min_tok)
            rid += 1
    return reqs, expected


def _run_continuous(engine, reqs):
    sched = Scheduler(engine)
    for r in reqs:
        sched.submit(Request(prompt=r.prompt, rid=r.rid, eos_id=r.eos_id,
                             min_tokens=r.min_tokens,
                             max_new_tokens=r.max_new_tokens))
    t0 = time.perf_counter()
    results = sched.run()
    dt = time.perf_counter() - t0
    return results, dt, sched.stats


def _run_fixed(fixed_engine, reqs, expected):
    """Legacy semantics: group by prompt length (rectangular batches of
    the fixed engine's width), full horizon each, truncate at eos after.
    Returns (wall seconds, useful tokens, per-request ttft lower bounds,
    decode steps)."""
    width = fixed_engine.ecfg.batch
    by_len: dict[int, list[Request]] = {}
    for r in reqs:
        by_len.setdefault(len(r.prompt), []).append(r)
    groups = []
    for _, rs in sorted(by_len.items()):
        for i in range(0, len(rs), width):
            groups.append(rs[i:i + width])
    t0 = time.perf_counter()
    useful, ttfts, steps = 0, [], 0
    for g in groups:
        t_start = time.perf_counter() - t0
        out = fixed_engine.generate(np.stack([r.prompt for r in g]))
        steps += fixed_engine.ecfg.max_new_tokens - 1
        for row, r in zip(out, g):
            np.testing.assert_array_equal(
                row[:len(expected[r.rid])], expected[r.rid])
            useful += len(expected[r.rid])
            ttfts.append(t_start)
    return time.perf_counter() - t0, useful, ttfts, steps


def rows(small: bool = False):
    rng = np.random.default_rng(0)
    max_new = 16 if small else 32
    lens = (4, 6, 8, 10) if small else (4, 6, 8, 10, 12, 14, 16, 18)
    cache_len = 32 if small else 64
    batch = 4

    # float engines: continuous (4 slots), fixed baseline (width 2 = the
    # per-length group size), per-request reference (batch=1)
    _, cfg, _, _, eng_cont = _build("granite-3-2b",
                                    QuantPolicy.full_precision(),
                                    batch, cache_len, max_new)
    eng_ref = Engine(eng_cont.spec, eng_cont.cfg, eng_cont.ctx,
                     eng_cont.params,
                     EngineConfig(batch=1, cache_len=cache_len,
                                  max_new_tokens=max_new))
    eng_fixed = Engine(eng_cont.spec, eng_cont.cfg, eng_cont.ctx,
                       eng_cont.params,
                       EngineConfig(batch=2, cache_len=cache_len,
                                    max_new_tokens=max_new))

    reqs, expected = _requests(cfg, lens, max_new, eng_ref, rng)

    # -- equivalence (float): continuous == per-request fixed, exactly --
    results, _, _ = _run_continuous(eng_cont, reqs)
    mismatch = [r.rid for r in reqs
                if not np.array_equal(results[r.rid], expected[r.rid])]
    yield {
        "mode": "equivalence", "engine": "float", "requests": len(reqs),
        "batch": batch, "max_new": max_new,
        "mismatches": len(mismatch),
        "exact_match": not mismatch,
    }

    # -- equivalence (packed, xla backend): the deployment-mode engine --
    pk_max_new = 6
    _, pcfg, _, _, pk_cont = _build(
        "granite-3-2b", QuantPolicy.binary(), 2, 24, pk_max_new,
        backend="xla", packed=True)
    pk_ref = Engine(pk_cont.spec, pk_cont.cfg, pk_cont.ctx, pk_cont.params,
                    EngineConfig(batch=1, cache_len=24,
                                 max_new_tokens=pk_max_new))
    pk_reqs, pk_expected = _requests(pcfg, (4, 5), pk_max_new, pk_ref, rng)
    pk_results, _, _ = _run_continuous(pk_cont, pk_reqs)
    pk_mismatch = [r.rid for r in pk_reqs
                   if not np.array_equal(pk_results[r.rid],
                                         pk_expected[r.rid])]
    yield {
        "mode": "equivalence", "engine": "packed-xla",
        "requests": len(pk_reqs), "batch": 2, "max_new": pk_max_new,
        "mismatches": len(pk_mismatch),
        "exact_match": not pk_mismatch,
    }

    # -- equivalence (paged): block-table pool + chunked prefill + prefix
    # sharing vs the SAME per-request fixed-batch reference streams --
    eng_paged = Engine(eng_cont.spec, eng_cont.cfg, eng_cont.ctx,
                       eng_cont.params,
                       EngineConfig(batch=batch, cache_len=cache_len,
                                    max_new_tokens=max_new,
                                    kv_block_size=8, prefill_chunk=5,
                                    shared_prefix=True))
    pg_results, _, pg_stats = _run_continuous(eng_paged, reqs)
    pg_mismatch = [r.rid for r in reqs
                   if not np.array_equal(pg_results[r.rid],
                                         expected[r.rid])]
    yield {
        "mode": "equivalence", "engine": "paged", "requests": len(reqs),
        "batch": batch, "max_new": max_new, "kv_block_size": 8,
        "prefill_chunk": 5,
        "prefill_tokens": pg_stats.prefill_tokens,
        "shared_tokens": pg_stats.shared_tokens,
        "mismatches": len(pg_mismatch),
        "exact_match": not pg_mismatch,
    }

    # -- equivalence (fused-attn): the SAME request set decoded through
    # the Pallas flash-decode kernel (kernels/attn_decode.py) instead of
    # gather + masked-sdpa, on both KV layouts.  fp KV storage, so greedy
    # streams must be IDENTICAL per request to the reference — the serve
    # half of the fused kernel's CI gate (the attn bench family gates the
    # numeric allclose) --
    for label, extra in (("fused-attn", {}),
                         ("fused-attn-paged",
                          {"kv_block_size": 8, "prefill_chunk": 5})):
        eng_fused = Engine(eng_cont.spec, eng_cont.cfg, eng_cont.ctx,
                           eng_cont.params,
                           EngineConfig(batch=batch, cache_len=cache_len,
                                        max_new_tokens=max_new,
                                        fused_attn=True, **extra))
        fa_results, _, _ = _run_continuous(eng_fused, reqs)
        fa_mismatch = [r.rid for r in reqs
                       if not np.array_equal(fa_results[r.rid],
                                             expected[r.rid])]
        yield {
            "mode": "equivalence", "engine": label, "requests": len(reqs),
            "batch": batch, "max_new": max_new,
            "mismatches": len(fa_mismatch),
            "exact_match": not fa_mismatch,
        }

    # -- shared-prefix throughput: identical-prefix stream, paged engine
    # with and without sharing.  Every request after the first admission
    # wave reuses the prefix's full blocks, so prefill work must drop by
    # exactly (requests - batch) * prefix_len tokens — gated alongside
    # stream identity --
    sp_batch, sp_new, sp_bs, prefix_len, n_sp = 2, 8, 8, 16, 6
    prefix = rng.integers(0, cfg.vocab_size, (prefix_len,)).astype(np.int32)
    sp_reqs = []
    for i in range(n_sp):
        suffix = rng.integers(0, cfg.vocab_size, (1 + i,)).astype(np.int32)
        sp_reqs.append(Request(prompt=np.concatenate([prefix, suffix]),
                               rid=i))

    def _sp_engine(share):
        return Engine(eng_cont.spec, eng_cont.cfg, eng_cont.ctx,
                      eng_cont.params,
                      EngineConfig(batch=sp_batch, cache_len=cache_len,
                                   max_new_tokens=sp_new,
                                   kv_block_size=sp_bs,
                                   shared_prefix=share))

    base_res, _, base_stats = _run_continuous(_sp_engine(False), sp_reqs)
    sh_res, _, sh_stats = _run_continuous(_sp_engine(True), sp_reqs)
    # warmed second passes for the timing comparison
    _, base_dt, _ = _run_continuous(_sp_engine(False), sp_reqs)
    _, sh_dt, _ = _run_continuous(_sp_engine(True), sp_reqs)
    identical = all(np.array_equal(base_res[i], sh_res[i])
                    for i in range(n_sp))
    saved = base_stats.prefill_tokens - sh_stats.prefill_tokens
    expected_saved = (n_sp - sp_batch) * prefix_len
    yield {
        "mode": "shared-prefix", "requests": n_sp, "batch": sp_batch,
        "kv_block_size": sp_bs, "prefix_len": prefix_len,
        "prefill_tokens_unshared": base_stats.prefill_tokens,
        "prefill_tokens_shared": sh_stats.prefill_tokens,
        "prefill_tokens_saved": saved,
        "expected_saved": expected_saved,
        "shared_tok_s_ratio": round(base_dt / sh_dt, 2),
        "exact_match": identical and saved == expected_saved
        and sh_stats.shared_tokens == expected_saved,
    }

    # -- throughput: fixed-batch vs continuous, half stopping at 25% --
    _run_fixed(eng_fixed, reqs, expected)  # warm the fixed engine's jits
    fx_dt, fx_useful, fx_ttfts, fx_steps = _run_fixed(
        eng_fixed, reqs, expected)
    # the equivalence pass above warmed the continuous engine's jits
    results, ct_dt, stats = _run_continuous(eng_cont, reqs)
    ct_useful = sum(len(v) for v in results.values())
    assert ct_useful == fx_useful, (ct_useful, fx_useful)
    fx_tps = fx_useful / fx_dt
    ct_tps = ct_useful / ct_dt
    yield {
        "mode": "throughput", "requests": len(reqs), "batch": batch,
        "max_new": max_new, "early_finish_frac": 0.5, "eos_at_frac": 0.25,
        "useful_tokens": ct_useful,
        "fixed_decode_steps": fx_steps,
        "cont_decode_steps": stats.steps,
        "fixed_tok_s": round(fx_tps, 1),
        "cont_tok_s": round(ct_tps, 1),
        "speedup": round(ct_tps / fx_tps, 2),
        "fixed_ttft_ms_mean": round(float(np.mean(fx_ttfts)) * 1e3, 1),
        "cont_ttft_ms_mean": round(
            float(np.mean(stats.ttfts())) * 1e3, 1),
        "cont_ttft_ms_p50": _pct(stats.ttfts(), 50),
        "cont_ttft_ms_p95": _pct(stats.ttfts(), 95),
        "cont_tpot_ms_p50": _pct(stats.tpots(), 50),
        "cont_tpot_ms_p95": _pct(stats.tpots(), 95),
    }

    # -- speculative decoding over a deeper float target.  The smoke stack
    # is only 2 blocks, so a depth-slice draft would be half the target;
    # a 4-block variant of the same arch gives the draft a real cost
    # edge (1 of 4 blocks) while staying CPU-cheap --
    sd_cfg = dataclasses.replace(cfg, n_layers=4)
    sd_new, sd_lens, sd_cache = 24, (4, 6, 8, 10), 64
    sd_params = lm.init(jax.random.PRNGKey(1), sd_cfg)
    sd_host = jax.tree.map(np.asarray, sd_params)
    sd_ref = Engine(eng_cont.spec, sd_cfg, eng_cont.ctx, sd_params,
                    EngineConfig(batch=1, cache_len=sd_cache,
                                 max_new_tokens=sd_new))
    sd_reqs, sd_expected = _requests(sd_cfg, sd_lens, sd_new, sd_ref, rng)

    def _sd_engine(draft, spec_len=0):
        return Engine(eng_cont.spec, sd_cfg, eng_cont.ctx, sd_params,
                      EngineConfig(batch=batch, cache_len=sd_cache,
                                   max_new_tokens=sd_new,
                                   draft=draft, spec_len=spec_len))

    # -- spec-equivalence: the paper-mode pairing — a w1a1 packed draft
    # (derive_draft's default) proposing for the float target.  On a
    # random-init checkpoint this draft is near-noise (acceptance ~0),
    # which is exactly the point of the gate: greedy spec streams must
    # equal the reference bit-for-bit NO MATTER what the draft says --
    w1_dp, w1_dcfg, w1_rep = converter.derive_draft(sd_host, sd_cfg,
                                                    n_layers=1)
    assert w1_rep.n_packed > 0
    w1_draft = DraftModel(
        cfg=w1_dcfg, params=jax.tree.map(jnp.asarray, w1_dp),
        ctx=QCtx(policy=QuantPolicy.binary(), compute_dtype=jnp.float32,
                 gemm_config=GemmConfig(backend="xla")))
    sd_res, _, sd_stats = _run_continuous(_sd_engine(w1_draft, 2), sd_reqs)
    sd_mismatch = [r.rid for r in sd_reqs
                   if not np.array_equal(sd_res[r.rid], sd_expected[r.rid])]
    yield {
        "mode": "spec-equivalence", "draft": "w1a1-slice1", "spec_len": 2,
        "requests": len(sd_reqs), "batch": batch, "max_new": sd_new,
        "target_layers": sd_cfg.n_layers, "draft_layers": w1_dcfg.n_layers,
        "acceptance_rate": round(sd_stats.acceptance_rate, 3),
        "spec_rounds": sd_stats.spec_rounds,
        "mismatches": len(sd_mismatch),
        "exact_match": not sd_mismatch,
    }

    # -- spec-throughput: float depth-slice draft (the high-agreement
    # pairing available without training) vs plain continuous batching --
    fp_dp, fp_dcfg, _ = converter.derive_draft(
        sd_host, sd_cfg, n_layers=1,
        policy=QuantPolicy.full_precision(), keep_float=True)
    fp_draft = DraftModel(cfg=fp_dcfg,
                          params=jax.tree.map(jnp.asarray, fp_dp),
                          ctx=eng_cont.ctx)
    spec_eng, plain_eng = _sd_engine(fp_draft, 2), _sd_engine(None)
    _run_continuous(spec_eng, sd_reqs)  # warm the spec jits
    _run_continuous(plain_eng, sd_reqs)  # warm the plain jits
    sp_res, sp_dt, sp_stats = _run_continuous(spec_eng, sd_reqs)
    pl_res, pl_dt, pl_stats = _run_continuous(plain_eng, sd_reqs)
    sp_identical = all(np.array_equal(sp_res[r.rid], pl_res[r.rid])
                       and np.array_equal(sp_res[r.rid], sd_expected[r.rid])
                       for r in sd_reqs)
    sp_useful = sum(len(v) for v in sp_res.values())
    sp_tps, pl_tps = sp_useful / sp_dt, sp_useful / pl_dt
    yield {
        "mode": "spec-throughput", "draft": "fp-slice1", "spec_len": 2,
        "requests": len(sd_reqs), "batch": batch, "max_new": sd_new,
        "target_layers": sd_cfg.n_layers, "draft_layers": fp_dcfg.n_layers,
        "useful_tokens": sp_useful,
        "acceptance_rate": round(sp_stats.acceptance_rate, 3),
        "spec_verify_steps": sp_stats.steps,
        "cont_decode_steps": pl_stats.steps,
        "spec_tok_s": round(sp_tps, 1),
        "cont_tok_s": round(pl_tps, 1),
        "speedup": round(sp_tps / pl_tps, 2),
        "spec_tpot_ms_p50": _pct(sp_stats.tpots(), 50),
        "spec_tpot_ms_p95": _pct(sp_stats.tpots(), 95),
        "cont_tpot_ms_p50": _pct(pl_stats.tpots(), 50),
        "cont_tpot_ms_p95": _pct(pl_stats.tpots(), 95),
        "spec_ttft_ms_p50": _pct(sp_stats.ttfts(), 50),
        "spec_ttft_ms_p95": _pct(sp_stats.ttfts(), 95),
        "exact_match": sp_identical,
    }
