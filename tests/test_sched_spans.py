"""The serving scheduler's host spans and counters, and the model's named
scopes: a tiny paged engine served under a profiler session.

One ``sched.step`` span per loop iteration, one ``sched.window`` per
prefill window and one ``sched.decode`` per decode step, every phase span
inside its step; ``host_s + wait_s`` accounts for the run's wall time,
split between host work and waiting on a device result;
admission precedes the first token; the ``t_us`` anchor of the step spans
places the scheduler's timestamps on the trace's clock.  The named scopes
reach the lowered decode step and leave greedy tokens unchanged."""

import contextlib
import glob
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import QuantPolicy
from repro.models import lm, registry
from repro.nn.common import QCtx
from repro.serve.engine import Engine, EngineConfig, Request, Scheduler

ECFG = dict(batch=2, cache_len=32, max_new_tokens=5, kv_block_size=4,
            prefill_chunk=5)
ANCHOR_NS = 200_000  # 200 us


def _engine():
    spec = registry.get("granite-3-2b")
    ctx = QCtx(policy=QuantPolicy.full_precision(), compute_dtype=jnp.float32)
    params = lm.init(jax.random.PRNGKey(0), spec.smoke)
    return Engine(spec, spec.smoke, ctx, params, EngineConfig(**ECFG))


def _prompts(vocab):
    rng = np.random.default_rng(5)
    return [rng.integers(0, vocab, (n,)).astype(np.int32)
            for n in (7, 3, 12, 4, 9)]


def _serve(eng, prompts):
    sched = Scheduler(eng)
    for p in prompts:
        sched.submit(Request(prompt=p))
    t = time.perf_counter()
    out = sched.run()
    return out, sched, time.perf_counter() - t


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A run under the profiler: (results, scheduler, wall seconds, host
    spans [(name, start ns, end ns, args)])."""
    from jax.profiler import ProfileData, ProfileOptions

    eng = _engine()
    prompts = _prompts(eng.cfg.vocab_size)
    _serve(eng, prompts)  # compile outside the traced run
    d = str(tmp_path_factory.mktemp("trace"))
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        out, sched, wall = _serve(eng, prompts)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host")
             for line in plane.lines for e in line.events
             if e.name.startswith("sched.")]
    return out, sched, wall, spans


def _named(spans, name):
    return sorted((s for s in spans if s[0] == name), key=lambda s: s[1])


def test_one_step_span_per_iteration_and_one_per_call(traced):
    _, sched, _, spans = traced
    st = sched.stats
    steps = _named(spans, "sched.step")
    assert st.iterations > 0 and len(steps) == st.iterations
    assert [s[3]["step_num"] for s in steps] == list(range(st.iterations))
    assert len(_named(spans, "sched.admit")) == st.iterations
    assert len(_named(spans, "sched.window")) == st.prefills > 0
    assert len(_named(spans, "sched.decode")) == st.steps > 0
    assert len(_named(spans, "sched.sync")) >= st.steps
    assert sum(s[3]["n"] for s in _named(spans, "sched.admit")) == len(
        st.admissions)


def test_phase_spans_nest_inside_their_step(traced):
    _, _, _, spans = traced
    steps = _named(spans, "sched.step")
    for name, s, e, _ in spans:
        if name == "sched.step":
            continue
        owners = [st for st in steps if st[1] <= s and e <= st[2]]
        assert len(owners) == 1, (name, s, e)
    # steps do not overlap
    assert all(a[2] <= b[1] for a, b in zip(steps, steps[1:]))


def test_host_and_wait_account_for_the_run(traced):
    _, sched, wall, spans = traced
    st = sched.stats
    assert st.host_s > 0 and st.wait_s > 0
    assert abs(st.host_s + st.wait_s - wall) <= 0.05 * wall
    # the counters at entry of each step are the running sums
    steps = _named(spans, "sched.step")
    assert steps[0][3]["host_us"] == 0 and steps[0][3]["wait_us"] == 0
    assert all(a[3]["host_us"] <= b[3]["host_us"]
               for a, b in zip(steps, steps[1:]))


class _SlowArray:
    """Stands for a device result that takes ``s`` seconds to arrive."""

    def __init__(self, s):
        self.s = s

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.s)
        return np.zeros(2, np.int32)


def test_counters_split_host_from_wait():
    sched = Scheduler(_engine())
    sched._t0 = time.perf_counter()
    for _ in range(2):
        with sched._step():
            time.sleep(0.05)  # host work
            sched._fetch(_SlowArray(0.1))  # waiting on the device
    st = sched.stats
    assert st.iterations == 2
    assert 0.2 <= st.wait_s < 0.3
    assert 0.1 <= st.host_s < 0.2


def test_admission_precedes_first_token(traced):
    out, sched, _, _ = traced
    st = sched.stats
    assert set(st.t_admit) == set(out)
    for rid, ts in st.t_tokens.items():
        assert st.t_admit[rid] <= ts[0]
    assert len(st.admit_ttfts()) == len(st.t_tokens)
    assert all(w >= 0 for w in st.admit_ttfts())


def test_step_anchor_places_token_times_on_the_trace(traced):
    _, sched, _, spans = traced
    steps = _named(spans, "sched.step")
    offsets = [s[1] - s[3]["t_us"] * 1e3 for s in steps]
    assert max(offsets) - min(offsets) < ANCHOR_NS
    off = statistics.median(offsets)
    emits = _named(spans, "sched.emit")
    for ts in sched.stats.t_tokens.values():
        for t in ts:
            at = t * 1e9 + off
            assert any(s - ANCHOR_NS <= at <= e + ANCHOR_NS
                       for _, s, e, _ in emits), at


def test_named_scopes_reach_the_decode_step():
    eng = _engine()
    b = ECFG["batch"]
    text = eng._decode.lower(
        eng.params, eng.init_cache(), jnp.zeros((b, 1), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool)).as_text(
            debug_info=True)
    for scope in ("embed", "attn/kv_write", "attn/attend", "ffn", "head"):
        assert f"/{scope}/" in text, scope


def test_named_scopes_leave_greedy_tokens_unchanged(monkeypatch):
    eng = _engine()
    prompts = _prompts(eng.cfg.vocab_size)
    scoped, _, _ = _serve(eng, prompts)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain, _, _ = _serve(_engine(), prompts)
    assert set(scoped) == set(plain)
    for rid in scoped:
        np.testing.assert_array_equal(scoped[rid], plain[rid])
