"""Offline serving: a saturated FIFO queue of distinct prompts through the
program's continuous-batching ``Scheduler`` over its paged ``Engine``.

The run: set-up (weights drawn and packed on the device, engine built,
every shape of the cell warmed up on a scheduler of its own), then the
timed scheduler is given the whole queue.  Its head is one short ramp
request per slot, with budgets spread over a range, so that the slots
turn over at staggered times as in a server that has been running; the
window opens once every ramp request has retired and every slot holds a
request of the mix, and closes ``seconds`` later, when a timer thread
gives every queued and running request a budget of zero, repeatedly,
until ``run()`` returns.  Only tokens and gaps inside the window count; a
step in flight at either edge counts by the share of its time inside.
With ``trace``, the profiler records a few seconds of the window.

Traffic parameters (``traffic/<name>.json``): ``requests``, ``block``,
``prompt``/``output`` length laws (lognormal by ``mean`` and ``sigma``,
or ``uniform``; ``min``, ``max``, optional ``round_up``), ``order_seed`` and ``ramp`` (``prompt``, ``min_new``,
``max_new``).  Lengths are the laws' stratified
quantiles, the same multiset in every block of ``block`` requests, paired
and ordered once by ``order_seed``: every run seed brings the same sizes
in the same order and draws its own tokens and weights, so the work in
the window does not change with the seed.
"""

from __future__ import annotations

import gc
import math
import statistics
import threading
import time
import types

import jax
import numpy as np

import bench
import program
import tracereduce
import weights

POLL_S = 0.002
# Tokens of one step reach the host together: emissions closer than this
# belong to one step (a decode step of the model takes far longer).
BURST_S = 5e-4


def lengths(law: dict, n: int) -> np.ndarray:
    """The law's quantiles at (j + 1/2) / n, clipped and rounded up: a
    lognormal of ``mean`` and ``sigma`` (its median is mean *
    exp(-sigma**2 / 2)) or, with ``"uniform": true``, the uniform law over
    [``min``, ``max``]."""
    u = (np.arange(n) + 0.5) / n
    if law.get("uniform"):
        q = law["min"] + u * (law["max"] - law["min"])
    else:
        nd = statistics.NormalDist()
        median = law["mean"] * math.exp(-law["sigma"] ** 2 / 2)
        q = median * np.exp(law["sigma"] * np.array(
            [nd.inv_cdf(x) for x in u]))
    q = np.clip(np.round(q), law["min"], law["max"]).astype(np.int64)
    r = law.get("round_up", 1)
    return (-(-q // r) * r).astype(np.int64)


def requests(traffic: dict, seed: int, vocab: int, slots: int) -> list:
    """[(prompt int32 array, max_new_tokens)] for the whole queue: first
    one ramp request per slot (one prefill chunk, budgets spread evenly
    over the ramp's range, so that the slots turn over at staggered times),
    then the mix in its fixed order."""
    ramp = traffic["ramp"]
    blk = traffic["block"]
    p_len, o_len = lengths(traffic["prompt"], blk), lengths(
        traffic["output"], blk)
    order = np.random.default_rng(traffic["order_seed"])
    sizes = []
    for _ in range(traffic["requests"] // blk):
        pair = order.permutation(blk)
        sizes += [(int(p_len[j]), int(o_len[pair[j]]))
                  for j in order.permutation(blk)]
    budgets = np.linspace(ramp["min_new"], ramp["max_new"], slots)
    sizes = [(ramp["prompt"], int(round(b))) for b in budgets] + sizes
    rng = np.random.default_rng([seed, 0])
    return [(rng.integers(0, vocab, n, dtype=np.int32), m) for n, m in sizes]


class Window(threading.Thread):
    """Opens the window once the ramp requests ``first`` have all retired,
    optionally traces part of it, and stops the scheduler when it
    closes."""

    def __init__(self, sched, first, seconds, trace_plan, trace_dir):
        super().__init__(daemon=True)
        self.s, self.first, self.seconds = sched, first, seconds
        self.plan, self.trace_dir = trace_plan, trace_dir
        self.t_open = self.t_close = None
        self.snaps: list = []
        self.done = threading.Event()
        self.error: BaseException | None = None

    def _snap(self):
        st = self.s.stats
        return {"t": time.perf_counter(), "steps": st.steps,
                "prefills": st.prefills,
                "admitted": len(st.admissions),
                "slots": [(s.rid, s.prompt_len, s.prefill_done)
                          if s is not None else None for s in self.s.slots]}

    def _sleep_until(self, t):
        while not self.done.is_set() and time.perf_counter() < t:
            time.sleep(POLL_S)

    def _trace(self):
        from jax.profiler import ProfileOptions, TraceAnnotation

        self._sleep_until(self.t_open + self.plan["offset_s"])
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with TraceAnnotation(tracereduce.WINDOW_SPAN):
                a = self._snap()
                self.snaps.append(a)
                end = min(a["t"] + self.plan["seconds"],
                          self.t_open + self.seconds)
                while not self.done.is_set():
                    now = time.perf_counter()
                    st = self.s.stats
                    enough = (now >= end and st.prefills > a["prefills"]
                              and st.steps > a["steps"])
                    if enough or now >= self.t_open + self.seconds:
                        break
                    time.sleep(POLL_S)
                self.snaps.append(self._snap())
        finally:
            jax.profiler.stop_trace()

    def _stop(self):
        """Budget zero for everything queued and running, until drained."""
        zero = program.SamplingParams(max_new_tokens=0)
        while not self.done.is_set():
            for r in list(self.s.queue):
                r.sampling = zero
            for st in list(self.s.slots):
                if st is not None:
                    st.budget = 0
            time.sleep(POLL_S)

    def run(self):
        try:
            t_done = self.s.stats.t_done
            while not all(r in t_done for r in self.first):
                if self.done.is_set():
                    return
                time.sleep(POLL_S)
            self.t_open = time.perf_counter()
            if self.plan is not None:
                self._trace()
            self._sleep_until(self.t_open + self.seconds)
            self.t_close = self.t_open + self.seconds
            self._stop()
        except BaseException as e:  # reported by the main thread
            self.error = e
            self._stop()


def warm_up(eng, cfg: dict, seed: int) -> None:
    """Every shape the window uses, on a scheduler of its own: one full
    prefill window of all slots, decode steps of all slots, sampling from
    both, block-table mapping and retirement."""
    prog = cfg["program"]
    rng = np.random.default_rng([seed, 2])
    sched = program.Scheduler(eng)
    for _ in range(prog["slots"]):
        sched.submit(program.Request(
            prompt=rng.integers(0, cfg["vocab_size"], prog["prefill_chunk"],
                                dtype=np.int32),
            sampling=program.SamplingParams(max_new_tokens=3)))
    sched.run()


def emitted(stats, a: float, b: float) -> float:
    """Output tokens emitted in [a, b], the step in flight at either edge
    counted by the share of its time that lies inside: the count of tokens
    emitted so far, as a function of time, rises linearly over each step
    from the end of the one before to the moment its tokens arrive."""
    times = np.sort(np.concatenate(
        [np.asarray(ts, np.float64) for ts in stats.t_tokens.values()]))
    last = np.r_[np.flatnonzero(np.diff(times) > BURST_S), len(times) - 1]
    arrived, count = times[last], last + 1.0
    return float(np.interp(b, arrived, count) - np.interp(a, arrived, count))


def _window_counts(stats, a: float, b: float):
    """Tokens emitted in [a, b], the gaps with both ends in it and the
    requests that received a token in it."""
    gaps, rids = [], 0
    for ts in stats.t_tokens.values():
        inside = [t for t in ts if a <= t <= b]
        rids += bool(inside)
        gaps += [y - x for x, y in zip(inside, inside[1:])]
    return emitted(stats, a, b), gaps, rids


def trace_counters(sched, reqs, snaps, slots: int) -> dict:
    """Scheduler counts over the traced interval: decode steps, tokens
    emitted by decode and by prefill, and the positions of every token
    the model processed, for the occupancy and MFU readers."""
    a, b = snaps
    t0 = sched._t0
    lo, hi = a["t"] - t0, b["t"] - t0
    stats = sched.stats
    decode_ctx, emitted = [], 0
    for rid, ts in stats.t_tokens.items():
        plen = len(reqs[rid][0])
        for m, t in enumerate(ts):
            if lo <= t <= hi:
                emitted += 1
                if m > 0:  # token m came from a decode step at position
                    decode_ctx.append(plen + m - 1)
    prefill_pos = []
    start = {s[0]: s[2] for s in a["slots"] if s is not None}
    end = {s[0]: s[2] for s in b["slots"] if s is not None}
    admitted = {rid for rid, _ in stats.admissions[a["admitted"]:b["admitted"]]}
    for rid in set(start) | set(end) | admitted:
        plen = len(reqs[rid][0])
        p0 = start.get(rid, 0)
        p1 = end.get(rid, plen)
        prefill_pos += range(p0, p1)
    return {"slots": slots, "steps": b["steps"] - a["steps"],
            "decode_tokens": len(decode_ctx), "decode_ctx": decode_ctx,
            "prefill_pos": prefill_pos, "emitted": emitted,
            "seconds": b["t"] - a["t"]}


def build(cell, seed: int, log):
    """Set-up: the seed's weights packed on the device, the engine, and
    every shape of the cell warmed up.  Returns (engine, dims)."""
    cfg = cell.config
    dims = weights.Dims.of(cfg)
    lm = program.lm_config(cfg)
    params = weights.served_params(seed, dims, program.block_converter(cfg))
    jax.block_until_ready(params)
    log("packed weights on the device")
    eng = program.Engine(
        program.arch_spec(cfg, lm), lm, program.qctx(cfg), params,
        program.engine_config(cfg))
    warm_up(eng, cfg, seed)
    log("warm-up served")
    return eng, dims


def serve(eng, cell, seed: int, seconds: float, plan=None, trace_dir=None):
    """The timed scheduler over the seed's whole queue; returns it, the
    window thread, the requests and every non-empty served stream."""
    cfg = cell.config
    slots = cfg["program"]["slots"]
    reqs = requests(cell.traffic, seed, cfg["vocab_size"], slots)
    sched = program.Scheduler(eng)
    for rid, (prompt, n_new) in enumerate(reqs):
        sched.submit(program.Request(
            prompt=prompt, rid=rid,
            sampling=program.SamplingParams(max_new_tokens=n_new)))
    win = Window(sched, range(slots), seconds, plan, trace_dir)
    win.start()
    try:
        results = sched.run()
    finally:
        win.done.set()
        win.join()
    if win.error is not None:
        raise win.error
    if win.t_close is None:
        raise RuntimeError("the queue drained before the window closed: "
                           "the traffic file needs more requests")
    served = {rid: r for rid, r in results.items()
              if len(r) and rid >= slots}
    return sched, win, reqs, served


def reference(cell):
    """The configuration's plain reference, ``references/<name>.py``,
    which refuses a configuration it does not model."""
    mod = bench.load_module("references", cell.config["reference"])
    mod.check_config(cell.config)
    return mod


def compared(gaps, cut) -> float:
    """The widest gap over every request's compared tokens."""
    return max((float(g[:c].max()) for g, c in zip(gaps, cut) if c),
               default=0.0)


def check(cell, seed: int, dims, reqs, served, control: bool = False):
    """Teacher-forced comparison of the served tokens of a sample of the
    requests with the reference.  Every served token of every sampled
    request is compared, with one exception (``cut_at_divergence``).
    Returns (sample, gaps per request, tokens compared per request, the
    control's gaps or None)."""
    ref = reference(cell)
    page = cell.config["program"]["kv_block_size"]
    limit = cell.spec["limits"]["served_logit_gap"]
    sample = check_sample(served, reqs, seed, cell.spec["check_requests"])
    seqs = [(reqs[r][0], served[r]) for r in sample]
    gaps, cgaps = ref.served_gaps(seed, dims, seqs, page, control)
    return sample, gaps, cut_at_divergence(
        ref, cell, seed, dims, seqs, gaps, limit), cgaps


def cut_at_divergence(ref, cell, seed, dims, seqs, gaps, limit) -> list:
    """Tokens compared per request: all of them, with one exception.
    Where exactly one request misses, and the reference under a softmax
    temperature moved by the cell's ``divergence_kappa`` misses the same
    limit in that request itself (its own first choices lie more than the
    limit below its best), that request is compared up to the position
    from which the moved reference's hidden state departs: from there on
    a rounding-sized change of the reference's arithmetic changes its
    answer.  A request the reference settles is never cut."""
    cut = [len(g) for g in gaps]
    bad = [j for j, g in enumerate(gaps) if g.max() > limit]
    if len(bad) == 1:
        (j,) = bad
        ((d, moved),) = ref.divergence(
            seed, dims, [seqs[j]], cell.config["program"]["kv_block_size"],
            cell.spec["divergence_kappa"])
        if moved > limit:
            cut[j] = min(cut[j], max(0, d - len(seqs[j][0]) + 1))
    return cut


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devs, trace_dir: str | None = None):
    log = bench.progress(t_start)
    counter = bench.CompileCounter()
    eng, dims = build(cell, seed, log)
    plan = cell.spec["trace"] if trace else None
    sched, win, reqs, served = serve(eng, cell, seed, seconds, plan,
                                     trace_dir)
    log(f"window {win.t_open - t_start:.1f}s to {win.t_close - t_start:.1f}s"
        "; drained")
    t0 = sched._t0
    n_tok, gaps, attempted = _window_counts(
        sched.stats, win.t_open - t0, win.t_close - t0)
    compiles = counter.between(win.t_open, win.t_close)
    device = bench.device_info(devs)

    metrics, breakdown = {}, None
    if trace:
        obs = types.SimpleNamespace(
            trace=tracereduce.load(trace_dir),
            counters=trace_counters(sched, reqs, win.snaps,
                                    cell.config["program"]["slots"]),
            dims=dims, device_kind=devs[0].device_kind)
        metrics = bench.read_per_layer(cell, obs)
        if obs.trace is not None:
            device["busy_s"] = obs.trace.busy_s
            device["window_s"] = obs.trace.window_s
            breakdown = obs.trace.breakdown()
    else:
        metrics = {
            "serve_tokens_per_s": {"value": n_tok / seconds,
                                   "unit": "tokens/s"},
            "itl_p95_ms": {"value": bench.percentile(gaps, 95) * 1e3,
                           "unit": "ms"},
            "setup_s": {"value": win.t_open - t_start, "unit": "s"},
        }

    del eng, sched, win  # the program's state goes before the reference
    gc.collect()
    sample, gaps, cut, _ = check(cell, seed, dims, reqs, served)
    log("reference compared requests " + ", ".join(
        f"{r}: {c} of {len(g)} tokens, widest gap {float(g.max()):.4g}"
        for r, g, c in zip(sample, gaps, cut)))
    limits = cell.spec["limits"]
    failed = sum(bool(c) and float(g[:c].max()) > limits["served_logit_gap"]
                 for g, c in zip(gaps, cut))
    checks = {
        "served_logit_gap": {"value": compared(gaps, cut),
                             "limit": limits["served_logit_gap"]},
        "served_tokens_checked": {"value": int(sum(cut)),
                                  "limit": limits["min_tokens_checked"]},
        "requests_cut": {"value": sum(c < len(g) for g, c in zip(gaps, cut)),
                         "limit": 1},
        "compiles_in_window": {"value": compiles, "limit": 0},
    }
    correct = (checks["served_logit_gap"]["value"]
               <= checks["served_logit_gap"]["limit"]
               and checks["requests_cut"]["value"] <= 1
               and compiles == 0 and n_tok > 0
               and checks["served_tokens_checked"]["value"]
               >= checks["served_tokens_checked"]["limit"])
    return bench.Result(correct=correct, attempted=attempted, failed=failed,
                        metrics=metrics, device=device, checks=checks,
                        breakdown=breakdown)


def check_sample(served: dict, reqs, seed: int, n: int) -> list:
    """Requests of the mix to compare, drawn from the seed, with the
    longest served stream among them."""
    rids = sorted(served)
    longest = max(rids, key=lambda r: (len(served[r]), len(reqs[r][0])))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]
