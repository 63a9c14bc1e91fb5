"""Record and read the program's host spans and model scopes on the chip.

    python perfbench/tools/span_trace.py probe --out perfbench/tests/data/trace_2layer_serve_spans.json
    python perfbench/tools/span_trace.py cell --workload granite-w1a1.chat --seed 5 [--offset 0 --seconds 30] [--out summary.json]

``probe``: the first two layers of the configuration's model, at its
widths, serving six requests (prompts of 128 or 256 tokens, 16 new tokens
each) inside a bracketing window span, traced as a cell's run traces.
The trace is reduced to plain data (program and op events of the TPU
planes, ``sched.*`` spans and the scopes of the ops in the interval) for
the reader tests.

``cell``: the cell's timed scheduler with the profiler over ``--offset``
to ``--offset + --seconds`` of the window (the whole 30 s window by
default, to read what tracing costs), without the reference check.
Prints one JSON line: the window's ``serve_tokens_per_s`` and
``itl_p95_ms`` as ``--trace 0`` computes them, the cell's per-layer
metrics, ``tracereduce``'s and ``spantrace``'s breakdowns, the count and
seconds of each span in the interval, each step's phases and the
scheduler's counters over the run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import copy  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import bench  # noqa: E402
import program  # noqa: E402
import spantrace  # noqa: E402
import tracereduce  # noqa: E402


def _xplane(trace_dir: str) -> str:
    return sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]


def _inside(ev: dict) -> dict:
    """Only the program and op events that overlap the interval."""
    t0, t1 = ev["window"]
    keep = lambda e: e[1] < t1 and e[1] + e[2] > t0  # noqa: E731
    devices = [dict(dv, modules=[m for m in dv["modules"] if keep(m)],
                    ops=[o for o in dv["ops"] if keep(o)])
               for dv in ev["devices"]]
    return spantrace.trim(dict(ev, devices=devices))


def probe(args, cell, log) -> None:
    kind = bench.load_module("kinds", cell.traffic["kind"])
    cell = copy.deepcopy(cell)
    cell.config["num_hidden_layers"] = 2
    eng, _ = kind.build(cell, args.seed, log)
    rng = np.random.default_rng([args.seed, 3])
    vocab = cell.config["vocab_size"]
    sched = program.Scheduler(eng)
    for n in rng.choice([128, 256], 6):
        sched.submit(program.Request(
            prompt=rng.integers(0, vocab, int(n), dtype=np.int32),
            sampling=program.SamplingParams(max_new_tokens=16)))
    trace_dir = str(bench.CHECKOUT / ".perfbench_trace" / "probe")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN):
            sched.run()
    finally:
        jax.profiler.stop_trace()
    ev = _inside(spantrace.events(_xplane(trace_dir)))
    spantrace.save_events(ev, args.out)
    st = sched.stats
    log(f"probe: {st.iterations} iterations, {st.steps} decode steps, "
        f"{st.prefills} windows, {len(ev['spans'])} spans, "
        f"{sum(map(len, ev['scopes'].values()))} scoped ops -> {args.out}")


def cell_run(args, cell, log) -> None:
    kind = bench.load_module("kinds", cell.traffic["kind"])
    devs = bench.require_chips(cell.chips)
    seconds = args.window or bench.load_json(
        bench.CHECKOUT / "BENCHMARK.json")["run_seconds"]
    eng, dims = kind.build(cell, args.seed, log)
    trace_dir = str(bench.CHECKOUT / ".perfbench_trace" / cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    plan = {"offset_s": args.offset, "seconds": args.seconds}
    sched, win, reqs, _ = kind.serve(eng, cell, args.seed, seconds, plan,
                                     trace_dir)
    t0 = sched._t0
    n_tok, gaps, _ = kind._window_counts(
        sched.stats, win.t_open - t0, win.t_close - t0)
    obs = types.SimpleNamespace(
        trace=tracereduce.load(trace_dir),
        counters=kind.trace_counters(sched, reqs, win.snaps,
                                     cell.config["program"]["slots"]),
        dims=dims, device_kind=devs[0].device_kind)
    sp = spantrace.Spans(spantrace.events(_xplane(trace_dir)))
    st = sched.stats
    span_n, span_s = collections.Counter(), collections.Counter()
    for name, _, d, _ in sp.spans:
        span_n[name] += 1
        span_s[name] += d * 1e-9
    steps = []  # [step_num, ms, prefill rows, decode rows, {phase: [ms]}]
    for _, s0, d0, a in sp.steps():
        phases = collections.defaultdict(list)
        for name, s, d, _ in sp.spans:
            if name != "sched.step" and s0 <= s and s + d <= s0 + d0:
                phases[name.removeprefix("sched.")].append(d * 1e-6)
        steps.append([a["step_num"], d0 * 1e-6, a["prefill_rows"],
                      a["decode_rows"], dict(phases)])
    out = {
        "seed": args.seed, "trace_offset_s": args.offset,
        "trace_seconds": args.seconds,
        "serve_tokens_per_s": n_tok / seconds,
        "itl_p95_ms": bench.percentile(gaps, 95) * 1e3,
        "setup_s": win.t_open - T_START,
        "per_layer": bench.read_per_layer(cell, obs),
        "busy_s": obs.trace.busy_s, "window_s": obs.trace.window_s,
        "op_s": sum(obs.trace.op_time.values()) * 1e-9,
        "breakdown": dict(obs.trace.breakdown(), **sp.breakdown()),
        "spans": dict(span_n), "span_s": dict(span_s), "steps": steps,
        "stats": {"iterations": st.iterations, "host_s": st.host_s,
                  "wait_s": st.wait_s, "steps": st.steps,
                  "prefills": st.prefills},
        "device": bench.device_info(devs),
    }
    shutil.rmtree(trace_dir, ignore_errors=True)
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("probe", "cell"))
    ap.add_argument("--workload", default="granite-w1a1.chat")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--offset", type=float, default=0.0)
    ap.add_argument("--seconds", type=float, default=30.0,
                    help="seconds of the window to trace")
    ap.add_argument("--window", type=float, default=None,
                    help="the window (default: run_seconds)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = bench.load_cell(args.workload)
    bench.require_chips(cell.chips)
    bench.use_compile_cache()
    log = bench.progress(T_START)
    if args.mode == "probe":
        if not args.out:
            ap.error("probe needs --out")
        probe(args, cell, log)
    else:
        cell_run(args, cell, log)


if __name__ == "__main__":
    main()
