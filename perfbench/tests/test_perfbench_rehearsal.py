"""The harness rehearsed on the CPU at smoke widths (Pallas in interpret
mode): its own functions, not the CLI, for a couple of seconds of the
serving traffic, and the CLI's refusal of a machine without the chip."""

import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import smoke
import bench

CLI = [sys.executable, str(smoke.HERE / "run.py")]


def _per_layer(workload):
    b = bench.load_json(bench.CHECKOUT / "BENCHMARK.json")
    return [m for m in b["per_layer"] if workload in m.get("workloads", ())]


@pytest.fixture(scope="module")
def sv():
    return smoke.serve_kind()


@pytest.fixture(scope="module")
def served(sv):
    """One smoke cell through set-up and a 2 s window."""
    cell = smoke.cell({"served_logit_gap": 1e-3, "min_tokens_checked": 8},
                      check_requests=3)
    counter = bench.CompileCounter()
    eng, dims = sv.build(cell, 2 ** 33 + 5, lambda msg: None)
    sched, win, reqs, served = sv.serve(eng, cell, 2 ** 33 + 5, 2.0)
    return cell, counter, sched, win, reqs, served


def test_serve_counts_only_the_window(sv, served):
    cell, counter, sched, win, reqs, _ = served
    t0 = sched._t0
    a, b = win.t_open - t0, win.t_close - t0
    n_tok, gaps, attempted = sv._window_counts(sched.stats, a, b)
    every = [t for ts in sched.stats.t_tokens.values() for t in ts]
    inside = sum(a <= t <= b for t in every)
    # a step in flight at either edge counts by its share of the window
    assert 0 < inside < len(every)
    assert abs(n_tok - inside) <= cell.config["program"]["slots"]
    assert min(every) < a  # the ramp's tokens are left out
    assert all(0 < g <= b - a for g in gaps)
    assert attempted >= cell.config["program"]["slots"]
    # the window opened once every ramp request had retired
    assert all(sched.stats.t_done[r] <= a for r in range(4))
    assert counter.between(win.t_open, win.t_close) == 0


def test_serve_stop_reaches_every_request(served):
    _, _, sched, win, reqs, served_streams = served
    for rid, ts in sched.stats.t_tokens.items():
        assert len(ts) <= reqs[rid][1]
    assert not sched.queue and all(s is None for s in sched.slots)
    # requests admitted after the close got a zero budget and no tokens
    assert len(served_streams) < len(reqs)


def test_serve_run_is_correct_and_traced_counts_are_shares(sv, tmp_path):
    cell = smoke.cell({"served_logit_gap": 1e-3, "min_tokens_checked": 8},
                      check_requests=3, trace={"offset_s": 0.2, "seconds": 0.5})
    cell.per_layer = _per_layer("granite-w1a1.chat")
    res = sv.run(cell, 7, 2.0, False, time.perf_counter(), jax.devices())
    assert res.correct and res.failed == 0
    assert set(res.metrics) == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    assert res.checks["compiles_in_window"]["value"] == 0
    res = sv.run(cell, 8, 2.0, True, time.perf_counter(), jax.devices(),
                 trace_dir=str(tmp_path))
    assert res.correct
    occ = res.metrics["decode_occupancy"]["value"]
    assert 0 < occ <= 100
    # a CPU trace has no device plane: no device metric is reported
    assert set(res.metrics) == {"decode_occupancy"}
    assert "busy_s" not in res.device


def test_cli_refuses_a_machine_without_the_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = ["--workload", "granite-w1a1.chat", "--seed", "1", "--seconds",
            "1", "--trace", "0"]
    p = subprocess.run(CLI + args, capture_output=True, text=True, env=env,
                       cwd=bench.CHECKOUT, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    # nor does it run from a directory with only the benchmark's own files
    shutil.copy(bench.CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(smoke.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    p = subprocess.run([sys.executable, "perfbench/run.py"] + args,
                       capture_output=True, text=True, env=env, cwd=tmp_path,
                       timeout=300)
    assert p.returncode != 0 and p.stdout == ""
