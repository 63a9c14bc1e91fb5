"""The harness's shared parts: the benchmark's files, the device, the
compile counter and the result line.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``  - the model and how the program runs it; its
  ``reference`` names the plain reference ``references/<reference>.py``;
* ``traffic/<traffic>.json`` - the mix; its ``kind`` names the module
  ``kinds/<kind>.py`` that reads the file's parameters and runs the cell;
* ``cells/<workload>.json``  - the cell's correctness limits and the
  readings they were set from;
* ``metrics/<metric>.py``    - a reader with ``read(obs) -> float | None``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def progress(t_start: float):
    """A logger of set-up phases on standard error, with the seconds since
    the process started."""
    def log(msg: str) -> None:
        print(f"[perfbench {time.perf_counter() - t_start:8.1f}s] {msg}",
              file=sys.stderr, flush=True)
    return log


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(folder: str, name: str):
    """The module ``<folder>/<name>.py`` of the benchmark, by name."""
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{folder}_{name.replace('.', '_').replace('-', '_')}",
        HERE / folder / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    spec: dict  # cells/<name>.json
    end_to_end: list
    per_layer: list


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_json(CHECKOUT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"],
        config=load_json(CHECKOUT / cfg_entry["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        spec=load_json(HERE / "cells" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer,
    )


def seed_key(seed: int):
    """A JAX key from a seed of up to 64 bits (``PRNGKey`` alone keeps
    only the low 32, which would give 5 and 2**33 + 5 the same weights)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# device and compile cache
# ---------------------------------------------------------------------------


class NoChip(SystemExit):
    """Raised when the cell's chips are not there; the CLI exits non-zero
    and prints no result."""


def require_chips(n: int):
    """The TPU devices of this process, at least ``n`` of them.  A CPU or
    any other platform is refused: the benchmark never falls back."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no accelerator: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports {devs[0].platform!r} devices")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX reports {len(devs)}")
    return devs[:n]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else ``<checkout>/.jax_cache``, a
    fixed path so that every run of a cell in this checkout finds what the
    first one compiled.  Every program is kept, however quick."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX's compile events (tracing to jaxpr, lowering, backend
    compiles and persistent-cache loads) with their host times, so a run
    can show that nothing compiled inside its measured window."""

    def __init__(self):
        import jax

        self.events: list[tuple[float, str]] = []
        self._lock = threading.Lock()

        def listener(event: str, duration: float, **_):
            if "compil" in event or "trace" in event or "lower" in event:
                with self._lock:
                    self.events.append((time.perf_counter(), event))

        jax.monitoring.register_event_duration_secs_listener(listener)

    def between(self, t0: float, t1: float) -> int:
        with self._lock:
            return sum(1 for t, _ in self.events if t0 <= t <= t1)


def device_info(devs) -> dict:
    """The devices as JAX reports them, with the peak bytes in use on the
    fullest chip so far."""
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(
                int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in devs)}


# ---------------------------------------------------------------------------
# per-layer metric readers
# ---------------------------------------------------------------------------


def read_per_layer(cell: Cell, obs) -> dict:
    """Run each of the cell's per-layer readers over the observations of a
    traced run; a reader that finds nothing returns None and its metric
    is left out."""
    out = {}
    for m in cell.per_layer:
        value = load_module("metrics", m["name"]).read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# arithmetic and the result line
# ---------------------------------------------------------------------------


def percentile(xs, q: float) -> float:
    """Linear-interpolation percentile (numpy's default), as
    ``benchmarks/serve_bench._pct`` takes it; NaN for an empty sample."""
    import numpy as np

    return float(np.percentile(xs, q)) if len(xs) else float("nan")


@dataclasses.dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: dict  # name -> {"value", "unit"}
    device: dict
    checks: dict  # name -> {"value", "limit"}
    breakdown: dict | None = None


def emit(res: Result) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error; then the result as the last line of standard output,
    with the checks under the key that comes last."""
    for name, c in res.checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    line = {"correct": bool(res.correct), "attempted": int(res.attempted),
            "failed": int(res.failed), "metrics": res.metrics,
            "device": res.device}
    if res.breakdown is not None:
        line["breakdown"] = res.breakdown
    line["checks"] = res.checks
    print(json.dumps(line), flush=True)
