"""Run one benchmark cell.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics read from a profiler trace of part of the window.  Both
check the outputs of the timed path against the plain reference.  The
last line of standard output is the result as one JSON object.  Without
the chips the cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench  # noqa: E402


def load_kind(kind: str):
    return bench.load_module("kinds", kind)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = bench.load_cell(args.workload)
    devs = bench.require_chips(cell.chips)
    bench.use_compile_cache()
    kind = load_kind(cell.traffic["kind"])
    trace_dir = None
    if args.trace:
        trace_dir = str(bench.CHECKOUT / ".perfbench_trace" / args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    res = kind.run(cell, args.seed, args.seconds, bool(args.trace), T_START,
                   devs, trace_dir)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    bench.emit(res)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except bench.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        sys.exit(2)
