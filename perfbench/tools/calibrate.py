"""Readings that a serving cell's correctness limit is set from: the
program on a dozen seeds or more and the control (the reference one
precision below the configuration, bfloat16) on the same seeds, each with
the reference's divergence point of every sampled request.  All seeds run
in one process, so set-up is paid once.  On the chip:

    python perfbench/tools/calibrate.py --workload granite-w1a1.chat --seeds 11 12 13 ...

``--kappas`` gives the temperature steps whose divergence points are
read for every sampled request (the cell's own is always among them);
``--traffic`` runs another traffic file in the cell's place.  Each seed's
readings go to standard output as one JSON line, and to ``--out`` when
given.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402

import bench  # noqa: E402
import program  # noqa: E402
import weights  # noqa: E402


def readings(sv, cell, seed, dims, reqs, served, kappas) -> dict:
    """The program's and the control's numbers under the cell's rule, and
    per sampled request: id, prompt and served length, widest gap of the
    program and of the control, and for each temperature step the first
    served token at or after the moved reference's divergence point with
    the moved reference's own widest gap; where the program misses, the
    first served token at which it does."""
    ref = sv.reference(cell)
    page = cell.config["program"]["kv_block_size"]
    limit = cell.spec["limits"]["served_logit_gap"]
    sample, gaps, cut, cgaps = sv.check(cell, seed, dims, reqs, served,
                                        control=True)
    seqs = [(reqs[r][0], served[r]) for r in sample]
    first = {}
    for k in sorted(set(kappas) | {cell.spec["divergence_kappa"]}):
        div = ref.divergence(seed, dims, seqs, page, k)
        first[k] = [(max(0, d - len(p) + 1), w)
                    for (d, w), (p, _) in zip(div, seqs)]
    ccut = sv.cut_at_divergence(ref, cell, seed, dims, seqs, cgaps, limit)
    row = {"seed": seed,
           "served_logit_gap": sv.compared(gaps, cut),
           "control_logit_gap": sv.compared(cgaps, ccut),
           "tokens_compared": int(sum(cut)),
           "control_tokens_compared": int(sum(ccut)),
           "per_request": [
               [int(r), len(reqs[r][0]), len(served[r]), float(g.max()),
                float(c.max()), {str(k): f[j] for k, f in first.items()}]
               for j, (r, g, c) in enumerate(zip(sample, gaps, cgaps))]}
    bad = [j for j, g in enumerate(gaps) if g.max() > limit]
    if bad:
        row["first_miss"] = [int((gaps[j] > limit).argmax()) for j in bad]
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="serving window (default: run_seconds)")
    ap.add_argument("--kappas", type=float, nargs="*", default=[])
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    bench_json = bench.load_json(bench.CHECKOUT / "BENCHMARK.json")
    cell = bench.load_cell(args.workload, bench_json)
    if args.traffic:
        cell.traffic = bench.load_json(Path(args.traffic))
    bench.require_chips(cell.chips)
    bench.use_compile_cache()
    seconds = args.seconds or bench_json["run_seconds"]

    sv = bench.load_module("kinds", cell.traffic["kind"])
    log = bench.progress(T_START)
    eng, dims = sv.build(cell, args.seeds[0], log)
    convert = program.block_converter(cell.config)
    for seed in args.seeds:
        eng.params = jax.block_until_ready(
            weights.served_params(seed, dims, convert))
        sched, win, reqs, served = sv.serve(eng, cell, seed, seconds)
        del sched, win
        row = readings(sv, cell, seed, dims, reqs, served, args.kappas)
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        log(f"seed {seed} done")


if __name__ == "__main__":
    main()
