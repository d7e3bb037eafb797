#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from (not part of a
benchmark run):

    python3 bench/control.py --workload <cell> --seeds <a> <b> ... \\
        --seconds <s> [--control-seeds <k>] [--loose-tols <tol> ...]

One process sets the cell up once, then for each seed runs a window of
``--seconds`` at the cell's own load and prints the numbers the check
compares (the lower reading is the largest over the seeds).  For the
first ``--control-seeds`` seeds it then puts the control in the
program's place — the float64 reference answer of every request of the
window, stored in bfloat16 — and prints what the same check reads for
it (the upper reading is the smallest), with the float32-stored
reference beside it for scale.  With ``--loose-tols`` it also runs the
program at each looser tolerance and prints what the check reads: a
program that stops early has to fail the independent residual limit.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--loose-tols", type=float, nargs="*", default=[],
                    help="also run the program at each of these looser "
                         "tolerances on the first seed and print what the "
                         "check reads")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.reference import DirectSolve, round_to
    harness.configure_jax()

    cell = harness.load_cell(args.workload)
    log = lambda m: print(f"control: {m}", flush=True)  # noqa: E731
    s = harness.Session(cell, args.seeds[0], log=log)
    runs = []
    try:
        s.warm_up()
        for seed in args.seeds:
            run = s.new_run(seed)
            s.window(run, args.seconds)
            c = harness.check(run)
            runs.append(run)
            print(json.dumps({"program": {"seed": seed, **{
                k: v["value"] for k, v in c.items()},
                "answers": len(run.sent) + 1, "failed": run.failed}}),
                flush=True)
        for tol in args.loose_tols:
            s.tol = tol
            run = s.new_run(args.seeds[0])
            s.window(run, args.seconds)
            c = harness.check(run)
            print(json.dumps({"loose_tol": {"tol": tol, **{
                k: v["value"] for k, v in c.items()},
                "answers": len(run.sent) + 1}}), flush=True)
    finally:
        s.close()
    t0 = time.perf_counter()
    ref = DirectSolve(runs[0].graph)
    log(f"float64 reference factored in {time.perf_counter() - t0!r} s")
    for run in runs[:args.control_seeds]:
        exact = [ref.solve(r.b) for r in [run.first] + run.sent]
        out = {"seed": run.seed}
        for dtype in ("bfloat16", "float32"):
            for r, x in zip([run.first] + run.sent, exact):
                r.x = round_to(x, dtype)
                r.status, r.relres = "converged", 0.0
            out[dtype] = harness.check(run)["host_relres_max"]["value"]
        print(json.dumps({"control": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
