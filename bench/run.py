#!/usr/bin/env python3
"""Chip benchmark of the Laplacian solve service: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell (``BENCHMARK.json``) names a
configuration (``bench/configs/``: graph, ordering, preconditioner,
engine, tolerance) and a traffic mix (``bench/traffic/``).  One process
builds the graph on the host, measures the cold start (ordering,
``FactorCache.factor`` and the first solve on the cell's path, with
programs from the persistent compile cache at ``<checkout>/.jax_cache``),
warms the shapes the window uses, drives the traffic for ``--seconds``,
checks every answer against a float64 reference, and prints one JSON
line: with ``--trace 0`` the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace.

It exits with 2, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program's sources (``src/``)
are not in the checkout.

The process that starts never touches JAX: it runs the run in a child
process.  Where that child's cold start compiled (a checkout's first
run of the cell), the child stops there and a second child, which finds
every program in the persistent cache as a freshly started service
would, makes the run; set-up counts from the first process's start.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CHILD = "BENCH_CHILD"       # "first" or "last"; unset in the parent
STARTED = "BENCH_STARTED"   # the parent's start on the monotonic clock
COMPILED = 75                    # a first child's cold start compiled


def _die_with_parent() -> None:
    """In the child: be killed when the parent dies (Linux)."""
    import ctypes
    import signal
    try:
        ctypes.CDLL(None, use_errno=True).prctl(1, signal.SIGKILL)
    except (OSError, AttributeError):
        pass


def _parent(argv) -> int:
    cmd = [sys.executable, str(Path(__file__).resolve()), *argv]
    for attempt in ("first", "last"):
        env = dict(os.environ, **{CHILD: attempt, STARTED: repr(T_START)})
        rc = subprocess.call(cmd, env=env, preexec_fn=_die_with_parent)
        if rc != COMPILED:
            return rc
    return rc


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program sources at {ROOT / 'src'}", file=sys.stderr)
        return 2
    if CHILD not in os.environ:
        return _parent(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, trace_reduce
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    import jax
    import numpy as np

    log = lambda msg: print(f"bench: {msg}", flush=True)  # noqa: E731
    try:
        run = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            t_start=float(os.environ[STARTED]), log=log,
            exit_if_compiled=os.environ[CHILD] == "first")
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except harness.ColdStartCompiled as e:
        log(f"{e}: a fresh process measures it again")
        return COMPILED
    compared = harness.check(run)
    metrics = harness.read_metrics(
        run, cell.per_layer if args.trace else cell.end_to_end)
    lat = [r.latency_s for r in run.done]
    median = float(np.median(lat)) if lat else 0.0
    log(f"latency samples {len(lat)} (requests due in the window and "
        f"answered; median {median!r} s, longest {max(lat, default=0.0)!r}"
        f" s); answers checked {len(run.sent) + 1}")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": harness.passed(compared) and bool(run.sent),
           "attempted": len(run.sent) + 1, "failed": run.failed,
           "metrics": metrics, "device": device}
    bounds = harness.segment_bounds(run.window_trace)
    if args.trace and bounds is not None:
        tr, (lo, hi) = run.window_trace, bounds
        device["busy_s"] = trace_reduce.busy_ns(tr, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = {
            "device_ops": [list(x) for x in trace_reduce.top_ops(tr, lo, hi)],
            "idle_gaps": [list(x) for x in trace_reduce.top_gaps(tr, lo, hi)]}
    out["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
