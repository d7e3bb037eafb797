"""Records ``data/cpu_spans.xplane.pb.gz`` and ``data/cpu_spans.json.gz``,
the small trace and scope tables that ``test_scopes.py`` reads:

    JAX_PLATFORMS=cpu python3 bench/tests/record_cpu_spans.py

One profiler session: a ``cold_start/factor`` span around
``FactorCache.factor`` of a 4 × 4 grid, then a segment
(``segment/start`` … ``segment/stop``) in which one request is served
through a frontend and engine (to tol 1e-3: a short trace).  The JSON
file holds the engine's tick count at the segment's two ends and the
program's scope tables (``repro.obs.tracing.scope_tables``)."""
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.solver import FactorCache  # noqa: E402
from repro.data import graphs  # noqa: E402
from repro.obs import tracing  # noqa: E402
from repro.serve import SolveEngine, SolveFrontend  # noqa: E402

OUT = HERE / "data" / "cpu_spans"


def _mark(name):
    with jax.profiler.TraceAnnotation(name):
        time.sleep(1e-4)


def main():
    g = graphs.grid2d(4, 4, seed=5)
    b = np.random.default_rng(0).standard_normal(g.n).astype(np.float32)
    b -= b.mean()
    kw = dict(tol=1e-3, maxiter=100)
    # compile everything once, outside the trace
    warm = FactorCache(fill_slack=64)
    warm.factor(g, jax.random.key(0), graph_id="g")
    with SolveFrontend(SolveEngine(warm, slots=2, iters_per_tick=4)) as fe:
        fe.submit("g", b, **kw).result()

    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("cold_start/factor"):
        cache = FactorCache(fill_slack=64)
        h = cache.factor(g, jax.random.key(0), graph_id="g")
        jax.block_until_ready(h.fleet.arrays)
    engine = SolveEngine(cache, slots=2, iters_per_tick=4)
    with SolveFrontend(engine) as fe:
        _mark("segment/start")
        tick0 = engine.ticks
        fe.submit("g", b, **kw).result()
        tick1 = engine.ticks
        _mark("segment/stop")
    jax.profiler.stop_trace()
    src, = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))
    trace = OUT.with_suffix(".xplane.pb.gz")
    trace.write_bytes(gzip.compress(Path(src).read_bytes()))
    shutil.rmtree(d)
    tables = {f"{m}|{fp}": t for (m, fp), t in tracing.scope_tables().items()}
    meta = OUT.with_suffix(".json.gz")
    meta.write_bytes(gzip.compress(json.dumps(
        {"tick0": tick0, "tick1": tick1, "tables": tables}).encode()))
    for p in (trace, meta):
        print(p, p.stat().st_size)


if __name__ == "__main__":
    main()
