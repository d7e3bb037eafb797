"""Device-idle time of the traced part of the window that falls inside
the engine driver thread's program spans (``engine/…``, ``frontend/…``),
over the engine ticks of the segment."""
from bench import harness, trace_reduce

DRIVER = ("engine/", "frontend/")


def read(run):
    tr, seg = run.window_trace, run.segment
    bounds = harness.segment_bounds(tr)
    if bounds is None or not tr.devices or seg.get("tick0") is None:
        return None
    host = trace_reduce.union([(s, e) for n, s, e in tr.spans
                               if n.startswith(DRIVER)])
    ticks = seg["tick1"] - seg["tick0"]
    if not host or ticks <= 0:
        return None
    idle = sum(e - s for gap in trace_reduce.gaps(tr, *bounds)
               for s, e in trace_reduce.clip(host, *gap))
    return 1e-6 * idle / ticks
