"""Share of the fleet step and solve dispatches in the traced part of
the window that took the shared-index path: the program's
``fleet/shared`` spans over those and its ``fleet/per_lane`` spans (one
around each dispatch, counted where it starts)."""
from bench import harness

PATHS = ("fleet/shared", "fleet/per_lane")


def read(run):
    bounds = harness.segment_bounds(run.window_trace)
    if bounds is None:
        return None
    lo, hi = bounds
    starts = [n for n, s, _ in run.window_trace.spans
              if n in PATHS and lo <= s < hi]
    return starts.count(PATHS[0]) / len(starts) if starts else None
