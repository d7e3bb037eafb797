"""Device-busy seconds inside the traced cold start's
``cold_start/factor`` span (``FactorCache.factor``)."""
from bench import trace_reduce


def read(run):
    tr = run.cold_trace
    span = None if tr is None or not tr.devices else trace_reduce.span_named(
        tr, "cold_start/factor")
    return None if span is None else trace_reduce.busy_ns(tr, *span) * 1e-9
