"""Wall seconds of the traced cold start's ``cold_start/factor`` span
less the device-busy seconds inside it."""
from bench import trace_reduce


def read(run):
    tr = run.cold_trace
    span = None if tr is None or not tr.devices else trace_reduce.span_named(
        tr, "cold_start/factor")
    if span is None:
        return None
    return (span[1] - span[0] - trace_reduce.busy_ns(tr, *span)) * 1e-9
