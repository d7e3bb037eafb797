"""The reader of ``shared_index_share``: the program's ``fleet/shared``
and ``fleet/per_lane`` dispatch spans that start inside the traced
segment."""
from types import SimpleNamespace

from bench import harness
from bench import trace_reduce as tr


def _read(spans):
    trace = tr.Trace(spans=[("segment/start", 0.0, 10.0),
                            ("segment/stop", 100.0, 110.0), *spans],
                     devices=1)
    return harness._load_module(
        harness.HERE / "metrics" / "shared_index_share.py").read(
            SimpleNamespace(window_trace=trace, segment={}))


def test_share_of_the_segment_dispatches():
    assert _read([("fleet/shared", 20.0, 21.0),
                  ("fleet/per_lane", 30.0, 31.0),
                  ("fleet/shared", 40.0, 41.0),
                  ("fleet/shared", 60.0, 61.0),
                  # outside the segment: not counted
                  ("fleet/per_lane", 5.0, 6.0),
                  ("fleet/per_lane", 105.0, 106.0)]) == 0.75


def test_a_program_without_the_spans_reads_nothing():
    assert _read([("engine/step", 20.0, 21.0),
                  ("solver/solve", 30.0, 31.0)]) is None
