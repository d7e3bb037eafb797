"""The trace reduction, on a small trace recorded on the CPU
(``record_cpu_trace.py``).  On the CPU, XLA's ops run on host threads
(``tf_XLA…`` lines); on a TPU they are the ``XLA Ops`` lines of the
device planes, which is what ``trace_reduce.load`` reads by default."""
from pathlib import Path

import pytest

from bench import harness, trace_reduce as tr

TRACE = Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb"
OPS = {"wrapped_sine", "dot_general.1", "broadcast_add_fusion"}


@pytest.fixture(scope="module")
def trace():
    t = tr.load(TRACE, op_line=lambda plane, line: plane == "/host:CPU"
                and line.startswith("tf_XLA"))
    t.ops = [o for o in t.ops if o[0] in OPS]
    return t


def test_spans_and_ops(trace):
    names = [s[0] for s in trace.spans]
    assert names == ["segment/start", "client/solve", "client/make_rhs",
                     "client/await", "segment/stop"]
    assert {o[0] for o in trace.ops} == OPS
    assert len(trace.ops) == 12          # four calls of three ops


def test_busy_and_idle_intervals(trace):
    lo, hi = harness.segment_bounds(trace)
    assert (lo, hi) == (192158.0, 55104084.0)
    busy = tr.busy_ns(trace, lo, hi)
    assert busy == 4101695.0
    assert len(tr.union([(s, e) for _, s, e in trace.ops])) == 12
    idle = sum(e - s for s, e in tr.gaps(trace, lo, hi))
    assert busy + idle == pytest.approx(hi - lo, abs=1e-3)


def test_gap_names(trace):
    lo, hi = harness.segment_bounds(trace)
    (name, secs), *rest = tr.top_gaps(trace, lo, hi, k=3)
    # the 50 ms sleep between the solves and the last call
    assert name == "client/make_rhs" and 0.05 <= secs < 0.06
    assert all(s < 0.001 for _, s in rest)
    # a gap under a waiting span alone is not attributed to it
    wait = trace_span(trace, "client/await")
    assert tr.name_gap(trace, (wait[0], wait[0] + 1.0)) == "unattributed"


def test_top_ops(trace):
    lo, hi = harness.segment_bounds(trace)
    top = dict(tr.top_ops(trace, lo, hi))
    assert set(top) == {f"?/{o}" for o in OPS}
    assert sum(top.values()) == pytest.approx(4101695.0e-9)


def trace_span(trace, name):
    return tr.span_named(trace, name)


def test_tpu_shaped_trace():
    """A TPU names each op event by its whole HLO instruction and nests
    ops inside ``while``/``cond`` ops: the breakdown lists leaf ops by
    instruction name under their program; busy time is the union of
    program runs and ops."""
    t = tr.Trace(
        modules=[("jit_step(1)", 0.0, 4e6), ("jit_gather(2)", 5e6, 5.5e6),
                 ("jit_step(1)", 6e6, 9e6)],
        ops=[("%while.3 = (f32[8]) while(f32[8] %p)", 0.0, 4e6),
             ("%fusion.1 = f32[8] fusion(f32[8] %a)", 0.0, 3e6),
             ("%gather.2 = f32[8] gather(f32[8] %a)", 3e6, 4e6),
             ("%fusion.1 = f32[8] fusion(f32[8] %a)", 6e6, 9e6),
             ("%copy.4 = f32[8] copy(f32[8] %x)", 5e6, 5.5e6)],
        spans=[("client/submit", 4e6, 5e6)], devices=1)
    assert tr.busy_ns(t, 0.0, 10e6) == 7.5e6
    assert tr.gaps(t, 0.0, 10e6) == [(4e6, 5e6), (5.5e6, 6e6), (9e6, 10e6)]
    top = tr.top_ops(t, 0.0, 10e6)
    assert [name for name, _ in top] == ["jit_step/%fusion.1",
                                         "jit_step/%gather.2",
                                         "jit_gather/%copy.4"]
    assert [s for _, s in top] == pytest.approx([6e-3, 1e-3, 5e-4])
    assert tr.top_gaps(t, 0.0, 10e6, k=1) == [("client/submit", 1e-3)]
