"""1 − device-busy time / length of the traced part of the window (busy:
the union of the device's op intervals)."""
from bench import harness


def read(run):
    w = harness.segment_work(run)
    return None if w is None else 1.0 - w["busy_s"] / w["span_s"]
