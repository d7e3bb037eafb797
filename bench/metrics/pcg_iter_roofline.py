"""Least bytes the traced PCG passes needed (``bench/work.py``: true
nnz, m and n) over their device-busy time × the chip's peak HBM
bandwidth, in percent."""
from bench import harness, work


def read(run):
    w = harness.segment_work(run)
    if w is None or w["busy_s"] <= 0:
        return None
    bw = work.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * w["bytes"] / (w["busy_s"] * bw)
