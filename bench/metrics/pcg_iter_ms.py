"""Device-busy time of the traced part of the window over the PCG
passes run in it (a pass advances every active lane by one iteration;
passes are counted from the answers, see ``bench/lanes.py``)."""
from bench import harness


def read(run):
    w = harness.segment_work(run)
    return None if w is None else 1e3 * w["busy_s"] / w["passes"]
