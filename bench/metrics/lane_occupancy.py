"""Lane-iterations advanced over (engine step calls × iterations per
tick × slots), in the traced part of the window."""
from bench import harness


def read(run):
    w = harness.segment_work(run)
    if w is None or run.cell.mix["path"] != "served" or not w["steps"]:
        return None
    cfg = run.cell.config
    return w["advances"] / (w["steps"] * int(cfg["iters_per_tick"])
                            * int(cfg["slots"]))
