"""Device time of the traced part of the window under the program's
``trisolve_fleet`` kernel scope (its sweeps' gathers and SpMVs
included) over the PCG passes run in it."""
from bench import harness, scopes


def read(run):
    w, s = harness.segment_work(run), scopes.seconds(run, "trisolve_fleet")
    return None if w is None or s is None else 1e3 * s / w["passes"]
