"""Device time of the traced part of the window under the program's
``fleet_matvec`` kernel scope (the Laplacian's edge-list matvec) over
the PCG passes run in it."""
from bench import harness, scopes


def read(run):
    w, s = harness.segment_work(run), scopes.seconds(run, "fleet_matvec")
    return None if w is None or s is None else 1e3 * s / w["passes"]
