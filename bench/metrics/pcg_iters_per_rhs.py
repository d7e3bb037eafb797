"""Mean PCG iterations over the columns the window answered."""
import numpy as np


def read(run):
    its = [i for r in run.converged for i in np.atleast_1d(r.iters)]
    return float(np.mean(its)) if its else None
