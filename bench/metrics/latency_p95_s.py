"""95th percentile, over every request due in the window and answered,
of the time from when it was due to when its client held the answer."""
import numpy as np


def read(run):
    lat = [r.latency_s for r in run.done]
    return float(np.percentile(lat, 95)) if lat else None
