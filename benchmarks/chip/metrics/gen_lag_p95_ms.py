"""Load generator: 95th percentile of how late each window request was
submitted after its scheduled send time."""
import numpy as np


def read(run):
    lag = [r.t_submit - r.t_sched for r in run.window_requests()
           if r.t_submit >= 0]
    return float(np.percentile(lag, 95)) * 1e3 if lag else None
