"""Scheduler: 95th percentile, over window requests, of the time from
scheduled send to first admission (first seen in the running set after a
step); a request never admitted is left out."""
import numpy as np


def read(run):
    wait = [r.t_admit - r.t_sched for r in run.window_requests()
            if r.t_admit >= 0]
    return float(np.percentile(wait, 95)) * 1e3 if wait else None
