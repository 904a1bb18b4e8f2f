"""95th percentile over every gap between consecutive delivered tokens of
every request sent in the window, stalls included."""
import numpy as np


def read(run):
    gaps = [b - a for r in run.window_requests()
            for a, b in zip(r.token_t, r.token_t[1:])]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
