"""95th percentile, over every stream in flight at a fault commit in the
window that had delivered a token, of the gap the fault put in it: its
last token before STALL_BEGIN to its first token after."""
import numpy as np


def read(run):
    stalls = [s for f in run.faults
              if run.t_window <= f.t_commit < run.t_window_end
              for s in f.stalls_s]
    return float(np.percentile(stalls, 95)) * 1e3 if stalls else None
