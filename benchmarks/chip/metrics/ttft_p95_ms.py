"""95th percentile, over every request sent in the window, of the time
from its scheduled send to its first token; a request that never delivers
one counts at the end of the drain. A closed batch sends nothing: no
request has a send time to count from."""
import numpy as np


def read(run):
    reqs = [r for r in run.window_requests() if r.t_sched >= 0]
    if not reqs:
        return None
    end = max(max((r.token_t[-1] for r in reqs if r.token_t),
                  default=run.t_window_end), run.t_window_end)
    ttft = [(r.token_t[0] if r.token_t else end) - r.t_sched for r in reqs]
    return float(np.percentile(ttft, 95)) * 1e3
