"""Tokens delivered inside the window, any request's, over the window's
seconds (a closed batch's window ends at its last step)."""


def read(run):
    n = sum(1 for r in run.requests for t in r.token_t
            if run.t_window < t <= run.t_window_end)
    return n / (run.t_window_end - run.t_window)
