"""Device: share of the traced slice in which no operation ran on the
chip (1 - union of op intervals / traced window), in percent."""


def read(run):
    if run.trace is None or not run.trace.planes or len(run.trace_window) != 2:
        return None
    window = run.trace_window[1] - run.trace_window[0]
    return 100.0 * (1.0 - run.trace.busy_s / window)
