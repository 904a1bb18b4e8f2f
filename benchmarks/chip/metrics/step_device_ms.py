"""Serve step: mean device time of one execution of the jitted serve step
in the traced slice."""

MODULE = r"jit_serve_step"


def read(run):
    if run.trace is None or not run.trace.planes:
        return None
    d = run.trace.module_durations(MODULE)
    return 1e3 * sum(d) / len(d) if d else None
