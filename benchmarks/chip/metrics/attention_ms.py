"""Serve step: device time per execution of the jitted serve step, in
ms, of the ops under the ``attention`` scope (the QKV projections, the
cache read and write, attention, the output projection) in the traced
slice."""
import scopes


def read(run):
    if run.trace is None or not run.trace.planes:
        return None
    return scopes.stage_ms(run.trace, scopes.SERVE_STEP).get("attention") or None
