"""Kernels: device time per execution of the jitted serve step, in ms, of
the ops under the ``moe.expert`` scope (the three ``gmm`` calls and the
SwiGLU) in the traced slice."""
import scopes


def read(run):
    if run.trace is None or not run.trace.planes:
        return None
    return scopes.stage_ms(run.trace, scopes.SERVE_STEP).get("moe.expert") or None
