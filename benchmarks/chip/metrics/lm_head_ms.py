"""Serve step: device time per execution of the jitted serve step, in
ms, of the ops under the ``lm_head`` scope (the final norm and the LM
head) in the traced slice."""
import scopes


def read(run):
    if run.trace is None or not run.trace.planes:
        return None
    return scopes.stage_ms(run.trace, scopes.SERVE_STEP).get("lm_head") or None
