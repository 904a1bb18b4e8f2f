"""Serve step: device time per execution of the jitted serve step, in
ms, of the ops under the ``moe.route`` scope (the router logits, the
routing, the replica choice through the membership) in the traced
slice."""
import scopes


def read(run):
    if run.trace is None or not run.trace.planes:
        return None
    return scopes.stage_ms(run.trace, scopes.SERVE_STEP).get("moe.route") or None
