"""Serve step: device time per execution of the jitted serve step, in
ms, of the ops under the ``moe.exchange`` scope (the dispatch's sort,
exchange and combine; the expert FFN nested in it is ``moe.expert``'s) in
the traced slice."""
import scopes


def read(run):
    if run.trace is None or not run.trace.planes:
        return None
    return scopes.stage_ms(run.trace, scopes.SERVE_STEP).get("moe.exchange") or None
