"""Serve step: model FLOPs of the live tokens the traced steps fed (each at
its own context length), of the share of the routed experts this chip
holds, over the device time of the serve step's executions times the
chip's bf16 peak, in percent."""
import flops

MODULE = r"jit_serve_step"


def read(run):
    if run.trace is None or not run.trace.planes or not run.trace_window or not run.peak:
        return None
    d = run.trace.module_durations(MODULE)
    steps = run.steps_between(*run.trace_window)
    if not d or not steps:
        return None
    # per step: the positions fed, and the sum of their context lengths
    m, dep = run.model, run.deployment
    per_step = sum(
        fed * flops.token_flops(m, 0, dep)
        + (flops.token_flops(m, ctx, dep) - flops.token_flops(m, 0, dep))
        for _, _, fed, ctx in steps) / len(steps)
    return 100.0 * per_step / (sum(d) / len(d)
                               * run.peak["bf16_flops_per_s"])
