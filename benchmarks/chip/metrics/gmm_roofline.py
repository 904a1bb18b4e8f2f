"""Kernels: the gmm grouped matmul's least time on the chip over its
measured time, summed over its calls in the traced slice, in percent
(bound by HBM at these sizes).
Each call is one projection of one MoE layer (w_in, w_gate, w_out, all of
the same size) over the slots this chip holds: the rows dispatched to them
(max_batch x top_k x their share of the slots, idle rows included, as the
step computes them) multiplied once, and each slot's weights read once."""
import flops


def read(run):
    if run.trace is None or not run.trace.planes or not run.peak:
        return None
    m, dep = run.model, run.deployment
    slots, share = flops.slots_held(dep)
    rows = dep["max_batch"] * m["num_experts_per_tok"] * share
    d_, de = m["hidden_size"], m[flops.expert_width_key(m)]
    # the trace names a Pallas kernel only as a tpu_custom_call; gmm's is
    # the one that takes the slot-stacked weights [slots, d, de] or
    # [slots, de, d]
    d = run.trace.op_durations(
        rf"bf16\[{slots},({d_},{de}|{de},{d_})\]\S* %[\w.\-]+\), "
        rf"custom_call_target=\"tpu_custom_call\"")
    if not d:
        return None
    f, b = flops.gmm_call(rows, slots, d_, de)
    least, _ = flops.roofline_s(f, b, run.peak)
    return 100.0 * least * len(d) / sum(d)
