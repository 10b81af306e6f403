"""Model operations of the real rows served in the traced window (every
weight matmul and causal attention, from shapes) over the ragged steps'
device time times the chip's peak: bf16, or int8 for W8A8."""
from bench import costs


def read(x):
    t = x.trace or {}
    busy = sum(d for _, d in t.get("step_exec") or [])
    if not busy or not x.steps:
        return None
    flops = sum(costs.step_model_flops(x.dims, s["rows"]) for s in x.steps)
    peak = x.peaks["int8_ops_per_s"] if x.w8a8 else x.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (busy * peak)
