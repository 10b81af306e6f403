"""Least time of the ragged paged attention calls in the traced window (live
KV read once, queries read and outputs written, QK and PV operations; one
call per layer per step) over the summed device time of its kernel's
events."""
from bench import costs, trace_reduce

KERNEL = "ragged_paged_flash"


def read(x):
    spent = trace_reduce.kernel_seconds(x.trace, KERNEL)
    if not spent or not x.steps:
        return None
    p = x.peaks
    least = 0.0
    for s in x.steps:
        ops, nbytes = costs.ragged_attention_call(x.dims, s["rows"],
                                                  kv_bits=x.kv_bits)
        least += x.dims.n_layers * costs.least_time_s(
            ops, nbytes, p["bf16_flops_per_s"], p["hbm_bytes_per_s"])[0]
    return 100.0 * least / spent
