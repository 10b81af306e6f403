"""Least time of the int8 weight matmuls in the traced window over the
summed device time of their kernel's events.  Each event's shapes come
from its HLO text, as dispatched: ``s8[M,N] custom-call(s8[M,K], s8[K,N]
...)``, giving 2MNK operations and M*K + K*N + M*N int8 bytes."""
import re

from bench import costs, trace_reduce

KERNEL = "int8_matmul"
SHAPES = re.compile(r"= s8\[(\d+),(\d+)\][^ ]* custom-call\(s8\[(\d+),(\d+)\]")


def read(x):
    ops = trace_reduce.kernel_ops(x.trace, KERNEL)
    spent = sum(t for _, t in ops.values())
    if not spent:
        return None
    p = x.peaks
    least = 0.0
    for name, (n_ev, _) in ops.items():
        m = SHAPES.search(name)
        if m is None:
            return None
        mm, n, m2, k = map(int, m.groups())
        flops, nbytes = costs.int8_matmul_call(mm, k, n)
        least += n_ev * costs.least_time_s(flops, nbytes, p["int8_ops_per_s"],
                                           p["hbm_bytes_per_s"])[0]
    return 100.0 * least / spent
