"""Median device-idle gap between consecutive ragged-step executions in the
trace: the host's planning, packing, transfer and sync between steps."""
import numpy as np


def read(x):
    ex = (x.trace or {}).get("step_exec") or []
    gaps = [b[0] - (a[0] + a[1]) for a, b in zip(ex, ex[1:])]
    return float(np.median(gaps)) * 1e3 if gaps else None
