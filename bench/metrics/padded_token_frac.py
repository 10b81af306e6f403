"""Padding rows over dispatched rows of the ragged stream, from the engine's
counters over the whole window."""


def read(x):
    d = x.counters.get("dispatched_tokens", 0)
    return x.counters["padded_tokens"] / d if d else None
