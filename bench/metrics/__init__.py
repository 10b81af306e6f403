"""Per-layer metric readers, one file per metric, found by the metric's
name.  Each defines ``read(inputs) -> float | None`` over
``bench.run.LayerInputs``; a reader that finds nothing to read returns
None and the metric is left out of the result line."""
