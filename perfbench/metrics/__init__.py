"""Per-layer metric readers, one module each, named as the metric.

``read(ctx) -> float | None`` takes a :class:`perfbench.harness.
LayerContext` of a traced window and returns the metric, or ``None``
where the window holds nothing to read (the harness then leaves the
metric out of the result line).
"""
