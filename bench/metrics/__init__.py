"""Per-layer metric readers, one file per metric, found by the metric's
name (``registry.metric_reader``). Each has ``read(run) -> float | None``
and returns ``None`` where its cell gives it nothing to read."""
