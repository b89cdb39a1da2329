"""Per-layer metrics: one reader a file, ``read(ctx) -> float | None``, found by
the metric's name. ``ctx`` is a ``benchmark.lib.harness.TraceContext``. A reader
that finds nothing to read returns None and the metric is left out."""
