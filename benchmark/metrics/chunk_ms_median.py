"""Layer: entry points. Median wall time of a chunk in the traced window: the
steady statistic beside the tail (``chunk_ms_p90``) and ``chunk_ms_longest``."""

import statistics


def read(ctx):
    return statistics.median(ctx.window["chunk_ms"])
