"""Layer: entry points. Median length of the ``evox:run`` spans in the traced
stretch: how long ``StdWorkflow.run`` holds the host for a chunk, from its
call to its return (the dispatch; the device's work ends later, in the
harness's ``block_until_ready``)."""

from benchmark.lib import scoped


def read(ctx):
    spans = scoped.run_spans(ctx)
    return scoped.median([e.dur_ns / 1e6 for e in spans]) if spans else None
