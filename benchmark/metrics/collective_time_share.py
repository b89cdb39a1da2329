"""Layer: mesh / collectives. Device time of all-gather, all-reduce,
collective-permute, all-to-all and reduce-scatter events on the fullest device,
as a share of the traced stretch. A cell on one chip has none and reports
nothing."""

from benchmark.lib import trace as tr


def read(ctx):
    ns = tr.union_ns([e for e in ctx.events if tr.COLLECTIVE.search(e.name)])
    return 100.0 * ns / ctx.stretch_ns if ns else None
