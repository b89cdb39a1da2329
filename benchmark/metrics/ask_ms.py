"""Layer: ask. Device time a generation under the ``evox.ask`` scope
(``StdWorkflow._dispatch_ask``: the algorithm's ``ask`` or ``init_ask``): own
time of the fullest device's operations whose ``op_name`` carries the scope,
over the generations of the traced stretch."""

from benchmark.lib import scoped


def read(ctx):
    return scoped.scope_ms(ctx, ("evox.ask",))
