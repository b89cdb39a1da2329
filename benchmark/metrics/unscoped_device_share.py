"""Layer: whole step. Share of the fullest device's busy time whose
operations carry none of ``evox.ask``, ``evox.evaluate``, ``evox.tell``,
``evox.constrain``, ``evox.monitors`` in their ``op_name``: the loop's
plumbing, copies the compiler inserted (they have no ``op_name``), and the
guard that the scopes stay complete. Nothing where no operation carries a
scope at all (a program from before the scopes)."""

from benchmark.lib import scoped


def read(ctx):
    view = scoped.load(ctx)
    if view is None or not ctx.busy_ns:
        return None
    unscoped = scoped.unscoped_ns(view)
    if unscoped >= sum(view.own_ns.values()):
        return None
    return 100.0 * unscoped / ctx.busy_ns
