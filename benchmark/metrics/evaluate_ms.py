"""Layer: evaluate. Device time a generation under the ``evox.evaluate``
scope: ``pop_transforms`` (the genome decoded), the ``"pop"`` constraints and
the problem's ``evaluate``, the rollout kernel included."""

from benchmark.lib import scoped


def read(ctx):
    return scoped.scope_ms(ctx, ("evox.evaluate",))
