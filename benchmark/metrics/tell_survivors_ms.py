"""Layer: tell. Device time a generation under ``evox.tell/crowding`` plus
``evox.tell/survivors``: crowding distance, the truncation's sort and the
gathers of the survivors."""

from benchmark.lib import scoped


def read(ctx):
    return scoped.scope_ms(ctx, ("evox.tell", "crowding"), ("evox.tell", "survivors"))
