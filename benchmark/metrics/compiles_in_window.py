"""Layer: entry points. Backend compiles (or cache retrievals) that jax
reported between the start and the end of the window, from its monitoring
events. Should read 0: every program is warmed in set-up."""


def read(ctx):
    return float(ctx.compiles_in_window)
