"""Layer: device. Share of the traced stretch in which no operation ran on the
fullest device: 1 less the union of its operations' intervals over the
stretch. The stretch runs from the first chunk's start to the last chunk's
end, so the host's work between chunks is inside it."""


def read(ctx):
    if not ctx.events:
        return None
    return 100.0 * (1.0 - ctx.busy_ns / ctx.stretch_ns)
