"""Layer: entry points. The longest chunk of the traced window, by the
host's clock: what stalls a chunk (the host late with the next dispatch, the
search in its slower kind of generation) shows here. It stands per layer
because a tail of the chunks follows the seed where the search sets a
generation's work (PERF.md section 2), so no bound holds it there."""


def read(ctx):
    return max(ctx.window["chunk_ms"])
