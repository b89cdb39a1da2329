"""The timed window, and a clock for what jax compiles.

The window is a closed loop of one client: a chunk, ``block_until_ready``,
the next, until the time is used up. Every chunk that starts is finished and
counted, and the window's length is the time to the end of the last one: a
rate is all the work over all the time.
"""

from __future__ import annotations

import time


class CompileClock:
    """Counts what jax traces, lowers and compiles, from its own monitoring
    events (the idea of ``chip_smoke.py``'s ``_CompileClock``).

    ``backend_compiles`` counts every backend compile or retrieval from the
    persistent cache: each is a program that was not ready when it was
    called. ``compile_seconds()`` is the length of the union of all tracing,
    lowering and compiling spans (nested traces report nested spans)."""

    _SPANS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.spans: list = []
        self.backend_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event: str, start: float, end: float, **_) -> None:
        if event in self._SPANS:
            self.spans.append((start, end))
        if event == self._SPANS[2]:
            self.backend_compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def compile_seconds(self) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self.spans):
            total += max(0.0, end - max(start, reach))
            reach = max(reach, end)
        return total


def run_window(chunk, state, seconds: float, annotate: bool = False) -> tuple:
    """Drive ``chunk`` from ``state`` for ``seconds``. Returns the last
    state, the wall seconds of every chunk, and the window's length.
    ``annotate`` writes a ``bench:chunk`` span round each chunk into the
    profiler's trace (the traced run only)."""
    if annotate:
        import jax

        def timed(s):
            with jax.profiler.TraceAnnotation("bench:chunk"):
                return chunk(s)
    else:
        timed = chunk
    chunk_s = []
    start = last = time.perf_counter()
    while last - start < seconds:
        state = timed(state)
        now = time.perf_counter()
        chunk_s.append(now - last)
        last = now
    return state, chunk_s, last - start
