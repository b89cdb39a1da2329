"""Profiling hooks (SURVEY.md §5.1: the reference has nothing built in —
its closest analog is EvoXVisMonitor.record_time; users fall back on
``jax.profiler``). evox_tpu ships both as first-class monitors:

- :class:`StepTimerMonitor` — per-generation wall-clock durations via
  ordered host callbacks around the step (works inside ``run()``'s fused
  fori_loop too, since the callbacks are ordered effects inside the loop
  body).
- :func:`trace` — a context manager around ``jax.profiler`` that captures
  a TPU/XLA profile (TensorBoard format) for any code region, e.g.
  ``with profiler.trace("/tmp/tb"): state = wf.run(state, 100)``, with
  the program's own host spans (``evox:run`` ...) and the device's
  operations (named by scope: ``evox.ask`` ...) in one file on one clock
  (the names: core/instrument.py).
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Iterator, Optional

import jax
import numpy as np
from jax.experimental import io_callback

from ..core.monitor import Monitor
from .common import host0_sharding


class StepTimerMonitor(Monitor):
    """Records wall-clock duration of every generation.

    Times generations with ordered host callbacks (``io_callback``), so
    it needs a runtime that can call back into Python. The callback-free
    alternatives are :class:`~evox_tpu.monitors.TelemetryMonitor`
    (on-device per-generation statistics) and
    :class:`~evox_tpu.core.instrument.DispatchRecorder` (host-side
    compile/dispatch wall-clock).
    """
    # convention flag: this monitor streams through host callbacks
    # (io_callback/pure_callback) inside the traced step — consumed by
    # surfaces that cannot host callbacks at all (VectorizedWorkflow
    # fleets: a callback cannot run under vmap on ANY backend)
    uses_host_callbacks = True


    def __init__(self):
        self.start_times: list = []
        self.end_times: list = []

    def hooks(self):
        return ("pre_step", "post_step")

    def pre_step(self, mstate: Any) -> Any:
        io_callback(
            lambda: self.start_times.append(time.monotonic()),
            None,
            sharding=host0_sharding(),
            ordered=True,
        )
        return mstate

    def post_step(self, mstate: Any, wf_state: Any) -> Any:
        io_callback(
            lambda: self.end_times.append(time.monotonic()),
            None,
            sharding=host0_sharding(),
            ordered=True,
        )
        return mstate

    def get_step_times(self) -> np.ndarray:
        """(n_generations,) seconds per generation."""
        self.flush()
        n = min(len(self.start_times), len(self.end_times))
        return np.asarray(self.end_times[:n]) - np.asarray(self.start_times[:n])

    def summary(self) -> dict:
        t = self.get_step_times()
        if t.size == 0:
            return {"steps": 0}
        return {
            "steps": int(t.size),
            "mean_s": float(t.mean()),
            "p50_s": float(np.percentile(t, 50)),
            "p99_s": float(np.percentile(t, 99)),
            "total_s": float(t.sum()),
        }


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace (XLA/TPU timeline) of the region.

    The profiler starts with the options under which the program's host
    spans (``core.instrument.SPANS``: ``evox:init``, ``evox:step``,
    ``evox:run`` with ``evox:run/peel`` and ``evox:run/loop`` in it,
    ``evox:checkpoint/save``, ``evox:executor/host_eval``, ``evox:fetch``;
    the names of ``LOG_ONLY`` are in ``host_records()`` alone)
    and the device's operations share the file and the clock: host tracer
    level 2 (the program's spans and jax's own dispatch spans), the
    Python tracer off (every Python call as an event swamps the host
    plane and slows the host it measures). Each device operation's
    ``op_name`` carries the scope it was traced under (``evox.ask``,
    ``evox.evaluate/rollout_kernel``, ``evox.tell/peel`` ...).

    View with TensorBoard's profile plugin, or Perfetto when
    ``create_perfetto_link`` is set.
    """
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(
        log_dir,
        create_perfetto_link=create_perfetto_link,
        profiler_options=options,
    )
    try:
        yield
    finally:
        jax.profiler.stop_trace()
