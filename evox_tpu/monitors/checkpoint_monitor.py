"""CheckpointMonitor — periodic auto-checkpointing of the whole workflow
state (SURVEY.md §5.4: the reference has save/load but no auto-checkpoint
cadence; this closes that gap).

A ``post_step`` hook checks the save predicate ON DEVICE and routes through
``lax.cond`` so the device-to-host copy of the state happens only on save
generations — off-generations execute an operand-free no-op callback, so
large populations pay no transfer. Saves are atomic (tmp + rename) and the
newest ``keep`` snapshots are retained. Restore with :meth:`latest` (which
also finds checkpoints left by a previous process) or
``evox_tpu.core.state_io.load(path, backend="pickle")`` — the saved object
is the full ``StdWorkflowState`` pytree with numpy leaves, which drops
straight back into ``wf.run``.

The save rides ``io_callback``, so it needs a runtime that can call back
into Python and cannot run under ``vmap``; the callback-free
:class:`~evox_tpu.workflows.checkpoint.WorkflowCheckpointer` snapshots
host-side between dispatches instead.
"""

from __future__ import annotations

import os
import pickle
import warnings
from pathlib import Path
from typing import Any, List, Optional

import jax
from jax.experimental import io_callback

from ..core.monitor import Monitor
from .common import host0_sharding


class CheckpointMonitor(Monitor):
    # convention flag: this monitor streams through host callbacks
    # (io_callback/pure_callback) inside the traced step — consumed by
    # surfaces that cannot host callbacks at all (VectorizedWorkflow
    # fleets: a callback cannot run under vmap on ANY backend)
    uses_host_callbacks = True

    def __init__(self, directory: str, every: int = 10, keep: int = 3):
        if every < 1:
            raise ValueError(f"every must be >= 1, got {every}")
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self.every = every
        self.keep = keep
        # adopt checkpoints from a previous process so crash-recovery and
        # keep-pruning see them
        self.saved: List[Path] = sorted(self.directory.glob("ckpt_????????"))

    def hooks(self):
        return ("post_step",)

    def post_step(self, mstate: Any, wf_state: Any) -> Any:
        def save():
            io_callback(
                self._save,
                None,
                wf_state.generation,
                wf_state,
                sharding=host0_sharding(),
            )

        def skip():
            io_callback(self._noop, None, sharding=host0_sharding())

        jax.lax.cond(wf_state.generation % self.every == 0, save, skip)
        return mstate

    def _noop(self):
        pass

    def _save(self, generation, wf_state):
        gen = int(generation)
        path = self.directory / f"ckpt_{gen:08d}"
        tmp = path.with_suffix(".tmp")
        with open(tmp, "wb") as f:
            pickle.dump(wf_state, f)
        os.replace(tmp, path)  # atomic: never leave a torn checkpoint
        if path in self.saved:  # re-saving a generation after a restore
            self.saved.remove(path)
        self.saved.append(path)
        while len(self.saved) > self.keep:
            old = self.saved.pop(0)
            try:
                old.unlink()
            except FileNotFoundError:
                pass

    def latest(self) -> Any:
        """Load the newest INTACT checkpoint (None if nothing usable).

        A corrupt/torn snapshot (killed mid-write by a crash that predates
        the atomic rename, disk trouble, partial copy) is skipped with a
        warning and the next-older one is tried — restore never raises
        mid-recovery because of one bad file."""
        self.flush()
        for path in reversed(self.saved):
            try:
                with open(path, "rb") as f:
                    return pickle.load(f)
            except Exception as e:  # unpicklable/truncated/unreadable
                warnings.warn(
                    f"skipping corrupt checkpoint {path.name}: {e}",
                    stacklevel=2,
                )
        return None
