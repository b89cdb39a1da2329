"""Overlapped driver loop for host (non-jittable) problems.

The reference's Ray workflow gains throughput from its async dispatch
queue (reference workflows/distributed.py:361-369): the driver processes
monitor output while the workers' ``tell`` (step2) futures are still in
flight. This module is the single-process TPU-native analog for
``StdWorkflow`` with an external problem:

- the device ``tell``/``ask`` work is *dispatched* asynchronously (JAX's
  async dispatch) and computes while the host thread hands the next
  candidate batch to the rollout pool;
- the host problem's ``evaluate`` for generation ``g+1`` runs in a worker
  thread concurrently with the user's per-generation host work
  (``on_generation``: logging, plotting, metric computation, checkpoint
  saves) for generation ``g`` — the two dominant host-side costs overlap
  instead of serializing.

The data-dependency chain eval -> tell -> ask -> eval is untouched, so
results are bit-identical to ``wf.step`` loops (asserted in
tests/test_pipelined.py); only wall-clock changes. For jittable problems
use ``wf.run`` — a fused device loop beats any host pipelining.

Since PR 8 the loop itself lives in
:class:`~evox_tpu.core.executor.GenerationExecutor` (one executor, five
policies — see GUIDE.md §6): this module keeps the host-problem policy
entry point (``run_host_pipelined``), the IPOP recursion, and
``chunked_evaluate``, and adds the opt-in ``max_staleness=K`` stale-tell
mode the executor implements.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.executor import GenerationExecutor
from .checkpoint import WorkflowCheckpointer


def chunked_evaluate(problem, pstate, cand, eval_chunk: Optional[int]):
    """``problem.evaluate`` over row slices of at most ``eval_chunk``
    candidates, fitness concatenated — the degradation the supervisor
    applies when a full-batch host evaluation dies with OOM / HTTP 413
    (a payload too large for whatever serves the host problem).

    Bit-equivalence contract: chunking is invisible exactly when the
    host ``evaluate`` scores rows independently of their batch (true for
    deterministic per-candidate problems; NOT for farms that draw one
    seed per evaluate() CALL — those re-seed per chunk, see GUIDE.md §6).
    The problem state threads through the chunks in order and the LAST
    chunk's returned state is kept, matching the unchunked call for
    pass-through states.

    Return contract: device/dtype-consistent with the unchunked path. A
    problem returning device arrays gets a device concatenation (the
    old code forced every chunk to host via ``np.asarray`` and returned
    NumPy fitness while the unchunked path returned whatever ``evaluate``
    produced — a silent device→host→device round trip per chunk); a
    NumPy-returning host problem still gets NumPy. The caller
    (``pipeline_tell``) accepts either — nothing fetches until someone
    actually needs host values."""
    if eval_chunk is None:
        return problem.evaluate(pstate, cand)
    leaves = jax.tree.leaves(cand)
    n = leaves[0].shape[0]
    if eval_chunk < 1:
        raise ValueError(f"eval_chunk must be >= 1, got {eval_chunk}")
    if eval_chunk >= n:
        return problem.evaluate(pstate, cand)
    fits = []
    for lo in range(0, n, eval_chunk):
        hi = min(lo + eval_chunk, n)
        part = jax.tree.map(lambda x: x[lo:hi], cand)
        fit, pstate = problem.evaluate(pstate, part)
        fits.append(fit)
    if any(isinstance(f, jax.Array) for f in fits):
        # mirror the unchunked path's device residency: concatenate on
        # device instead of round-tripping every chunk through the host
        return jnp.concatenate([jnp.asarray(f) for f in fits], axis=0), pstate
    return np.concatenate([np.asarray(f) for f in fits], axis=0), pstate


def run_host_pipelined(
    wf,
    state,
    n_steps: int,
    on_generation: Optional[Callable[[int, Any, jax.Array], None]] = None,
    checkpointer: Optional[WorkflowCheckpointer] = None,
    resume_from: Any = None,
    restarts: Any = None,
    eval_chunk: Optional[int] = None,
    max_staleness: Optional[int] = None,
    executor: Optional[GenerationExecutor] = None,
):
    """Run ``n_steps`` generations of ``wf`` (a :class:`StdWorkflow` whose
    problem is external/host-side), overlapping host evaluation with
    device dispatch and with ``on_generation(gen_index, state, fitness)``
    host work of the previous generation. Returns the final state —
    identical to ``for _ in range(n_steps): state = wf.step(state)``.

    Crash safety: ``checkpointer=`` snapshots the state whenever
    ``state.generation`` crosses a multiple of its cadence (host-side,
    between dispatches — the next generation's evaluate is already in
    flight while the snapshot pickles, and the final state is always
    snapshotted). ``resume_from=`` (a
    :class:`~evox_tpu.workflows.checkpoint.WorkflowCheckpointer` or a
    directory) restores the newest intact snapshot and reinterprets
    ``n_steps`` as the TOTAL generation target. Note the snapshot holds
    only the workflow-state pytree: a host problem that draws
    per-generation seeds from its own RNG (the rollout farms) re-seeds
    fresh after a resume — resume bit-equivalence holds for host problems
    whose evaluate is deterministic (see GUIDE.md §6).

    Observability: ``instrument(wf)`` covers this loop — it wraps
    ``wf.pipeline_ask``/``wf.pipeline_tell``, which this driver calls
    through the workflow object, so per-half dispatch timings, retrace
    flags, and (with ``analyze=True``) the AOT roofline of both jitted
    halves land in ``run_report()`` exactly as for ``wf.run``; a
    :class:`~evox_tpu.problems.neuroevolution.process_farm.
    ProcessRolloutFarm` problem additionally contributes worker-health
    counter tracks to ``write_chrome_trace(extra_counters=
    farm.counter_tracks())``.

    ``eval_chunk=``: evaluate the candidate batch in host-side row
    slices of at most this many candidates (see :func:`chunked_evaluate`
    for the bit-equivalence contract) — the payload-size degradation the
    :class:`~evox_tpu.workflows.supervisor.RunSupervisor` halves on
    OOM / HTTP 413, also usable directly to keep request sizes
    bounded.

    ``max_staleness=K`` (opt-in; ``None`` — the default — defers to the
    passed ``executor``'s configured bound, else 0): admit tells up to
    ``K`` generations stale — up to ``K+1`` host evaluations in flight, each
    tell grafted onto the newest told state with its own matched
    (ask-artifacts, fitness) pair (stale-gradient ES; see
    :class:`~evox_tpu.core.executor.GenerationExecutor`). ``K=0``
    stays bit-identical to a ``wf.step`` loop; ``K>0`` trades
    per-update freshness for throughput when host evaluations can run
    concurrently and is gated by convergence tests, not equivalence.

    ``executor=``: the :class:`~evox_tpu.core.executor.
    GenerationExecutor` to drive (counters/overlap spans accumulate on
    it and surface in ``run_report()["executor"]``); a private default
    executor is created per call otherwise.
    """
    if not wf.external:
        raise ValueError(
            "run_host_pipelined is for external (host) problems; jittable "
            "problems should use wf.run()'s fused device loop"
        )
    if restarts is not None:
        # host-boundary IPOP (workflows/ipop.py): chunk the pipelined loop
        # at the policy cadence; each chunk is a plain pipelined run, the
        # doubling decision happens between chunks on the guarded counters
        from .ipop import ipop_run

        return ipop_run(
            wf,
            state,
            n_steps,
            restarts,
            segment=lambda w, s, c, ck: run_host_pipelined(
                w, s, c, on_generation=on_generation, checkpointer=ck,
                eval_chunk=eval_chunk, max_staleness=max_staleness,
                executor=executor,
            ),
            checkpointer=checkpointer,
            resume_from=resume_from,
        )
    ex = executor if executor is not None else GenerationExecutor(
        max_staleness=max_staleness or 0
    )
    # the executor owns the loop (double-buffered dispatch, background
    # checkpoint/hook lanes, resume resolution, stale window); this
    # function is the host-problem POLICY entry point kept for API
    # stability and the IPOP recursion above
    return ex.run_host(
        wf,
        state,
        n_steps,
        on_generation=on_generation,
        checkpointer=checkpointer,
        resume_from=resume_from,
        eval_chunk=eval_chunk,
        max_staleness=max_staleness,
    )
