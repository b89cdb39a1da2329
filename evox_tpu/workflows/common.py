"""Plumbing shared by the workflow implementations."""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.instrument import (
    CONSTRAIN,
    FIT_TRANSFORMS,
    MONITORS,
    RUN_DISPATCH,
    RUN_LOOP,
    RUN_PEEL,
    RUN_TRIP_COUNT,
    TELL,
    scope,
    span,
)
from ..core.monitor import HOOK_NAMES, Monitor
from ..core.problem import Problem


def build_hook_table(monitors: Sequence[Monitor]) -> Dict[str, Tuple[int, ...]]:
    """name -> indices of the monitors implementing that hook."""
    return {
        name: tuple(i for i, m in enumerate(monitors) if name in m.hooks())
        for name in HOOK_NAMES
    }


def run_hooks(
    monitors: Sequence[Monitor],
    table: Dict[str, Tuple[int, ...]],
    name: str,
    mstates: list,
    *args: Any,
) -> None:
    """Dispatch one hook across monitors, updating ``mstates`` in place.
    What the hooks trace is named ``evox.monitors/<hook>``."""
    if not table[name]:
        return
    with scope(MONITORS), scope(name):
        for i in table[name]:
            mstates[i] = getattr(monitors[i], name)(mstates[i], *args)


def finish_step(
    monitors: Sequence[Monitor],
    table: Dict[str, Tuple[int, ...]],
    new_state: Any,
) -> Any:
    """Run the ``post_step`` hooks against the otherwise-final workflow
    state (so monitors observe exactly what the step returns), then fold
    their updated states back in."""
    mstates = list(new_state.monitors)
    run_hooks(monitors, table, "post_step", mstates, new_state)
    return new_state.replace(monitors=tuple(mstates))


def make_run_loop(step_impl: Callable, donate: bool = False) -> Callable:
    """Jitted ``(state, n) -> state`` running ``step_impl`` n times in one
    on-device ``fori_loop``; the trip count is a traced operand, so one
    compilation covers every ``n``.

    ``donate=True`` donates the state carry (``donate_argnums=0``): XLA
    aliases the input state's buffers into the loop carry and output
    instead of double-buffering them across the program boundary — the
    aliasing shows up as ``alias_bytes`` in ``memory_analysis()`` and as
    reduced peak bytes in ``run_report()["roofline"]``. The donated input
    is INVALIDATED after the call. Default False (matching the
    workflows' ``donate_carries`` default): whoever turns it on owns the
    snapshot-before-donate contract — the loop must only ever be fed
    states its driver produced itself. :func:`fused_run` (the driver
    behind ``StdWorkflow.run``/``IslandWorkflow.run``) honors it by
    advancing caller-owned states one non-donating ``wf.step`` first, so
    checkpoints are always taken from states the loop never donates.

    The program is named: a trace shows the module as ``jit_run_loop`` and
    the host's dispatch as ``PjitFunction(run_loop)``."""

    def run_loop(s, n):
        return jax.lax.fori_loop(0, n, lambda _, x: step_impl(x), s)

    return jax.jit(run_loop, donate_argnums=(0,) if donate else ())


def fused_run(wf: Any, state: Any, n_steps: int) -> Any:
    """Shared ``run()`` body: peel the first generation eagerly through
    the non-donating ``wf.step`` — both for the init_ask dispatch (the
    loop carry stays type-stable) and so the CALLER's state buffers are
    never donated (the step's output is a fresh intermediate owned by
    this function; jax 0.4.x does not forward unchanged inputs to
    outputs, verified in tests/test_dtype_policy.py) — then hand the rest
    to the donated ``wf._run_loop`` (or an eager Python loop when
    ``wf.jit_step=False``)."""
    if n_steps <= 0:
        return state
    # the peel is mandatory when the loop donates: without it a warm
    # caller state would be handed straight to the donated loop and the
    # caller's arrays (re-timing loops, checkpointer snapshots, test
    # fixtures) would be invalidated under it
    if state.first_step or getattr(wf, "donate_carries", False):
        with span(RUN_PEEL):
            state = wf.step(state)
        n_steps -= 1
    if not wf.jit_step:
        for _ in range(n_steps):
            state = wf._step_impl(state)
        return state
    if n_steps > 0:
        # the span brackets the two things the host does before the device
        # starts on the chunk, and the host log parts them (log-only
        # records: core/instrument.py LOG_ONLY): the trip count's own
        # little program and transfer, and the loop's jitted call, which
        # has two speeds (PERF.md section 6, PR 34). CPU time near the
        # dispatch's wall time says the thread computed (jax's Python
        # path); far under it, that it was blocked
        with span(RUN_LOOP, n_steps=n_steps):
            with span(RUN_TRIP_COUNT, annotate=False):
                n = jnp.asarray(n_steps, dtype=jnp.int32)
            with span(RUN_DISPATCH, annotate=False) as dispatch:
                cpu_ns = time.thread_time_ns()
                state = wf._run_loop(state, n)
                dispatch.args["cpu_ns"] = time.thread_time_ns() - cpu_ns
    return state


def ingest_fitness(
    wf: Any,
    astate: Any,
    mstates: list,
    fitness: jax.Array,
    use_init: bool,
) -> Any:
    """The tell half every workflow variant shares once the fitness is
    FINAL (sign-flipped, quarantined/filled): fit_transforms → pre_tell
    hook → ``init_tell``/``tell`` dispatch → the ``migrate_helper``
    ``lax.cond`` → the end-of-step ``constrain_state`` boundary. One
    body (used by StdWorkflow's step and pipelined tell and by
    SurrogateWorkflow's screened variants) so a change to any of these
    steps cannot silently drift between the copies."""
    from ..core.distributed import constrain_state

    with scope(TELL), scope(FIT_TRANSFORMS):
        for t in wf.fit_transforms:
            fitness = t(fitness)
    run_hooks(wf.monitors, wf._hook_table, "pre_tell", mstates, fitness)
    with scope(TELL):
        if use_init:
            astate = wf.algorithm.init_tell(astate, fitness)
        else:
            astate = wf.algorithm.tell(astate, fitness)
        if wf.migrate_helper is not None:
            do_migrate, foreign_pop, foreign_fit = wf.migrate_helper()
            # foreign fitness arrives in the user's convention: sign-flip
            # it to the internal minimization key, but never
            # fit_transforms — population-relative shaping over a lone
            # migrant batch is meaningless/NaN (see
            # StdWorkflow.migrate_helper docs)
            foreign_fit = wf._flip(foreign_fit)
            astate = jax.lax.cond(
                do_migrate,
                lambda a: wf.algorithm.migrate(a, foreign_pop, foreign_fit),
                lambda a: a,
                astate,
            )
    # declared sharding + storage-dtype downcast in one fused walk: the
    # loop-carried algorithm state leaves the step at storage width
    with scope(CONSTRAIN):
        return constrain_state(astate, wf.mesh, wf.dtype_policy)


def quarantine_nonfinite(fitness: jax.Array) -> jax.Array:
    """Replace non-finite fitness entries with the worst FINITE value of
    the batch (internal minimization convention: the per-objective max),
    so a poison candidate loses every comparison cleanly instead of
    corrupting argmin/sorting/ranking — NaN propagates through every
    comparison-based selection op. Multi-objective fitness is quarantined
    per objective column. A column with NO finite entry falls back to the
    dtype's max finite value. Jittable, shape-preserving."""
    finite = jnp.isfinite(fitness)
    worst = jnp.max(jnp.where(finite, fitness, -jnp.inf), axis=0)
    worst = jnp.where(
        jnp.isfinite(worst), worst, jnp.finfo(fitness.dtype).max
    )
    return jnp.where(finite, fitness, worst)


def callback_evaluate(
    problem: Problem, pstate: Any, cand: Any, num_objectives: int = 1
) -> Tuple[jax.Array, Any]:
    """Host-side evaluation through ``jax.pure_callback`` with a declared
    fitness signature (the reference's ``external_problem=True`` contract,
    std_workflow.py:146-158). External problems are stateless from the jit
    program's point of view: the state operand passes through and any host
    update lives on the problem object itself."""
    leaves = jax.tree.leaves(cand)
    pop_size = leaves[0].shape[0]
    if num_objectives > 1:
        shape: Tuple[int, ...] = (pop_size, num_objectives)
    else:
        shape = problem.fit_shape(pop_size)
    result_sds = jax.ShapeDtypeStruct(shape, jnp.dtype(problem.fit_dtype))

    def host_eval(ps, c):
        fit, _ = problem.evaluate(ps, c)
        return np.asarray(fit, dtype=problem.fit_dtype)

    fitness = jax.pure_callback(host_eval, result_sds, pstate, cand)
    return fitness, pstate
