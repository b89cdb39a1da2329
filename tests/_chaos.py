"""Fault-injection harness for the self-healing evaluation stack.

Module-level (hence picklable) chaos workers and flaky env wrappers that
make every failure mode the farm/checkpointer must survive REPRODUCIBLE:

- :func:`chaos_worker_main` — a farm worker that completes the normal
  handshake/register/setup exchange, then misbehaves deterministically on
  its first rollout request:

  * ``"kill"``  — hard-exits mid-generation (``os._exit``), the closest
    analog to an OOM-killed / preempted worker. The socket dies with it.
  * ``"hang"``  — accepts the request and never answers (a wedged env or
    a network partition); only the coordinator's ``request_timeout`` can
    reclaim the slice.
  * ``"drop"``  — closes the TCP connection cleanly without answering
    (a crashed-but-flushed peer).
  * ``"nan"``   — answers with NaN rewards of the right shape (a
    numerically-poisoned simulator; exercises fitness quarantine rather
    than farm recovery).

  Modes fire ``after`` that many well-served rollout requests (default
  0: misbehave on the very first), so tests can also exercise
  late-generation failures.

- :class:`NaNEnv` — gymnasium-API env wrapper whose reward turns NaN
  after a step threshold, for in-process (HostRolloutFarm / workflow
  quarantine) tests without any sockets.

- numeric state poisoning (PR 3): :func:`poison_algo_field` surgically
  corrupts a field of the (possibly guarded) algorithm state — NaN into
  CMA-ES's covariance, ``sigma -> 0``, and friends — to reproduce the
  failure class restart strategies recover from; :class:`PlateauSphere`
  and :class:`HostPlateauSphere` are fitness plateaus (device / host
  flavor) that starve any improvement signal, the deterministic trigger
  for stagnation guards. Consumed by tests/test_numeric_chaos.py.

- dispatch faults (PR 5): :class:`FlakyDispatch` wraps ANY callable at
  the dispatch boundary (``wf.run``, ``problem.evaluate``, a pipelined
  chunk) and injects a remote backend's failure modes — scripted
  per call index, no real fault needed: ``"hang"`` (sleeps past any
  deadline), ``"transient"`` (an ``UNAVAILABLE: connection reset``
  RuntimeError, the message jaxlib's XlaRuntimeError carries),
  ``"oom"`` (``RESOURCE_EXHAUSTED``), ``"http413"`` (payload too
  large), ``"fatal"`` (an unclassifiable ValueError). Consumed by
  tests/test_supervisor.py.

Everything here is deterministic — no random fault timing — so the
chaos tests assert exact outcomes (bit-identical fitness, pytree
equality) rather than "usually survives".
"""

from __future__ import annotations

import os
import time
from typing import Tuple

import numpy as np

from evox_tpu.problems.neuroevolution.process_farm import (
    DEFAULT_AUTHKEY,
    _handshake,
    _recv,
    _send,
)

from tests._farm_helpers import ScalarCartPole  # noqa: F401  (re-export)


def chaos_worker_main(
    address: Tuple[str, int],
    authkey: bytes = DEFAULT_AUTHKEY,
    mode: str = "kill",
    after: int = 0,
) -> None:
    """A protocol-complete farm worker that injects one fault, see module
    docstring for the modes. Serves pings and (for ``after > 0``) real
    rollouts until the fault fires."""
    import socket

    import jax

    from evox_tpu.problems.neuroevolution.rollout_farm import _Worker

    sock = socket.create_connection(address)
    try:
        _handshake(sock, authkey, server=False)
        _send(sock, {"type": "register"})
        setup = _recv(sock)
        assert setup["type"] == "setup", setup
        worker = _Worker(setup["env_creator"], setup["mo_keys"])
        policy = jax.jit(jax.vmap(setup["policy"]))
        served = 0
        while True:
            try:
                msg = _recv(sock)
            except (ConnectionError, OSError):
                return
            if msg["type"] == "shutdown":
                return
            if msg["type"] == "ping":
                _send(sock, {"type": "pong"})
                continue
            assert msg["type"] == "rollout", msg
            if served < after:  # behave until the fault threshold
                worker.rollout(policy, msg["subpop"], msg["seed"], msg["cap"])
                rewards, mo, lengths = worker.results()
                _send(
                    sock,
                    {
                        "type": "result",
                        "slice": msg.get("slice"),
                        "rewards": rewards,
                        "mo": mo,
                        "lengths": lengths,
                    },
                )
                served += 1
                continue
            # ------------------------------------------------ inject fault
            if mode == "kill":
                os._exit(1)  # mid-generation hard death, socket torn down
            elif mode == "hang":
                time.sleep(3600)  # wedged: only request_timeout reclaims us
            elif mode == "drop":
                sock.close()  # clean disconnect without a result
                return
            elif mode == "nan":
                n = np.asarray(
                    next(iter(jax.tree.leaves(msg["subpop"])))
                ).shape[0]
                _send(
                    sock,
                    {
                        "type": "result",
                        "slice": msg.get("slice"),
                        "rewards": np.full((n,), np.nan),
                        "mo": np.zeros((n, len(setup["mo_keys"]))),
                        "lengths": np.ones((n,)),
                    },
                )
                served += 1
            else:
                raise ValueError(f"unknown chaos mode: {mode!r}")
    finally:
        try:
            sock.close()
        except OSError:
            pass


def spawn_chaos_worker(
    address: Tuple[str, int],
    mode: str,
    after: int = 0,
    authkey: bytes = DEFAULT_AUTHKEY,
):
    """Start ONE chaos worker process (spawn context, daemonized)."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    p = ctx.Process(
        target=chaos_worker_main,
        args=(address, authkey, mode, after),
        daemon=True,
    )
    p.start()
    return p


# --------------------------------------------------------------------------
# dispatch-boundary fault injection (PR 5)


def make_fault(kind: str) -> Exception:
    """An exception whose type/message classifies exactly like the real
    backend failure it mimics (see workflows/supervisor.py patterns)."""
    if kind == "transient":
        return RuntimeError(
            "UNAVAILABLE: connection reset by peer (transport dropped)"
        )
    if kind == "oom":
        return RuntimeError(
            "RESOURCE_EXHAUSTED: out of memory allocating 268435456 bytes"
        )
    if kind == "http413":
        return RuntimeError("remote_compile failed: HTTP 413 payload too large")
    if kind == "fatal":
        return ValueError("algorithm state is structurally broken")
    raise ValueError(f"unknown fault kind: {kind!r}")


class FlakyDispatch:
    """Callable shim injecting dispatch-layer faults at the call boundary.

    ``faults`` maps 0-based call indices to a fault kind (``"hang"`` /
    ``"transient"`` / ``"oom"`` / ``"http413"`` / ``"fatal"``) or an
    exception instance; unlisted calls delegate to ``fn``. ``trigger``
    (optional) is consulted per call with ``(index, args, kwargs)`` and
    may return a kind/exception too — e.g. "OOM whenever the evaluated
    batch is wider than K" for degradation tests. Deterministic by
    construction, so supervisor tests assert exact outcomes.

    ``hang_s``: how long a "hang" blocks (a plain sleep on the abandoned
    watchdog thread — keep it bounded so leaked daemon threads exit
    before the suite does). ``calls`` counts every invocation,
    ``served`` only the delegated ones.
    """

    def __init__(self, fn, faults=None, trigger=None, hang_s: float = 20.0):
        self.fn = fn
        self.faults = dict(faults or {})
        self.trigger = trigger
        self.hang_s = hang_s
        self.calls = 0
        self.served = 0

    def _fault_for(self, index, args, kwargs):
        fault = self.faults.get(index)
        if fault is None and self.trigger is not None:
            fault = self.trigger(index, args, kwargs)
        return fault

    def __call__(self, *args, **kwargs):
        index = self.calls
        self.calls += 1
        fault = self._fault_for(index, args, kwargs)
        if fault is not None:
            if isinstance(fault, BaseException):
                raise fault
            if fault == "hang":
                time.sleep(self.hang_s)
                raise TimeoutError(
                    "FlakyDispatch hang elapsed without a deadline firing"
                )
            raise make_fault(fault)
        self.served += 1
        return self.fn(*args, **kwargs)


# --------------------------------------------------------------------------
# surrogate fault injection (ISSUE 15)


class LyingSurrogate:
    """Wrap any surrogate model and systematically LIE at predict time:
    the predicted mean is negated (the model's ordering becomes exactly
    wrong) and the reported uncertainty is scaled toward overconfidence.
    ``fit`` and state management delegate unchanged, so the lie is pure
    prediction-layer poison — the deterministic trigger for
    SurrogateWorkflow's rank-correlation fallback predicate
    (tests/test_surrogate.py asserts the fallback fires AND the guarded
    run still converges, because fallback == full evaluation)."""

    def __init__(self, inner, lie_after: int = 0):
        self.inner = inner
        self.kind = inner.kind
        self.lie_after = lie_after
        self.predict_calls = 0

    def check_capacity(self, capacity: int) -> None:
        check = getattr(self.inner, "check_capacity", None)
        if check is not None:
            check(capacity)

    def init_model(self, capacity: int, dim: int):
        return self.inner.init_model(capacity, dim)

    def fit(self, model, x, y, mask, key=None):
        return self.inner.fit(model, x, y, mask, key)

    def predict(self, model, x_test):
        # NOTE: traced once per compiled program — the lie must be
        # unconditional in traced code, so `lie_after` only gates
        # whether the POISONED trace is built at all (0 = always lie)
        self.predict_calls += 1
        mean, unc = self.inner.predict(model, x_test)
        if self.predict_calls > self.lie_after:
            return -mean, unc * 1e-3
        return mean, unc


# --------------------------------------------------------------------------
# numeric (algorithm-state) fault injection


def poison_algo_field(wf_state, field_name: str, value):
    """Return a copy of a workflow state with ``field_name`` of the
    algorithm state overwritten by ``value`` (broadcast to the field's
    shape, cast to its dtype). Sees through a GuardedAlgorithm wrapper:
    when the algorithm state is a ``GuardedState``, the INNER state is
    poisoned — the realistic fault is inside the wrapped algorithm's
    math, not the wrapper's bookkeeping."""
    import jax.numpy as jnp

    from evox_tpu.core.guardrail import GuardedState

    astate = wf_state.algo
    if isinstance(astate, GuardedState):
        inner = astate.inner
        cur = getattr(inner, field_name)
        poisoned = jnp.full_like(cur, value)
        return wf_state.replace(
            algo=astate.replace(inner=inner.replace(**{field_name: poisoned}))
        )
    cur = getattr(astate, field_name)
    poisoned = jnp.full_like(cur, value)
    return wf_state.replace(algo=astate.replace(**{field_name: poisoned}))


class PlateauSphere:
    """Sphere whose fitness is floored to a constant beyond a radius —
    inside jit. Every candidate outside ``radius`` scores exactly
    ``plateau``, so a search that starts far away receives ZERO
    improvement signal: the deterministic trigger for stagnation-based
    restarts (a run re-centered near the optimum escapes the plateau and
    converges, which is what the recovery tests assert). Duck-typed
    Problem (jittable/fit_shape/fit_dtype), no base class needed."""

    jittable = True
    fit_dtype = "float32"

    def __init__(self, radius: float = 4.0, plateau: float = 1e3):
        self.radius = radius
        self.plateau = plateau

    def init(self, key=None):
        return None

    def fit_shape(self, pop_size):
        return (pop_size,)

    def evaluate(self, state, pop):
        import jax.numpy as jnp

        sq = jnp.sum(pop**2, axis=-1)
        return jnp.where(sq > self.radius**2, self.plateau, sq), state


class HostPlateauSphere(PlateauSphere):
    """Host (non-jittable) flavor of :class:`PlateauSphere`, for driving
    the same stagnation/restart scenarios through ``run_host_pipelined``."""

    jittable = False

    def evaluate(self, state, pop):
        sq = np.sum(np.asarray(pop) ** 2, axis=-1)
        out = np.where(sq > self.radius**2, self.plateau, sq)
        return out.astype(np.float32), state


class NaNEnv:
    """ScalarCartPole whose reward goes NaN after ``poison_after`` steps —
    an in-process numerically-poisoned simulator for quarantine tests."""

    def __init__(self, poison_after: int = 0, max_steps: int = 200):
        self._base = ScalarCartPole(max_steps=max_steps)
        self.poison_after = poison_after
        self._steps = 0

    def reset(self, seed=0):
        self._steps = 0
        return self._base.reset(seed)

    def step(self, action):
        obs, r, term, trunc, info = self._base.step(action)
        self._steps += 1
        if self._steps > self.poison_after:
            r = float("nan")
        return obs, r, term, trunc, info


# --------------------------------------------------------------------------
# silent-data-corruption injection (ISSUE 20, core/attest.py)


def flip_bit(state, leaf: str, index: int = 0, bit: int = 0, at_gen=None,
             kind: str = "mantissa"):
    """Return ``state`` with exactly ONE bit flipped in the named leaf —
    the canonical silent-data-corruption analog (a cosmic-ray upset in
    HBM). On-device and trace-safe: the flip is a bitcast-XOR
    where-select, so it composes into a jitted/fused step and can be
    gated on a TRACED generation (``at_gen``; ``None`` flips
    unconditionally).

    ``leaf`` is a dotted attribute path into the state
    (``"algo.C"``, ``"tenants.algo.mean"``); ``index`` is the FLAT
    element index; ``kind`` picks the bit region for float leaves:
    ``"mantissa"`` flips mantissa bit ``bit`` (a tiny, sub-tolerance
    perturbation — exactly what allclose-based checks miss and bitwise
    attestation catches), ``"exponent"`` flips exponent bit ``bit`` (a
    catastrophic magnitude error). Integer leaves flip bit ``bit``
    directly."""
    import jax
    import jax.numpy as jnp

    parts = leaf.split(".")
    target = state
    for p in parts:
        target = getattr(target, p)
    x = jnp.asarray(target)
    if x.dtype == jnp.float32:
        word = jnp.uint32(1) << jnp.uint32(
            bit if kind == "mantissa" else 23 + bit
        )
        flat = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
        flat = flat.at[index].set(flat[index] ^ word)
        flipped = jax.lax.bitcast_convert_type(
            flat.reshape(x.shape), jnp.float32
        )
    elif x.dtype in (jnp.int32, jnp.uint32):
        word = jnp.asarray(1, x.dtype) << jnp.asarray(bit, x.dtype)
        flat = x.reshape(-1)
        flat = flat.at[index].set(flat[index] ^ word)
        flipped = flat.reshape(x.shape)
    else:
        raise NotImplementedError(f"flip_bit: unsupported dtype {x.dtype}")
    if at_gen is not None:
        due = jnp.asarray(state.generation, jnp.int32) == jnp.asarray(
            at_gen, jnp.int32
        )
        flipped = jnp.where(due, flipped, x)
    rebuilt = flipped
    for i in range(len(parts) - 1, -1, -1):
        holder = state
        for p in parts[:i]:
            holder = getattr(holder, p)
        rebuilt = holder.replace(**{parts[i]: rebuilt})
    return rebuilt


class BitFlipStep:
    """Workflow shim whose ``run`` flips one bit at generation ``at_gen``
    then continues honestly — the reproducible ``suspect`` leg for
    :func:`evox_tpu.core.attest.bisect_divergence` (the fault is a pure
    function of the traced generation, so it reproduces identically at
    ANY chunking). Also usable as a full faulty drive in executor tests."""

    def __init__(self, wf, leaf: str, at_gen: int, index: int = 0,
                 bit: int = 0, kind: str = "mantissa"):
        self.wf = wf
        self.leaf = leaf
        self.at_gen = at_gen
        self.index = index
        self.bit = bit
        self.kind = kind

    def __getattr__(self, name):
        return getattr(self.wf, name)

    def run(self, state, n_steps: int):
        # step one generation at a time so the flip gate sees every
        # intermediate generation; bit-identical to wf.run when the
        # flip generation is outside [gen, gen+n) (fori chunking law)
        for _ in range(int(n_steps)):
            state = self.wf.run(state, 1)
            state = flip_bit(
                state, self.leaf, index=self.index, bit=self.bit,
                at_gen=self.at_gen, kind=self.kind,
            )
        return state


class LyingPod:
    """Dispatch shim that returns WRONG-BUT-PLAUSIBLE chunk results on
    scripted call indices — the silent-data-corruption analog of
    :class:`FlakyDispatch` (which models loud faults). ``lies`` maps
    0-based call indices to a flavor: ``"perturb"`` returns the honest
    result with one mantissa bit flipped in ``leaf`` (sub-tolerance SDC),
    ``"stale"`` returns the PREVIOUS honest result (a pod that silently
    dropped its chunk). Unlisted calls pass through. Deterministic, so
    voting tests assert exact heal/abort outcomes; ``sticky=True`` makes
    every listed flavor apply to ALL calls from its index on (the
    reproducible-fault shape bisection needs)."""

    def __init__(self, fn, lies=None, leaf: str = "algo.mean",
                 bit: int = 0, sticky: bool = False):
        self.fn = fn
        self.lies = dict(lies or {})
        self.leaf = leaf
        self.bit = bit
        self.sticky = sticky
        self.calls = 0
        self.honest = 0
        self._last = None

    def _flavor(self, index):
        if self.sticky:
            live = [i for i in self.lies if i <= index]
            return self.lies[max(live)] if live else None
        return self.lies.get(index)

    def __call__(self, *args, **kwargs):
        index = self.calls
        self.calls += 1
        flavor = self._flavor(index)
        result = self.fn(*args, **kwargs)
        if flavor is None:
            self.honest += 1
            self._last = result
            return result
        if flavor == "stale":
            return self._last if self._last is not None else result
        if flavor == "perturb":
            return flip_bit(result, self.leaf, index=0, bit=self.bit)
        raise ValueError(f"unknown lie flavor: {flavor!r}")
