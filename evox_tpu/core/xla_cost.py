"""AOT XLA cost/memory analysis and roofline attribution — all host-side.

PR 1's :mod:`~evox_tpu.core.instrument` answers *how long* each dispatch
took; this module answers *why*: is an entry point compute-bound
(MXU-limited), memory-bound (HBM-limited), or dispatch-bound (the
per-dispatch overhead dwarfs the useful work)? The machinery is
deliberately callback-free and trace-free:

- **AOT analysis**: ``jit(fn).lower(*args).compile()`` once per entry
  point and harvest ``compiled.cost_analysis()`` (FLOPs, bytes accessed)
  and ``compiled.memory_analysis()`` (argument/output/temp bytes). Both
  are host-side XLA queries — nothing runs on the device, no
  ``io_callback``/``pure_callback`` anywhere, so the analysis works
  identically on the 8-device CPU mesh and on the TPU.
- **Roofline merge**: static FLOPs/bytes divided by the *differenced*
  measured seconds (``DispatchRecorder``'s slope over distinct trip
  counts, in which the per-dispatch latency cancels) give achieved TF/s
  and GB/s, compared against the chip's published peaks below.
- **Dynamic trip counts**: XLA's HLO cost analysis counts a
  dynamic-trip-count ``fori_loop`` body ONCE (verified empirically: a
  10-iteration loop of a 528 kFLOP body reports 528 kFLOPs), so the
  static cost of a ``make_run_loop`` program is the PER-GENERATION cost
  — exactly the unit the differenced slope measures. The two merge
  without any trip-count bookkeeping.

Dependency direction: this module imports only jax/numpy; it must never
import :mod:`~evox_tpu.core.instrument` (which imports *it*), monitors,
or workflows. Workflows opt in by exposing ``analysis_targets(state)``
(duck-typed — see :meth:`CostAnalyzer.analyze_workflow`).
"""

from __future__ import annotations

import hashlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

__all__ = [
    "CHIP_CEILINGS",
    "CostAnalyzer",
    "chip_ceilings",
    "abstract_signature",
    "analyze_callable",
    "roofline_section",
]

# Published peaks of the chips this repo is measured on, keyed by the
# ``device_kind`` jax reports. A device that is not in the table has no
# fraction of peak: asking for one raises (:func:`chip_ceilings`) — the
# v5e's numbers are never applied to another device, the CPU included.
CHIP_CEILINGS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "mxu_bf16_tflops": 197.0,
        "hbm_gbps": 819.0,
        "source": (
            'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and '
            "819 GB/s of HBM bandwidth per chip"
        ),
    },
}


def chip_ceilings(device_kind: Optional[str] = None) -> Dict[str, Any]:
    """The published peaks of ``device_kind`` (default: the kind of
    ``jax.devices()[0]``). Raises ``LookupError`` for a device outside
    :data:`CHIP_CEILINGS` — there is no default peak; callers on another
    device (the CPU tests) pass their own ``ceilings=``."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    try:
        return dict(CHIP_CEILINGS[device_kind])
    except KeyError:
        raise LookupError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(CHIP_CEILINGS)}): a fraction of peak is undefined "
            "here — pass ceilings= explicitly or add the device to "
            "core/xla_cost.py CHIP_CEILINGS with its source"
        ) from None


# measured >= factor * ideal  =>  the entry spends most of its time on
# neither FLOPs nor HBM traffic: per-dispatch overhead (host Python,
# XLA launch) dominates -> "dispatch-bound"
DISPATCH_BOUND_FACTOR = 4.0

CLASSIFICATIONS = ("compute-bound", "memory-bound", "dispatch-bound")


# --------------------------------------------------------------- signatures


def _leaf_sig(leaf: Any) -> str:
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return f"{np.dtype(leaf.dtype).name}[{','.join(map(str, leaf.shape))}]"
    # python scalars trace to weak-typed scalar avals: any int is the same
    # aval as any other int, so the VALUE must not enter the signature
    # (wf.run(state, 100) vs run(state, 200) is NOT a retrace)
    return type(leaf).__name__


def abstract_signature(args: tuple, kwargs: Optional[dict] = None) -> Tuple[str, str]:
    """``(aval_sig, static_sig)`` of a call's arguments.

    ``aval_sig`` keys the abstract values jit actually specializes on —
    leaf shapes/dtypes (python scalars collapse to their type: they trace
    to weak-typed scalar avals). A *new* ``aval_sig`` for an
    already-compiled entry is the classic silent retrace (a shape or
    dtype changed). ``static_sig`` hashes the pytree structure including
    static fields; it changes on benign, designed recompiles too — e.g.
    ``StdWorkflowState.first_step`` flipping after the init-generation
    peel — so the two are reported separately and only aval changes are
    flagged.
    """
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    aval_sig = ";".join(_leaf_sig(leaf) for leaf in leaves)
    static_sig = hashlib.sha1(
        (str(treedef) + "|" + aval_sig).encode()
    ).hexdigest()[:16]
    return aval_sig, static_sig


# ------------------------------------------------------------- AOT analysis


def _cost_dict(compiled: Any) -> Optional[dict]:
    """``compiled.cost_analysis()``, or None on a backend without HLO
    cost analysis."""
    try:
        return compiled.cost_analysis()
    except Exception:
        return None


_MEMORY_ATTRS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)


def _memory_dict(compiled: Any) -> Optional[dict]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out: dict = {}
    for attr, key in _MEMORY_ATTRS:
        v = getattr(ma, attr, None)
        if v is not None:
            out[key] = int(v)
    if not out:
        return None
    # arguments alias in place, temps live alongside outputs: the sum is
    # the standard upper estimate of live bytes during execution
    out["peak_bytes_estimate"] = (
        out.get("argument_bytes", 0)
        + out.get("output_bytes", 0)
        + out.get("temp_bytes", 0)
    )
    return out


def analyze_callable(fn: Callable, *args: Any, **kwargs: Any) -> dict:
    """AOT-lower and compile ``fn(*args, **kwargs)`` once, harvesting XLA's
    static cost and memory analysis. ``fn`` may be a ``jax.jit`` wrapper
    (lowered directly — the same program the workflow dispatches) or any
    traceable callable (jitted ad hoc). ``args`` may be concrete arrays
    or ``jax.ShapeDtypeStruct`` pytrees — lowering never executes the
    program, so this is safe and side-effect-free on every backend.

    Returns ``{"flops", "bytes_accessed", "memory": {...}, "signature"}``
    with ``None`` for quantities the backend does not report, or
    ``{"error": ...}`` when lowering/compilation fails (analysis must
    never sink the run it describes).
    """
    try:
        lowerable = fn if hasattr(fn, "lower") else jax.jit(fn)
        compiled = lowerable.lower(*args, **kwargs).compile()
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}
    cost = _cost_dict(compiled)

    def _metric(key: str) -> Optional[float]:
        v = cost.get(key) if cost else None
        # XLA reports -1/absent for metrics a backend doesn't model
        return float(v) if v is not None and v >= 0 else None

    return {
        "flops": _metric("flops"),
        "bytes_accessed": _metric("bytes accessed"),
        "memory": _memory_dict(compiled),
        "signature": abstract_signature(args, kwargs)[0],
    }


class CostAnalyzer:
    """Per-entry-point AOT analysis cache.

    One lower+compile per ``(entry, aval_signature)`` — re-analysis with
    the same abstract arguments is free, so :func:`~evox_tpu.core.
    instrument.run_report` can call :meth:`analyze_workflow` on every
    report without recompiling anything.
    """

    def __init__(self, ceilings: Optional[dict] = None):
        # no ceilings given: the attached device's published peaks, or
        # LookupError right here where the device has none
        self.ceilings = dict(ceilings) if ceilings is not None else chip_ceilings()
        self.analyses: Dict[str, dict] = {}
        self._cache: Dict[Tuple[str, str], dict] = {}

    def analyze(self, name: str, fn: Callable, *args: Any, **kwargs: Any) -> dict:
        key = (name, abstract_signature(args, kwargs)[0])
        if key not in self._cache:
            self._cache[key] = analyze_callable(fn, *args, **kwargs)
        self.analyses[name] = self._cache[key]
        return self.analyses[name]

    def analyze_workflow(self, workflow: Any, state: Any) -> Dict[str, dict]:
        """Analyze every entry point the workflow advertises through
        ``analysis_targets(state)`` (duck-typed: workflows without the
        method contribute nothing). Targets map entry names to
        ``(jitted_callable, example_args)`` — the exact programs the
        workflow dispatches, so the analysis covers what actually runs."""
        targets = getattr(workflow, "analysis_targets", None)
        if targets is None:
            return {}
        for name, (fn, args) in targets(state).items():
            self.analyze(name, fn, *args)
        return self.analyses


# ----------------------------------------------------------------- roofline


def roofline_section(
    analyses: Dict[str, dict],
    dispatch_summary: Optional[dict] = None,
    ceilings: Optional[dict] = None,
    dispatch_bound_factor: float = DISPATCH_BOUND_FACTOR,
) -> dict:
    """Merge static AOT analyses with measured per-unit dispatch timings
    into the ``roofline`` section of ``run_report()``.

    Per entry: static FLOPs/bytes/memory, the measured seconds per work
    unit (differenced slope when the recorder saw two trip counts, else
    the steady-state median — flagged ``latency_confounded`` because a
    single-trip-count timing still contains the full per-dispatch
    round-trip), achieved TF/s and GB/s, fractions of the published chip
    ceilings, and a bound-ness classification:

    - ``dispatch-bound``: measured time exceeds ``dispatch_bound_factor``
      x the roofline-ideal time — per-dispatch overhead dominates.
    - ``compute-bound`` / ``memory-bound``: whichever of the FLOP and HBM
      ideal times is larger when the measurement is near the roofline.

    Entries with an analysis error or no recorded timing keep their
    static half and classify ``None`` — the report never invents rates.
    """
    ceilings = dict(ceilings) if ceilings is not None else chip_ceilings()
    peak_flops = float(ceilings["mxu_bf16_tflops"]) * 1e12
    peak_bytes = float(ceilings["hbm_gbps"]) * 1e9
    entry_stats = (dispatch_summary or {}).get("entry_points", {})
    entries: Dict[str, dict] = {}
    for name, analysis in sorted(analyses.items()):
        entry: dict = {"static": analysis, "classification": None}
        if "error" in analysis:
            entries[name] = entry
            continue
        per_work = (entry_stats.get(name) or {}).get("per_work_s") or {}
        t = per_work.get("seconds")
        flops = analysis.get("flops")
        nbytes = analysis.get("bytes_accessed")
        if not t or t <= 0:
            entries[name] = entry
            continue
        if flops is None and nbytes is None:
            # the backend reported no static metrics at all: a verdict
            # here would be invented — keep the measurement, classify None
            entry.update(
                measured_s_per_unit=t,
                timing_method=per_work.get("method"),
                latency_confounded=bool(per_work.get("latency_confounded")),
            )
            entries[name] = entry
            continue
        ideal_compute_s = (flops or 0.0) / peak_flops
        ideal_memory_s = (nbytes or 0.0) / peak_bytes
        ideal_s = max(ideal_compute_s, ideal_memory_s)
        if ideal_s <= 0 or t > dispatch_bound_factor * ideal_s:
            classification = "dispatch-bound"
        elif ideal_compute_s >= ideal_memory_s:
            classification = "compute-bound"
        else:
            classification = "memory-bound"
        entry.update(
            measured_s_per_unit=t,
            timing_method=per_work.get("method"),
            latency_confounded=bool(per_work.get("latency_confounded")),
            achieved_tflops=(
                round(flops / t / 1e12, 6) if flops is not None else None
            ),
            achieved_gbps=(
                round(nbytes / t / 1e9, 6) if nbytes is not None else None
            ),
            frac_peak_compute=(
                round(flops / t / peak_flops, 6) if flops is not None else None
            ),
            frac_peak_bandwidth=(
                round(nbytes / t / peak_bytes, 6)
                if nbytes is not None
                else None
            ),
            ideal_s=round(ideal_s, 9),
            dispatch_overhead_frac=round(max(0.0, 1.0 - ideal_s / t), 6),
            classification=classification,
        )
        entries[name] = entry
    return {
        "ceilings": ceilings,
        "dispatch_bound_factor": dispatch_bound_factor,
        "entries": entries,
    }
