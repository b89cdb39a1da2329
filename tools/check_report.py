"""Schema validator for evox_tpu run reports, metrics streams and traces.

``run_report()`` (core/instrument.py), the serving metrics stream
(workflows/flightrec.py) and ``write_chrome_trace()`` are the
structured-JSON surfaces the program writes. This validator pins their
shape so a refactor that silently drops a key or leaks a bare
``NaN``/``Infinity`` token (rejected by strict JSON parsers) fails a fast
tier-1 test (tests/test_check_report.py) instead of a downstream
pipeline.

Usage::

    python tools/check_report.py runs.jsonl trace.json ...

``.jsonl`` files are validated line by line as run reports, or as one
metrics stream when the first record carries that schema tag; ``.json``
files are sniffed: a ``traceEvents`` key means a Chrome trace, anything
else a run report. Exit status 0 = every file valid, 1 = violations
(printed one per line).

One version of the run report is valid: the one the program emits
(``RUN_REPORT_SCHEMA_VERSION``; ``core/instrument.py`` stamps the same
integer). A report stamped with another version is a violation.

The finiteness rule is exactly ``core.instrument.sanitize_json``'s: a
value the sanitizer would rewrite (non-finite float) is a violation —
report producers must sanitize before writing.
"""

from __future__ import annotations

import json
import math
import sys
from typing import Any, Iterator, List, Tuple

RUN_REPORT_SCHEMA_PREFIX = "evox_tpu.run_report/"
#: the one version ``run_report`` emits (core/instrument.py) and this
#: validator takes
RUN_REPORT_SCHEMA_VERSION = 14
RUN_REPORT_SCHEMA = f"{RUN_REPORT_SCHEMA_PREFIX}v{RUN_REPORT_SCHEMA_VERSION}"
# v11 (PR 16, workflows/flightrec.py): the serving metrics stream is a
# third .jsonl surface — sniffed by its per-record schema tag
METRICS_STREAM_SCHEMA_PREFIX = "evox_tpu.metrics_stream/"
STREAM_KINDS = {"meta", "sample", "event", "barrier"}
SLO_KEYS = (
    "tenant_gens",
    "elapsed_s",
    "tenant_gens_per_s",
    "admissions",
    "preemptions",
    "deadline_hits",
    "deadline_misses",
)
CLASSIFICATIONS = {"compute-bound", "memory-bound", "dispatch-bound", None}
SUPERVISOR_OUTCOMES = {"clean", "recovered", "aborted"}
# v14 (ISSUE 20, core/attest.py): integrity_mismatch/integrity_heal are
# the voted re-dispatch rung's supervisor events
SUPERVISOR_EVENTS = {
    "retry",
    "deadline",
    "restore",
    "degrade",
    "abort",
    "integrity_mismatch",
    "integrity_heal",
}
SUPERVISOR_COUNTERS = (
    "dispatches",
    "retries",
    "deadline_hits",
    "restores",
    "degradations",
    "aborts",
)
# v9 (ISSUE 14, core/pod_supervisor.py): the pod fault domain's section
POD_OUTCOMES = {"clean", "drained", "failed", "resumed"}
POD_EVENTS = {
    "join",
    "census",
    "barrier_timeout",
    "failure",
    "drain_requested",
    "drain",
    "reform",
    "resume",
}
POD_FAILURE_CLASSES = {
    "worker_dead",
    "hung_collective",
    "coordinator_loss",
    # v14 (ISSUE 20): a pod outvoted in a 2-of-3 integrity vote
    "integrity_dissent",
}
# v14 (ISSUE 20, core/attest.py): the integrity section's verdict set
INTEGRITY_VERDICTS = {"clean", "detected", "healed", "aborted"}
POD_COUNTERS = (
    "heartbeats",
    "censuses",
    "barriers",
    "barrier_timeouts",
    "supervised_calls",
    "failures",
    "drains",
    "reforms",
    "resumes",
)


def _walk(obj: Any, path: str = "$") -> Iterator[Tuple[str, Any]]:
    yield path, obj
    if isinstance(obj, dict):
        for k, v in obj.items():
            yield from _walk(v, f"{path}.{k}")
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            yield from _walk(v, f"{path}[{i}]")


def find_nonfinite(obj: Any) -> List[str]:
    """Paths of every value ``sanitize_json`` would rewrite — i.e. every
    float that breaks RFC 8259 strict JSON."""
    return [
        path
        for path, v in _walk(obj)
        if isinstance(v, float) and not math.isfinite(v)
    ]


def _num(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def validate_run_report(report: Any, where: str = "run_report") -> List[str]:
    errors: List[str] = []
    if not isinstance(report, dict):
        return [f"{where}: not a JSON object"]
    schema = report.get("schema")
    if schema != RUN_REPORT_SCHEMA:
        errors.append(
            f"{where}: missing/unknown schema key (want "
            f"{RUN_REPORT_SCHEMA!r}, the version the program emits; got "
            f"{schema!r})"
        )
    # the version also rides as a grep-able top-level int, and the two
    # must agree — a report that says one version in the tag and another
    # in the int is lying to somebody
    sv = report.get("schema_version")
    if not isinstance(sv, int) or isinstance(sv, bool):
        errors.append(f"{where}: schema_version missing or not an int")
    elif sv != RUN_REPORT_SCHEMA_VERSION:
        errors.append(
            f"{where}: schema_version {sv} disagrees with schema "
            f"{RUN_REPORT_SCHEMA!r}"
        )
    errors += [f"{where}: non-finite number at {p}" for p in find_nonfinite(report)]
    for i, mon in enumerate(report.get("telemetry", []) or []):
        if not isinstance(mon, dict) or "monitor" not in mon:
            errors.append(f"{where}: telemetry[{i}] lacks a 'monitor' key")
    dispatch = report.get("dispatch")
    if dispatch is not None:
        if not isinstance(dispatch, dict):
            errors.append(f"{where}: dispatch is not an object")
        else:
            for name, stats in (dispatch.get("entry_points") or {}).items():
                for key in ("calls", "first_call_s", "total_s"):
                    if not _num(stats.get(key)):
                        errors.append(
                            f"{where}: dispatch.entry_points.{name}.{key} "
                            "missing or non-numeric"
                        )
                if isinstance(stats.get("calls"), int) and stats["calls"] < 1:
                    errors.append(
                        f"{where}: dispatch.entry_points.{name}.calls < 1"
                    )
            if not isinstance(dispatch.get("wall_s"), (int, float)):
                errors.append(f"{where}: dispatch.wall_s missing")
    sup = report.get("supervisor")
    if sup is not None:
        if not isinstance(sup, dict):
            errors.append(f"{where}: supervisor is not an object")
        else:
            if sup.get("outcome") not in SUPERVISOR_OUTCOMES:
                errors.append(
                    f"{where}: supervisor.outcome {sup.get('outcome')!r} "
                    f"not in {sorted(SUPERVISOR_OUTCOMES)}"
                )
            counters = sup.get("counters")
            if not isinstance(counters, dict):
                errors.append(f"{where}: supervisor.counters missing")
            else:
                for key in SUPERVISOR_COUNTERS:
                    v = counters.get(key)
                    if not isinstance(v, int) or v < 0:
                        errors.append(
                            f"{where}: supervisor.counters.{key} missing or "
                            "not a non-negative int"
                        )
            events = sup.get("events")
            if not isinstance(events, list):
                errors.append(f"{where}: supervisor.events missing")
            else:
                last_t = float("-inf")
                for i, ev in enumerate(events):
                    loc = f"{where}: supervisor.events[{i}]"
                    if not isinstance(ev, dict):
                        errors.append(f"{loc} is not an object")
                        continue
                    if ev.get("event") not in SUPERVISOR_EVENTS:
                        errors.append(
                            f"{loc}.event {ev.get('event')!r} not in "
                            f"{sorted(SUPERVISOR_EVENTS)}"
                        )
                    t = ev.get("t")
                    if not _num(t) or t < 0:
                        errors.append(f"{loc}.t missing/negative")
                    elif t < last_t:
                        errors.append(f"{loc}.t not monotonic")
                    else:
                        last_t = t
                # a ladder that ended in abort must say so coherently
                if (
                    any(
                        isinstance(ev, dict) and ev.get("event") == "abort"
                        for ev in events
                    )
                    and sup.get("outcome") != "aborted"
                ):
                    errors.append(
                        f"{where}: supervisor has an abort event but "
                        f"outcome {sup.get('outcome')!r}"
                    )
    pod = report.get("pod_supervisor")
    if pod is not None:
        errors += _validate_pod_supervisor(pod, where)
    surrogate = report.get("surrogate")
    if surrogate is not None:
        errors += _validate_surrogate(surrogate, where)
    search = report.get("search")
    if search is not None:
        errors += _validate_search(search, where)
    integrity = report.get("integrity")
    if integrity is not None:
        errors += _validate_integrity(integrity, where)
    control_plane = report.get("control_plane")
    if control_plane is not None:
        errors += _validate_control_plane(control_plane, where)
    tenancy = report.get("tenancy")
    if tenancy is not None:
        errors += _validate_tenancy(tenancy, where)
    serving = report.get("serving")
    if serving is not None:
        errors += _validate_serving(serving, where)
    executor = report.get("executor")
    if executor is not None:
        errors += _validate_executor(executor, where)
    metrics = report.get("metrics")
    if metrics is not None:
        errors += _validate_metrics_section(metrics, where)
    slo = report.get("slo")
    if slo is not None:
        errors += _validate_slo_ledger(slo, where)
        if isinstance(metrics, dict):
            # the ledger IS the slo.* counter namespace rendered — the
            # two views come from one registry, so they must agree
            # exactly
            counters = metrics.get("counters") or {}
            for short, name in (
                ("tenant_gens", "slo.tenant_gens"),
                ("admissions", "slo.admissions"),
                ("preemptions", "slo.preemptions"),
                ("deadline_hits", "slo.deadline_hits"),
                ("deadline_misses", "slo.deadline_misses"),
            ):
                if _num(slo.get(short)) and slo[short] != counters.get(
                    name, 0
                ):
                    errors.append(
                        f"{where}: slo.{short} {slo[short]} disagrees with "
                        f"metrics.counters.{name} {counters.get(name, 0)}"
                    )
        queue = (tenancy or {}).get("queue") if isinstance(tenancy, dict) else None
        qcounters = queue.get("counters") if isinstance(queue, dict) else None
        if isinstance(qcounters, dict):
            # the recorder counts admissions/preemptions at the queue's
            # own call sites, but MAY be shared across bucket queues
            # (ElasticServer), so the ledger dominates any single
            # queue's counters — a ledger BELOW them is incoherent
            for short, qkey in (
                ("admissions", "admitted"),
                ("preemptions", "preempted"),
            ):
                if (
                    _num(slo.get(short))
                    and _num(qcounters.get(qkey))
                    and slo[short] < qcounters[qkey]
                ):
                    errors.append(
                        f"{where}: slo.{short} {slo[short]} < "
                        f"tenancy.queue.counters.{qkey} "
                        f"{qcounters[qkey]} — the ledger lost admissions "
                        "the queue itself recorded"
                    )
    roofline = report.get("roofline")
    if roofline is not None:
        if not isinstance(roofline, dict):
            errors.append(f"{where}: roofline is not an object")
        elif set(roofline) == {"error"}:
            # degraded form: analysis failed, run_report kept the rest of
            # the report and recorded why — valid by design
            if not isinstance(roofline["error"], str):
                errors.append(f"{where}: roofline.error is not a string")
        else:
            ceilings = roofline.get("ceilings") or {}
            for key in ("mxu_bf16_tflops", "hbm_gbps"):
                if not _num(ceilings.get(key)):
                    errors.append(
                        f"{where}: roofline.ceilings.{key} missing — rates "
                        "without their ceiling are uninterpretable"
                    )
            entries = roofline.get("entries")
            if not isinstance(entries, dict) or not entries:
                errors.append(f"{where}: roofline.entries missing or empty")
            else:
                for name, entry in entries.items():
                    loc = f"{where}: roofline.entries.{name}"
                    static = entry.get("static")
                    if not isinstance(static, dict):
                        errors.append(f"{loc}.static missing")
                    elif "error" not in static:
                        for key in ("flops", "bytes_accessed"):
                            if static.get(key) is not None and not _num(
                                static[key]
                            ):
                                errors.append(f"{loc}.static.{key} non-numeric")
                    if entry.get("classification") not in CLASSIFICATIONS:
                        errors.append(
                            f"{loc}.classification "
                            f"{entry.get('classification')!r} not in "
                            f"{sorted(c for c in CLASSIFICATIONS if c)}"
                        )
                # provenance: rates are only interpretable next to the
                # dtype the state was stored at and whether the run carry
                # was donated — a roofline section without them is stale
                dp = roofline.get("dtype_policy")
                if not isinstance(dp, dict):
                    errors.append(f"{where}: roofline.dtype_policy missing")
                else:
                    for key in ("storage", "compute"):
                        if not isinstance(dp.get(key), str):
                            errors.append(
                                f"{where}: roofline.dtype_policy.{key} "
                                "missing or not a dtype name"
                            )
                    if not isinstance(dp.get("active"), bool):
                        errors.append(
                            f"{where}: roofline.dtype_policy.active missing"
                        )
                # PR-10 (schema v5+): POP-sharded large-pop runs carry a
                # `sharding` subsection whose whole point is the
                # gather-free inequality — per-device peak bytes must be
                # strictly below the full-pop artifact bytes (a compiled
                # step that gathers the population to one device fails
                # here, not in a dashboard). Optional: replicated runs
                # don't carry it.
                shd = roofline.get("sharding")
                if shd is not None:
                    errors += _validate_sharding(shd, where)
                # ISSUE-13 (schema v8+): multi-process runs carry a
                # `multihost` subsection citing the per-process AOT peak
                # and the collective-traffic estimate. Optional:
                # single-process runs don't carry it.
                mh = roofline.get("multihost")
                if mh is not None:
                    errors += _validate_multihost(mh, where)
                don = roofline.get("donation")
                if not isinstance(don, dict):
                    errors.append(f"{where}: roofline.donation missing")
                else:
                    if not isinstance(don.get("donate_carries"), bool):
                        errors.append(
                            f"{where}: roofline.donation.donate_carries "
                            "missing or not a bool"
                        )
                    ab = don.get("alias_bytes")
                    if not isinstance(ab, dict) or not all(
                        isinstance(v, int) and v >= 0 for v in ab.values()
                    ):
                        errors.append(
                            f"{where}: roofline.donation.alias_bytes missing "
                            "or not a {entry: non-negative int} map"
                        )
                    elif don.get("donate_carries") and not any(
                        v > 0 for v in ab.values()
                    ) and any(
                        name in ab for name in ("run", "pipeline_tell")
                    ):
                        # coherence is only checkable when a DONATED entry
                        # (run carry / pipelined tell-ctx) actually got a
                        # successful memory analysis — degraded analyses
                        # (per-entry 'error' statics, the designed AOT
                        # fallback) drop out of the map and must not flag
                        errors.append(
                            f"{where}: roofline.donation claims "
                            "donate_carries but the analyzed run/"
                            "pipeline_tell entries show zero alias bytes — "
                            "the aliasing never reached the compiled program"
                        )
    return errors


# v12 (ISSUE 18, workflows/control_plane.py): the multi-pod gateway's
# global ledger event-kind whitelist
CONTROL_LEDGER_KINDS = {
    "submit",
    "place",
    "steal",
    "autoscale",
    "pod_open",
    "pod_dead",
    "pod_close",
    "recover",
}


def _validate_control_plane(cp: Any, where: str) -> List[str]:
    """The ``control_plane`` section (schema v12, ISSUE 18,
    workflows/control_plane.py): a disjoint pod census whose draining
    set is live, known ledger event kinds whose counts sum to the
    ledger's record count, ledger-vs-counter coherence for the
    transitions both sides record (submit/steal/pod_open/pod_dead), and
    the exactly-once admission audit — ANY duplicate admission across
    the live pods' journals is a violated law, not a warning."""
    errors: List[str] = []
    if not isinstance(cp, dict):
        return [f"{where}: control_plane is not an object"]
    pods = cp.get("pods")
    live: List[str] = []
    if not isinstance(pods, dict):
        errors.append(f"{where}: control_plane.pods missing")
        pods = {}
    opened = pods.get("opened")
    if not isinstance(opened, int) or opened < 0:
        errors.append(
            f"{where}: control_plane.pods.opened missing or not a "
            "non-negative int"
        )
    census: dict = {}
    for key in ("live", "dead", "closed", "draining"):
        v = pods.get(key)
        if not isinstance(v, list) or not all(
            isinstance(p, str) for p in v
        ):
            errors.append(
                f"{where}: control_plane.pods.{key} missing or not a "
                "list of pod ids"
            )
            census[key] = set()
        else:
            census[key] = set(v)
    live = sorted(census.get("live", ()))
    for a, b in (("live", "dead"), ("live", "closed"), ("dead", "closed")):
        both = census[a] & census[b]
        if both:
            errors.append(
                f"{where}: control_plane.pods {sorted(both)} listed as "
                f"both {a} and {b} — the census must be disjoint"
            )
    if not census["draining"] <= census["live"]:
        errors.append(
            f"{where}: control_plane.pods.draining "
            f"{sorted(census['draining'] - census['live'])} not live — "
            "only a live pod can drain"
        )
    if isinstance(opened, int) and opened < sum(
        len(census[k]) for k in ("live", "dead", "closed")
    ):
        errors.append(
            f"{where}: control_plane.pods.opened {opened} < the census "
            "total — pods exist the ledger never opened"
        )
    tenants = cp.get("tenants")
    if not isinstance(tenants, dict):
        errors.append(f"{where}: control_plane.tenants missing")
        tenants = {}
    for key in ("submitted", "placed", "stolen", "steal_dedup", "results"):
        v = tenants.get(key)
        if not isinstance(v, int) or v < 0:
            errors.append(
                f"{where}: control_plane.tenants.{key} missing or not a "
                "non-negative int"
            )
    events = cp.get("events")
    if not isinstance(events, dict):
        errors.append(f"{where}: control_plane.events missing")
        events = {}
    total = 0
    for kind, count in events.items():
        if kind not in CONTROL_LEDGER_KINDS:
            errors.append(
                f"{where}: control_plane.events has unknown ledger kind "
                f"{kind!r}"
            )
        if not isinstance(count, int) or count < 0:
            errors.append(
                f"{where}: control_plane.events.{kind} not a "
                "non-negative int"
            )
        else:
            total += count
    ledger = cp.get("ledger")
    if not isinstance(ledger, dict):
        errors.append(f"{where}: control_plane.ledger missing")
        ledger = {}
    for key in ("records", "rotations", "recoveries"):
        v = ledger.get(key)
        if not isinstance(v, int) or v < 0:
            errors.append(
                f"{where}: control_plane.ledger.{key} missing or not a "
                "non-negative int"
            )
    if events and isinstance(ledger.get("records"), int) and total != ledger[
        "records"
    ]:
        errors.append(
            f"{where}: control_plane.events sum {total} != ledger.records "
            f"{ledger['records']} — the kind histogram lost records"
        )
    # ledger-vs-counter coherence: both sides record these transitions
    # (the gateway's counter at the call site, the ledger as the WAL),
    # and recovery rebuilds the counters FROM the ledger — so they must
    # agree exactly
    for counter_side, ledger_kind, counter in (
        ("tenants.submitted", "submit", tenants.get("submitted")),
        ("tenants.stolen", "steal", tenants.get("stolen")),
        ("pods.opened", "pod_open", opened),
        (
            "pods.dead census",
            "pod_dead",
            len(census["dead"]) if census.get("dead") is not None else None,
        ),
    ):
        led = events.get(ledger_kind, 0)
        if isinstance(counter, int) and isinstance(led, int) and counter != led:
            errors.append(
                f"{where}: control_plane.{counter_side} {counter} "
                f"disagrees with ledger {ledger_kind} count {led}"
            )
    # placements can exceed the counter after a recovery replay
    # (re-placements reuse the original place record) — only the
    # impossible direction is a violation
    placed = tenants.get("placed")
    if isinstance(placed, int) and isinstance(
        events.get("place"), int
    ) and placed > events["place"]:
        errors.append(
            f"{where}: control_plane.tenants.placed {placed} > ledger "
            f"place count {events['place']} — a placement the WAL never "
            "saw"
        )
    eo = cp.get("exactly_once")
    if not isinstance(eo, dict):
        errors.append(f"{where}: control_plane.exactly_once missing")
    else:
        if not isinstance(eo.get("audited_tags"), int):
            errors.append(
                f"{where}: control_plane.exactly_once.audited_tags "
                "missing or not an int"
            )
        dup = eo.get("duplicate_admissions")
        if not isinstance(dup, dict):
            errors.append(
                f"{where}: control_plane.exactly_once."
                "duplicate_admissions missing or not an object"
            )
        elif dup:
            errors.append(
                f"{where}: control_plane.exactly_once reports duplicate "
                f"admissions {dup} — a spec was admitted twice; the "
                "steal-dedup law is violated"
            )
    steals = cp.get("steals")
    if not isinstance(steals, list):
        errors.append(f"{where}: control_plane.steals missing")
    else:
        if isinstance(tenants.get("stolen"), int) and len(
            steals
        ) != tenants["stolen"]:
            errors.append(
                f"{where}: control_plane.steals has {len(steals)} "
                f"events but tenants.stolen is {tenants['stolen']}"
            )
        for i, ev in enumerate(steals):
            loc = f"{where}: control_plane.steals[{i}]"
            if not isinstance(ev, dict):
                errors.append(f"{loc} is not an object")
                continue
            for key in ("tag", "from_pod", "to_pod"):
                if not isinstance(ev.get(key), str):
                    errors.append(f"{loc}.{key} missing or not a string")
            if ev.get("from_pod") == ev.get("to_pod"):
                errors.append(
                    f"{loc}: from_pod == to_pod {ev.get('to_pod')!r} — a "
                    "steal that moved nothing"
                )
    auto = cp.get("autoscale")
    if not isinstance(auto, dict):
        errors.append(f"{where}: control_plane.autoscale missing")
    elif not isinstance(auto.get("events"), list):
        errors.append(f"{where}: control_plane.autoscale.events missing")
    slo = cp.get("slo")
    if slo is not None:
        errors += _validate_slo_ledger(slo, f"{where}: control_plane")
    metrics = cp.get("metrics")
    if metrics is not None:
        if not isinstance(metrics, dict):
            errors.append(f"{where}: control_plane.metrics not an object")
        else:
            for name, v in metrics.items():
                if not str(name).startswith("control."):
                    errors.append(
                        f"{where}: control_plane.metrics.{name} outside "
                        "the control.* namespace"
                    )
                if not _num(v):
                    errors.append(
                        f"{where}: control_plane.metrics.{name} "
                        "non-numeric"
                    )
    return errors


def _validate_pod_supervisor(pod: Any, where: str) -> List[str]:
    """The ``pod_supervisor`` section (schema v9, ISSUE 14,
    core/pod_supervisor.py): known event kinds on a monotonic clock,
    censuses whose alive set never GROWS within one pod epoch (members
    leave by dying; they rejoin only through a re-formation, which is a
    new report), classified failures, and reform ↔ resume coherence —
    a report that claims a re-formation must show the barrier resume
    that completes it, and vice versa for the ``resumed`` outcome."""
    errors: List[str] = []
    if not isinstance(pod, dict):
        return [f"{where}: pod_supervisor is not an object"]
    if pod.get("outcome") not in POD_OUTCOMES:
        errors.append(
            f"{where}: pod_supervisor.outcome {pod.get('outcome')!r} not "
            f"in {sorted(POD_OUTCOMES)}"
        )
    for key in ("process_id", "process_count", "epoch"):
        v = pod.get(key)
        if not isinstance(v, int) or v < 0:
            errors.append(
                f"{where}: pod_supervisor.{key} missing or not a "
                "non-negative int"
            )
    counters = pod.get("counters")
    if not isinstance(counters, dict):
        errors.append(f"{where}: pod_supervisor.counters missing")
    else:
        for key in POD_COUNTERS:
            v = counters.get(key)
            if not isinstance(v, int) or v < 0:
                errors.append(
                    f"{where}: pod_supervisor.counters.{key} missing or "
                    "not a non-negative int"
                )
    events = pod.get("events")
    kinds_seen = []
    if not isinstance(events, list):
        errors.append(f"{where}: pod_supervisor.events missing")
        events = []
    last_t = float("-inf")
    last_alive = None
    for i, ev in enumerate(events):
        loc = f"{where}: pod_supervisor.events[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{loc} is not an object")
            continue
        kind = ev.get("event")
        kinds_seen.append(kind)
        if kind not in POD_EVENTS:
            errors.append(
                f"{loc}.event {kind!r} not in {sorted(POD_EVENTS)}"
            )
        t = ev.get("t")
        if not _num(t) or t < 0:
            errors.append(f"{loc}.t missing/negative")
        elif t < last_t:
            errors.append(f"{loc}.t not monotonic")
        else:
            last_t = t
        if kind == "census":
            alive = ev.get("alive")
            if not isinstance(alive, list):
                errors.append(f"{loc}.alive missing")
            else:
                if last_alive is not None and not set(alive) <= set(
                    last_alive
                ):
                    errors.append(
                        f"{loc}: census alive set {alive} grew vs the "
                        f"previous census {last_alive} — membership is "
                        "monotonic within a pod epoch"
                    )
                last_alive = alive
        if kind == "failure" and ev.get(
            "classification"
        ) not in POD_FAILURE_CLASSES:
            errors.append(
                f"{loc}.classification {ev.get('classification')!r} not "
                f"in {sorted(POD_FAILURE_CLASSES)}"
            )
        if kind == "resume" and not (
            isinstance(ev.get("generation"), int) and ev["generation"] >= 0
        ):
            errors.append(f"{loc}.generation missing/negative")
    # reform ↔ resume coherence
    if "reform" in kinds_seen and "resume" not in kinds_seen:
        errors.append(
            f"{where}: pod_supervisor records a reform but no resume — a "
            "re-formed pod that never restored a barrier snapshot did "
            "not actually heal"
        )
    if pod.get("outcome") == "resumed" and "resume" not in kinds_seen:
        errors.append(
            f"{where}: pod_supervisor.outcome 'resumed' without a resume "
            "event"
        )
    if pod.get("outcome") == "failed" and "failure" not in kinds_seen:
        errors.append(
            f"{where}: pod_supervisor.outcome 'failed' without a failure "
            "event"
        )
    if pod.get("outcome") == "drained" and "drain" not in kinds_seen:
        errors.append(
            f"{where}: pod_supervisor.outcome 'drained' without a drain "
            "event"
        )
    return errors


SURROGATE_MODELS = {"gp", "ensemble"}
SURROGATE_COUNTERS = (
    "candidates_seen",
    "true_evals",
    "screened_out",
    "generations",
    "screened_gens",
    "fallback_gens",
    "warmup_gens",
)
# bitmask of known fallback reasons (workflows/surrogate.py
# FALLBACK_RANK | FALLBACK_UNCERTAINTY)
_SURROGATE_REASON_MASK = 3


def _validate_surrogate(sur: Any, where: str) -> List[str]:
    """The ``surrogate`` section (schema v10, workflows/surrogate.py):
    the screened-vs-true eval ledger must be internally coherent —
    ``true_evals + screened_out == candidates_seen`` (every asked row is
    either truly evaluated or screened out, never both or neither) and
    ``screened_gens + fallback_gens + warmup_gens == generations``
    (every generation is exactly one of the three) — counters are
    non-negative ints, the archive fill respects its capacity, and the
    fallback events are chronological with known reason bits (the
    chunk-ordered discipline every event log in this repo follows)."""
    errors: List[str] = []
    if not isinstance(sur, dict):
        return [f"{where}: surrogate is not an object"]
    if set(sur) == {"error"}:
        # degraded form, same contract as roofline.error
        if not isinstance(sur["error"], str):
            errors.append(f"{where}: surrogate.error is not a string")
        return errors
    enabled = sur.get("enabled")
    if not isinstance(enabled, bool):
        errors.append(f"{where}: surrogate.enabled missing or not a bool")
    if not enabled:
        return errors  # disabled sections are minimal by design
    if sur.get("model") not in SURROGATE_MODELS:
        errors.append(
            f"{where}: surrogate.model {sur.get('model')!r} not in "
            f"{sorted(SURROGATE_MODELS)}"
        )
    frac = sur.get("screen_frac")
    if not _num(frac) or not (0 < frac < 1):
        errors.append(
            f"{where}: surrogate.screen_frac {frac!r} must be in (0, 1) "
            "for an enabled section (1.0 is the disabled path)"
        )
    archive = sur.get("archive")
    if not isinstance(archive, dict):
        errors.append(f"{where}: surrogate.archive missing")
        archive = {}
    for key in ("capacity", "fill", "writes"):
        v = archive.get(key)
        if not isinstance(v, int) or v < 0:
            errors.append(
                f"{where}: surrogate.archive.{key} missing or not a "
                "non-negative int"
            )
    cap, fill, writes = (
        archive.get("capacity"),
        archive.get("fill"),
        archive.get("writes"),
    )
    if isinstance(cap, int) and isinstance(fill, int) and fill > cap:
        errors.append(f"{where}: surrogate.archive fill {fill} > capacity {cap}")
    if isinstance(fill, int) and isinstance(writes, int) and fill > writes:
        errors.append(
            f"{where}: surrogate.archive fill {fill} > writes {writes} — "
            "the ring cannot hold pairs that were never written"
        )
    refit = sur.get("refit")
    if not isinstance(refit, dict):
        errors.append(f"{where}: surrogate.refit missing")
        refit = {}
    if not isinstance(refit.get("count"), int) or refit.get("count", -1) < 0:
        errors.append(f"{where}: surrogate.refit.count missing or negative")
    if not isinstance(refit.get("every"), int) or refit.get("every", 0) < 1:
        errors.append(f"{where}: surrogate.refit.every missing or < 1")
    counters = sur.get("counters")
    if not isinstance(counters, dict):
        errors.append(f"{where}: surrogate.counters missing")
        counters = {}
    for key in SURROGATE_COUNTERS:
        v = counters.get(key)
        if not isinstance(v, int) or v < 0:
            errors.append(
                f"{where}: surrogate.counters.{key} missing or not a "
                "non-negative int"
            )
    if all(isinstance(counters.get(k), int) for k in SURROGATE_COUNTERS):
        if (
            counters["true_evals"] + counters["screened_out"]
            != counters["candidates_seen"]
        ):
            errors.append(
                f"{where}: surrogate counters true_evals "
                f"{counters['true_evals']} + screened_out "
                f"{counters['screened_out']} != candidates_seen "
                f"{counters['candidates_seen']} — every asked row is "
                "either truly evaluated or screened out"
            )
        if (
            counters["screened_gens"]
            + counters["fallback_gens"]
            + counters["warmup_gens"]
            != counters["generations"]
        ):
            errors.append(
                f"{where}: surrogate generation counters do not "
                "partition: screened + fallback + warmup != generations"
            )
    events = sur.get("fallback_events")
    if not isinstance(events, list):
        errors.append(f"{where}: surrogate.fallback_events missing")
        events = []
    if isinstance(counters.get("fallback_gens"), int) and len(events) > counters[
        "fallback_gens"
    ]:
        errors.append(
            f"{where}: surrogate records {len(events)} fallback events "
            f"but only {counters['fallback_gens']} fallback generations"
        )
    last_gen = -1
    for i, ev in enumerate(events):
        loc = f"{where}: surrogate.fallback_events[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{loc} is not an object")
            continue
        g = ev.get("generation")
        if not isinstance(g, int) or g < 0:
            errors.append(f"{loc}.generation missing/negative")
        elif g < last_gen:
            errors.append(f"{loc}.generation not chronological")
        else:
            last_gen = g
        r = ev.get("reason")
        if (
            not isinstance(r, int)
            or r <= 0
            or r & ~_SURROGATE_REASON_MASK
        ):
            errors.append(
                f"{loc}.reason {r!r} is not a known fallback bitmask "
                f"(known bits: {_SURROGATE_REASON_MASK:#x})"
            )
    return errors


# v13 (ISSUE 19, monitors/lineage.py + core/attribution.py): the
# operator-attribution tag vocabulary — ledger keys and ancestry op tags
# must come from here (append-only in the source; renaming would corrupt
# forensics across checkpoint resumes)
SEARCH_OP_NAMES = {
    "none",
    "init",
    "sample",
    "velocity",
    "de_rand_1",
    "de_rand_2",
    "de_rand_to_best_2",
    "de_cur_to_rand_1",
    "de_cur_to_pbest_1",
    "de_best",
    "crossover",
    "mutation",
}


def _validate_integrity(integrity: Any, where: str) -> List[str]:
    """The ``integrity`` section (schema v14, ISSUE 20, core/attest.py):
    the attestation ring's generations must be strictly increasing and
    cadence-aligned (every entry divisible by ``every``) with 48-char
    hex digests; the verdict must come from the closed set; the verify
    counters must cohere (``verify_dispatches == verified_chunks +
    2*mismatches`` — each mismatch costs exactly two extra dispatches —
    and ``healed <= mismatches``); a bisection that names a first
    divergent generation must name one inside its replay window."""
    errors: List[str] = []
    if not isinstance(integrity, dict):
        return [f"{where}: integrity is not an object"]
    if set(integrity) == {"error"}:
        # degraded form, same contract as roofline.error / search.error
        if not isinstance(integrity["error"], str):
            errors.append(f"{where}: integrity.error is not a string")
        return errors
    enabled = integrity.get("enabled")
    if not isinstance(enabled, bool):
        errors.append(f"{where}: integrity.enabled missing or not a bool")
    if not enabled:
        return errors  # disabled sections are minimal by design
    verdict = integrity.get("verdict")
    if verdict not in INTEGRITY_VERDICTS:
        errors.append(
            f"{where}: integrity.verdict {verdict!r} not in "
            f"{sorted(INTEGRITY_VERDICTS)}"
        )
    attestations = integrity.get("attestations")
    if not isinstance(attestations, int) or attestations < 0:
        errors.append(
            f"{where}: integrity.attestations missing or not a "
            "non-negative int"
        )
    every = integrity.get("every")
    if every is not None and (not isinstance(every, int) or every < 1):
        errors.append(f"{where}: integrity.every is not a positive int")
    ring = integrity.get("ring")
    if not isinstance(ring, list):
        errors.append(f"{where}: integrity.ring missing")
        ring = []
    last_gen = None
    for i, entry in enumerate(ring):
        loc = f"{where}: integrity.ring[{i}]"
        if not isinstance(entry, dict):
            errors.append(f"{loc} is not an object")
            continue
        gen = entry.get("generation")
        if not isinstance(gen, int) or gen < 0:
            errors.append(f"{loc}.generation missing or negative")
            continue
        if last_gen is not None and gen <= last_gen:
            errors.append(
                f"{loc}.generation {gen} not strictly increasing "
                f"(previous {last_gen}) — the ring is chronological"
            )
        last_gen = gen
        if isinstance(every, int) and every >= 1 and gen % every != 0:
            errors.append(
                f"{loc}.generation {gen} is not a multiple of the "
                f"attestation cadence {every}"
            )
        digest = entry.get("digest")
        if (
            not isinstance(digest, str)
            or len(digest) != 48
            or any(c not in "0123456789abcdef" for c in digest)
        ):
            errors.append(f"{loc}.digest is not a 48-char lowercase hex")
    verify = integrity.get("verify")
    if verify is not None:
        if not isinstance(verify, dict):
            errors.append(f"{where}: integrity.verify is not an object")
        else:
            for key in (
                "redispatches",
                "verified_chunks",
                "mismatches",
                "healed",
                "aborted",
            ):
                v = verify.get(key)
                if not isinstance(v, int) or v < 0:
                    errors.append(
                        f"{where}: integrity.verify.{key} missing or not "
                        "a non-negative int"
                    )
            rd, vc, mm = (
                verify.get("redispatches"),
                verify.get("verified_chunks"),
                verify.get("mismatches"),
            )
            if (
                isinstance(rd, int)
                and isinstance(vc, int)
                and isinstance(mm, int)
                and rd != vc + 2 * mm
            ):
                errors.append(
                    f"{where}: integrity.verify.redispatches {rd} != "
                    f"verified_chunks {vc} + 2*mismatches {mm} — each "
                    "mismatch escalates to exactly two more dispatches"
                )
            healed = verify.get("healed")
            if (
                isinstance(healed, int)
                and isinstance(mm, int)
                and healed > mm
            ):
                errors.append(
                    f"{where}: integrity.verify.healed {healed} > "
                    f"mismatches {mm} — a heal needs a detected mismatch"
                )
            ve = verify.get("verify_every")
            if ve is not None and (not isinstance(ve, int) or ve < 1):
                errors.append(
                    f"{where}: integrity.verify.verify_every is not a "
                    "positive int"
                )
    bisection = integrity.get("bisection")
    if bisection is not None:
        if not isinstance(bisection, dict):
            errors.append(f"{where}: integrity.bisection is not an object")
        else:
            fdg = bisection.get("first_divergent_generation")
            window = bisection.get("window")
            if fdg is not None:
                if not isinstance(fdg, int):
                    errors.append(
                        f"{where}: integrity.bisection."
                        "first_divergent_generation is not an int"
                    )
                elif (
                    isinstance(window, (list, tuple))
                    and len(window) == 2
                    and all(isinstance(w, int) for w in window)
                    and not (window[0] < fdg <= window[1])
                ):
                    errors.append(
                        f"{where}: integrity.bisection names generation "
                        f"{fdg} outside its replay window {list(window)}"
                    )
    # verdict ↔ counter coherence: a verdict that claims healing/abort
    # must be backed by the matching counter, and vice versa
    if isinstance(verify, dict):
        healed, aborted, mm = (
            verify.get("healed"),
            verify.get("aborted"),
            verify.get("mismatches"),
        )
        if verdict == "healed" and not healed:
            errors.append(
                f"{where}: integrity.verdict 'healed' with verify.healed 0"
            )
        if verdict == "aborted" and not aborted:
            errors.append(
                f"{where}: integrity.verdict 'aborted' with "
                "verify.aborted 0"
            )
        if (
            verdict == "clean"
            and isinstance(mm, int)
            and mm > 0
        ):
            errors.append(
                f"{where}: integrity.verdict 'clean' with "
                f"verify.mismatches {mm}"
            )
    return errors


def _validate_search(search: Any, where: str) -> List[str]:
    """The ``search`` section (schema v13, ISSUE 19,
    monitors/lineage.py): the attribution ledger must be coherent —
    per-operator ``successes <= attempts``, improvement mass
    non-negative, and total attempts exactly ``generations * width``
    (every generation attributes every slot exactly once); the
    best-ancestry chain must carry in-range slot/parent indices, strictly
    descending consecutive generations, and a single epoch (the monitor
    never walks an edge across a restart); the trajectory window's delta
    is non-negative (best-so-far is monotone), its epoch non-decreasing,
    and the MO churn/front-size rings non-negative and front sizes within
    the batch width."""
    errors: List[str] = []
    if not isinstance(search, dict):
        return [f"{where}: search is not an object"]
    if set(search) == {"error"}:
        # degraded form, same contract as roofline.error
        if not isinstance(search["error"], str):
            errors.append(f"{where}: search.error is not a string")
        return errors
    enabled = search.get("enabled")
    if not isinstance(enabled, bool):
        errors.append(f"{where}: search.enabled missing or not a bool")
    if not enabled:
        return errors  # disabled sections are minimal by design
    for key in ("generations", "capacity", "width", "epoch", "restarts"):
        v = search.get(key)
        if not isinstance(v, int) or v < 0:
            errors.append(
                f"{where}: search.{key} missing or not a non-negative int"
            )
    gens = search.get("generations")
    cap = search.get("capacity")
    width = search.get("width")
    if isinstance(cap, int) and cap < 1:
        errors.append(f"{where}: search.capacity {cap} < 1")
    epoch, restarts = search.get("epoch"), search.get("restarts")
    if (
        isinstance(epoch, int)
        and isinstance(restarts, int)
        and epoch < restarts
    ):
        errors.append(
            f"{where}: search.epoch {epoch} < restarts {restarts} — the "
            "epoch counter includes every restart"
        )
    # ---- ledger: the credit table sums must add up
    ledger = search.get("ledger")
    if not isinstance(ledger, dict):
        errors.append(f"{where}: search.ledger missing")
        ledger = {}
    total_attempts = 0
    for op, row in ledger.items():
        loc = f"{where}: search.ledger.{op}"
        if op not in SEARCH_OP_NAMES:
            errors.append(f"{loc} is not a known operator tag")
        if not isinstance(row, dict):
            errors.append(f"{loc} is not an object")
            continue
        a, s, imp = row.get("attempts"), row.get("successes"), row.get(
            "improvement"
        )
        for key, v in (("attempts", a), ("successes", s)):
            if not isinstance(v, int) or v < 0:
                errors.append(f"{loc}.{key} missing or not a non-negative int")
        if isinstance(a, int) and isinstance(s, int) and s > a:
            errors.append(
                f"{loc}: successes {s} > attempts {a} — a candidate "
                "cannot succeed without being attempted"
            )
        if not _num(imp) or imp < 0:
            errors.append(
                f"{loc}.improvement missing or negative — improvement "
                "mass is clipped at the source"
            )
        if isinstance(a, int):
            total_attempts += a
    if (
        isinstance(gens, int)
        and isinstance(width, int)
        and total_attempts != gens * width
    ):
        errors.append(
            f"{where}: search.ledger attempts sum to {total_attempts} but "
            f"generations*width = {gens * width} — every generation "
            "attributes every slot exactly once"
        )
    # ---- ancestry: the traceback chain must be walkable
    ancestry = search.get("ancestry")
    if not isinstance(ancestry, list):
        errors.append(f"{where}: search.ancestry missing")
        ancestry = []
    if (
        isinstance(gens, int)
        and isinstance(cap, int)
        and len(ancestry) > min(gens, cap)
    ):
        errors.append(
            f"{where}: search.ancestry has {len(ancestry)} links but only "
            f"min(generations={gens}, capacity={cap}) are recorded"
        )
    prev_gen = None
    chain_epochs = set()
    for i, link in enumerate(ancestry):
        loc = f"{where}: search.ancestry[{i}]"
        if not isinstance(link, dict):
            errors.append(f"{loc} is not an object")
            continue
        g = link.get("generation")
        if not isinstance(g, int) or g < 1 or (
            isinstance(gens, int) and g > gens
        ):
            errors.append(f"{loc}.generation {g!r} out of range")
        elif prev_gen is not None and g != prev_gen - 1:
            errors.append(
                f"{loc}.generation {g} does not descend consecutively "
                f"from {prev_gen} — the chain is newest-first, one link "
                "per generation"
            )
        prev_gen = g if isinstance(g, int) else prev_gen
        for key in ("slot", "parent"):
            v = link.get(key)
            if not isinstance(v, int) or v < 0 or (
                isinstance(width, int) and width > 0 and v >= width
            ):
                errors.append(
                    f"{loc}.{key} {v!r} not in [0, width={width})"
                )
        if link.get("op") not in SEARCH_OP_NAMES:
            errors.append(f"{loc}.op {link.get('op')!r} unknown")
        if isinstance(link.get("epoch"), int):
            chain_epochs.add(link["epoch"])
        else:
            errors.append(f"{loc}.epoch missing or not an int")
    if len(chain_epochs) > 1:
        errors.append(
            f"{where}: search.ancestry spans epochs {sorted(chain_epochs)} "
            "— descent across a restart/exploit boundary is fiction"
        )
    # ---- trajectory window (+ MO churn coherence)
    traj = search.get("trajectory")
    if not isinstance(traj, dict):
        errors.append(f"{where}: search.trajectory missing")
        traj = {}
    tg = traj.get("generation")
    if not isinstance(tg, list):
        errors.append(f"{where}: search.trajectory.generation missing")
        tg = []
    if isinstance(cap, int) and len(tg) > cap:
        errors.append(
            f"{where}: search.trajectory holds {len(tg)} rows but "
            f"capacity is {cap}"
        )
    if tg != sorted(tg):
        errors.append(f"{where}: search.trajectory.generation not ascending")
    track_keys = ["best_slot", "best_fitness", "delta", "epoch"]
    is_mo = isinstance(search.get("num_objectives"), int) and search[
        "num_objectives"
    ] > 1
    if is_mo:
        track_keys += ["front_size", "churn"]
    for key in track_keys:
        col = traj.get(key)
        if not isinstance(col, list) or len(col) != len(tg):
            errors.append(
                f"{where}: search.trajectory.{key} missing or length "
                f"mismatch with .generation"
            )
            continue
        if key == "delta" and any(not _num(v) or v < 0 for v in col):
            errors.append(
                f"{where}: search.trajectory.delta has negative entries — "
                "best-so-far deltas are non-negative by construction"
            )
        if key == "epoch" and col != sorted(col):
            errors.append(
                f"{where}: search.trajectory.epoch decreases — restart "
                "epochs only ever advance"
            )
        if key == "best_slot" and isinstance(width, int) and width > 0 and any(
            not isinstance(v, int) or v < 0 or v >= width for v in col
        ):
            errors.append(
                f"{where}: search.trajectory.best_slot out of [0, {width})"
            )
        if key == "churn" and any(not _num(v) or v < 0 for v in col):
            errors.append(
                f"{where}: search.trajectory.churn has negative or "
                "non-numeric entries"
            )
        if key == "front_size" and any(
            not isinstance(v, int)
            or v < 0
            or (isinstance(width, int) and width > 0 and v > width)
            for v in col
        ):
            errors.append(
                f"{where}: search.trajectory.front_size out of "
                f"[0, width={width}]"
            )
    return errors


def _validate_sharding(shd: Any, where: str) -> List[str]:
    """The roofline ``sharding`` subsection (schema v5, PR 10): a
    POP-sharded run's AOT per-device peak vs full-pop bytes. The
    inequality IS the acceptance criterion — per-device memory must scale
    as pop/n_dev, so the per-device peak of a gather-free compiled step
    sits strictly below the bytes of the full-population artifacts."""
    errors: List[str] = []
    if not isinstance(shd, dict):
        return [f"{where}: roofline.sharding is not an object"]
    if not isinstance(shd.get("axis"), str):
        errors.append(f"{where}: roofline.sharding.axis missing")
    for key in ("n_devices", "pop_size", "per_device_peak_bytes", "full_pop_bytes"):
        v = shd.get(key)
        if not isinstance(v, int) or v < 1:
            errors.append(
                f"{where}: roofline.sharding.{key} missing or not a "
                "positive int"
            )
    peak, full = shd.get("per_device_peak_bytes"), shd.get("full_pop_bytes")
    if isinstance(peak, int) and isinstance(full, int) and peak >= full:
        errors.append(
            f"{where}: roofline.sharding per_device_peak_bytes {peak} >= "
            f"full_pop_bytes {full} — the compiled step materializes the "
            "full population on one device (not gather-free)"
        )
    if shd.get("gather_free") is not True:
        errors.append(
            f"{where}: roofline.sharding.gather_free is not true — a "
            "sharded run whose own report denies the gather-free property "
            "must not ship"
        )
    return errors


def _validate_multihost(mh: Any, where: str) -> List[str]:
    """The roofline ``multihost`` subsection (schema v8, ISSUE 13): a
    multi-process run's per-process AOT peak and collective-bytes
    estimate. Coherence rules: per-process peak = per-device peak ×
    local device count (memory_analysis is per-device for SPMD
    programs), and the per-DEVICE peak must stay below the full-pop
    artifact bytes — a pod program that gathers the population onto one
    device fails here, not in a dashboard."""
    errors: List[str] = []
    if not isinstance(mh, dict):
        return [f"{where}: roofline.multihost is not an object"]
    for key, floor in (
        ("process_count", 2),
        ("n_local_devices", 1),
        ("per_device_peak_bytes", 1),
        ("per_process_peak_bytes", 1),
        ("full_pop_bytes", 1),
        ("collective_bytes_estimate", 0),
    ):
        v = mh.get(key)
        if not isinstance(v, int) or v < floor:
            errors.append(
                f"{where}: roofline.multihost.{key} missing or below "
                f"{floor}"
            )
    per_dev = mh.get("per_device_peak_bytes")
    per_proc = mh.get("per_process_peak_bytes")
    n_local = mh.get("n_local_devices")
    full = mh.get("full_pop_bytes")
    if (
        isinstance(per_dev, int)
        and isinstance(per_proc, int)
        and isinstance(n_local, int)
        and per_proc != per_dev * n_local
    ):
        errors.append(
            f"{where}: roofline.multihost per_process_peak_bytes "
            f"{per_proc} != per_device_peak_bytes {per_dev} * "
            f"n_local_devices {n_local}"
        )
    if (
        isinstance(per_dev, int)
        and isinstance(full, int)
        and full > 0
        and per_dev >= full
    ):
        errors.append(
            f"{where}: roofline.multihost per_device_peak_bytes "
            f"{per_dev} >= full_pop_bytes {full} — the pod program "
            "materializes the full population per device"
        )
    return errors


EXECUTOR_COUNTERS = (
    "runs",
    "chunks",
    "generations",
    "asks",
    "tells",
    "stale_tells",
    "max_lag",
    "bg_checkpoint",
    "bg_hook",
    "bg_fetch",
)
EXECUTOR_SPANS = ("device_dispatch_s", "host_eval_s", "io_s", "wall_s")


def _validate_executor(executor: Any, where: str) -> List[str]:
    """The ``executor`` section (schema v4, core/executor.py): counters
    must be coherent non-negative ints (a tell can't be staler than the
    declared bound, stale tells can't outnumber tells), and the overlap
    spans must be coherent with each other and with the dispatch
    recorder's window — device dispatch time is a subset of the
    executor's wall, which is a subset of the recorder's."""
    errors: List[str] = []
    if not isinstance(executor, dict):
        return [f"{where}: executor is not an object"]
    k = executor.get("max_staleness")
    if not isinstance(k, int) or k < 0:
        errors.append(f"{where}: executor.max_staleness missing or negative")
    counters = executor.get("counters")
    if not isinstance(counters, dict):
        errors.append(f"{where}: executor.counters missing")
        counters = {}
    for key in EXECUTOR_COUNTERS:
        v = counters.get(key)
        if not isinstance(v, int) or v < 0:
            errors.append(
                f"{where}: executor.counters.{key} missing or not a "
                "non-negative int"
            )
    if isinstance(counters.get("stale_tells"), int) and isinstance(
        counters.get("tells"), int
    ):
        if counters["stale_tells"] > counters["tells"]:
            errors.append(f"{where}: executor stale_tells > tells")
    if (
        isinstance(counters.get("max_lag"), int)
        and isinstance(k, int)
        and counters["max_lag"] > k
    ):
        errors.append(
            f"{where}: executor max_lag {counters['max_lag']} exceeds "
            f"max_staleness {k}"
        )
    queue = executor.get("queue")
    if not isinstance(queue, dict):
        errors.append(f"{where}: executor.queue missing")
    else:
        for key in ("io_inflight_limit", "io_inflight_max", "stale_window_max"):
            v = queue.get(key)
            if not isinstance(v, int) or v < 0:
                errors.append(
                    f"{where}: executor.queue.{key} missing or not a "
                    "non-negative int"
                )
        if (
            isinstance(queue.get("io_inflight_max"), int)
            and isinstance(queue.get("io_inflight_limit"), int)
            and queue["io_inflight_max"] > queue["io_inflight_limit"]
        ):
            errors.append(
                f"{where}: executor.queue io_inflight_max exceeds its limit "
                "— the in-flight bound was not enforced"
            )
    overlap = executor.get("overlap")
    if not isinstance(overlap, dict):
        errors.append(f"{where}: executor.overlap missing")
        return errors
    for key in EXECUTOR_SPANS:
        v = overlap.get(key)
        if not _num(v) or v < 0:
            errors.append(
                f"{where}: executor.overlap.{key} missing or negative"
            )
    wall = overlap.get("wall_s")
    device = overlap.get("device_dispatch_s")
    if _num(wall) and _num(device) and device > wall * 1.05 + 1e-3:
        # device dispatch happens INSIDE executor runs: its total can
        # never exceed the executor's wall window (host eval legitimately
        # can — K>0 runs evaluations concurrently)
        errors.append(
            f"{where}: executor.overlap.device_dispatch_s {device} exceeds "
            f"wall_s {wall} — overlap spans incoherent"
        )
    eff = overlap.get("overlap_efficiency")
    if eff is not None:
        if not _num(eff) or eff <= 0:
            errors.append(
                f"{where}: executor.overlap.overlap_efficiency neither null "
                "nor positive"
            )
        elif _num(wall) and _num(device) and _num(overlap.get("host_eval_s")):
            bound = max(device, overlap["host_eval_s"])
            if bound > 1e-9 and abs(eff - wall / bound) > max(
                0.15 * eff, 0.01
            ):
                errors.append(
                    f"{where}: executor.overlap.overlap_efficiency {eff} "
                    "inconsistent with wall / max(device, host)"
                )
    # NOTE: no executor-wall vs recorder-wall cross-check — a
    # GenerationExecutor documents accumulation across runs, so its wall
    # window may legitimately predate (and exceed) a recorder attached
    # later; span coherence is enforced WITHIN the executor section
    # (device <= wall, efficiency == wall / max(device, host)) instead.
    return errors


HEALTH_ACTIONS = {"freeze", "evict", "restart"}
JOURNAL_KINDS = {
    "submit",
    "start",
    "admit",
    "chunk_complete",
    "retire",
    "evict",
    "freeze",
    "health",
    "recover",
    # v7 (PR 12): SLA preemption and elastic-autoscale close-outs
    "preempt",
    "autoscale",
    # v12 (ISSUE 18): a queued continuation/spec released because the
    # multi-pod gateway re-placed it on another pod
    "steal",
    # v9 (ISSUE 14): pod membership transitions (core/pod_supervisor.py)
    "pod_join",
    "pod_failure",
    "pod_drain",
    "pod_reform",
    "pod_resume",
}


def _validate_journal(journal: Any, where: str) -> List[str]:
    """``tenancy.queue.journal`` (schema v6, workflows/journal.py): the
    WAL's event counters must be known kinds with non-negative counts
    summing to the record total (monotonic by construction: records ==
    last_seq + 1), and the ``recovered`` flag must agree with the
    presence of a ``recover`` event."""
    errors: List[str] = []
    if not isinstance(journal, dict):
        return [f"{where}: tenancy.queue.journal is not an object"]
    events = journal.get("events")
    if not isinstance(events, dict):
        errors.append(f"{where}: tenancy.queue.journal.events missing")
        events = {}
    total = 0
    for kind, count in events.items():
        if kind not in JOURNAL_KINDS:
            errors.append(
                f"{where}: tenancy.queue.journal.events has unknown kind "
                f"{kind!r}"
            )
        if not isinstance(count, int) or count < 0:
            errors.append(
                f"{where}: tenancy.queue.journal.events.{kind} not a "
                "non-negative int"
            )
        else:
            total += count
    records = journal.get("records")
    last_seq = journal.get("last_seq")
    if not isinstance(records, int) or records < 0:
        errors.append(f"{where}: tenancy.queue.journal.records missing")
    else:
        if events and total != records:
            errors.append(
                f"{where}: tenancy.queue.journal event counts sum to "
                f"{total} but records is {records} — the counters are "
                "not monotonic with the ledger"
            )
        if isinstance(last_seq, int) and last_seq != records - 1:
            errors.append(
                f"{where}: tenancy.queue.journal.last_seq {last_seq} != "
                f"records-1 ({records - 1})"
            )
    recovered = journal.get("recovered")
    if not isinstance(recovered, bool):
        errors.append(f"{where}: tenancy.queue.journal.recovered missing")
    elif recovered != (events.get("recover", 0) > 0):
        errors.append(
            f"{where}: tenancy.queue.journal.recovered {recovered} "
            "incoherent with its recover event count "
            f"{events.get('recover', 0)}"
        )
    return errors


def _validate_fleet_health(health: Any, where: str, n: int) -> List[str]:
    """``tenancy.fleet_health`` (schema v6, workflows/fleet_health.py):
    every event names a real slot and a known action, with chunk indices
    non-decreasing (the policy fires at chunk boundaries in order)."""
    errors: List[str] = []
    if not isinstance(health, dict):
        return [f"{where}: tenancy.fleet_health is not an object"]
    events = health.get("events")
    if not isinstance(events, list):
        return [f"{where}: tenancy.fleet_health.events missing"]
    last_chunk = -1
    for i, ev in enumerate(events):
        loc = f"{where}: tenancy.fleet_health.events[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{loc} is not an object")
            continue
        if ev.get("action") not in HEALTH_ACTIONS:
            errors.append(
                f"{loc}.action {ev.get('action')!r} not in "
                f"{sorted(HEALTH_ACTIONS)}"
            )
        slot = ev.get("slot")
        if not isinstance(slot, int) or not 0 <= slot < n:
            errors.append(
                f"{loc}.slot {slot!r} not a valid slot index for an "
                f"n_tenants={n} fleet"
            )
        if not isinstance(ev.get("reason"), str):
            errors.append(f"{loc}.reason missing")
        chunk = ev.get("chunk")
        if not isinstance(chunk, int) or chunk < 0:
            errors.append(f"{loc}.chunk missing/negative")
        elif chunk < last_chunk:
            errors.append(f"{loc}.chunk not non-decreasing")
        else:
            last_chunk = chunk
    return errors


def _validate_tenancy(tenancy: Any, where: str) -> List[str]:
    """The ``tenancy`` section (schema v3, workflows/tenancy.py): fleet
    shape coherent with the state's measured leading axes, per-tenant
    monitor counters non-negative with monotonic trajectory rings, and
    sane RunQueue counters when a queue drove the fleet. v6 adds the
    serving durability surfaces: ``queue.journal`` and
    ``fleet_health``, and requires every evicted result of a journaled
    queue to name its resumable checkpoint."""
    errors: List[str] = []
    if not isinstance(tenancy, dict):
        return [f"{where}: tenancy is not an object"]
    if set(tenancy) == {"error"}:
        # degraded form, same contract as roofline.error
        if not isinstance(tenancy["error"], str):
            errors.append(f"{where}: tenancy.error is not a string")
        return errors
    n = tenancy.get("n_tenants")
    if not isinstance(n, int) or n < 1:
        errors.append(f"{where}: tenancy.n_tenants missing or < 1")
        return errors
    leading = tenancy.get("leading_axes")
    if not isinstance(leading, list) or any(
        not isinstance(v, int) for v in leading
    ):
        errors.append(f"{where}: tenancy.leading_axes missing/non-int")
    elif leading and leading != [n]:
        # every tenant-stacked leaf must lead with exactly n_tenants —
        # anything else means the report and the state disagree about
        # the fleet width
        errors.append(
            f"{where}: tenancy.leading_axes {leading} incoherent with "
            f"n_tenants={n}"
        )
    per_tenant = tenancy.get("per_tenant")
    if not isinstance(per_tenant, list) or len(per_tenant) != n:
        errors.append(
            f"{where}: tenancy.per_tenant missing or length != n_tenants"
        )
        return errors
    for i, entry in enumerate(per_tenant):
        loc = f"{where}: tenancy.per_tenant[{i}]"
        if not isinstance(entry, dict):
            errors.append(f"{loc} is not an object")
            continue
        if entry.get("tenant") != i:
            errors.append(
                f"{loc}.tenant {entry.get('tenant')!r} != index {i}"
            )
        for mi, mon in enumerate(entry.get("monitors", []) or []):
            mloc = f"{loc}.monitors[{mi}]"
            if not isinstance(mon, dict) or "monitor" not in mon:
                errors.append(f"{mloc} lacks a 'monitor' key")
                continue
            for key in ("generations", "evals"):
                v = mon.get(key)
                if v is not None and (not isinstance(v, int) or v < 0):
                    errors.append(
                        f"{mloc}.{key} not a non-negative int"
                    )
            traj = mon.get("trajectory")
            if isinstance(traj, dict):
                gens = traj.get("generation", [])
                if not isinstance(gens, list):
                    errors.append(
                        f"{mloc}.trajectory.generation is not a list"
                    )
                elif any(b <= a for a, b in zip(gens, gens[1:])):
                    errors.append(
                        f"{mloc}.trajectory.generation not strictly "
                        "increasing"
                    )
    queue = tenancy.get("queue")
    if queue is not None:
        if not isinstance(queue, dict):
            errors.append(f"{where}: tenancy.queue is not an object")
        else:
            counters = queue.get("counters")
            if not isinstance(counters, dict):
                errors.append(f"{where}: tenancy.queue.counters missing")
            else:
                for key in ("submitted", "admitted", "retired", "evicted"):
                    v = counters.get(key)
                    if not isinstance(v, int) or v < 0:
                        errors.append(
                            f"{where}: tenancy.queue.counters.{key} "
                            "missing or not a non-negative int"
                        )
                if all(
                    isinstance(counters.get(k), int)
                    for k in ("submitted", "admitted", "retired", "evicted")
                ):
                    if counters["admitted"] > counters["submitted"]:
                        errors.append(
                            f"{where}: tenancy.queue admitted > submitted"
                        )
                    if (
                        counters["retired"] + counters["evicted"]
                        > counters["admitted"]
                    ):
                        errors.append(
                            f"{where}: tenancy.queue retired+evicted > "
                            "admitted"
                        )
            journal = queue.get("journal")
            if journal is not None:
                errors += _validate_journal(journal, where)
                # a journaled eviction's whole point is the resumable
                # artifact: every evicted/frozen result must name the
                # snapshot directory it parked its tenant in
                for i, res in enumerate(queue.get("results") or []):
                    if (
                        isinstance(res, dict)
                        and res.get("status")
                        in ("evicted", "frozen", "preempted")
                        and not isinstance(res.get("checkpoint"), str)
                    ):
                        # v7 adds preempted: its continuation resumes
                        # from exactly this artifact
                        errors.append(
                            f"{where}: tenancy.queue.results[{i}] is "
                            f"{res.get('status')} under a journal but "
                            "names no checkpoint path"
                        )
    health = tenancy.get("fleet_health")
    if health is not None:
        errors += _validate_fleet_health(health, where, n)
    return errors


SERVING_CACHE_COUNTERS = ("hits", "disk_hits", "misses", "saves", "evictions")
SERVING_ENTRY_SOURCES = {"compiled", "disk"}


def _validate_serving(serving: Any, where: str) -> List[str]:
    """The ``serving`` section (schema v7, core/exec_cache.py +
    workflows/elastic.py): the AOT executable cache's hit/miss/compile
    accounting and the bucket lattice. Coherence rules: every miss is a
    compile event (``misses`` == entries recorded ``source: compiled``),
    every disk hit a deserialize (``disk_hits`` == entries ``source:
    disk``), byte/seconds traffic finite and non-negative, and every
    entry bucket must sit ON the advertised lattice (an off-lattice
    bucket id means the router and the cache disagree about shapes)."""
    errors: List[str] = []
    if not isinstance(serving, dict):
        return [f"{where}: serving is not an object"]
    cache = serving.get("cache")
    if not isinstance(cache, dict):
        return [f"{where}: serving.cache missing — the section's point"]
    counters = cache.get("counters")
    if not isinstance(counters, dict):
        errors.append(f"{where}: serving.cache.counters missing")
        counters = {}
    for key in SERVING_CACHE_COUNTERS:
        v = counters.get(key)
        if not isinstance(v, int) or v < 0:
            errors.append(
                f"{where}: serving.cache.counters.{key} missing or not a "
                "non-negative int"
            )
    for key in (
        "compile_s_paid",
        "compile_s_saved",
        "load_s",
        "bytes_written",
        "bytes_read",
    ):
        v = cache.get(key)
        if not _num(v) or v < 0:
            errors.append(
                f"{where}: serving.cache.{key} missing or negative"
            )
    entries = cache.get("entries")
    if not isinstance(entries, list):
        errors.append(f"{where}: serving.cache.entries missing")
        entries = []
    compiled = disk = 0
    buckets = serving.get("buckets")
    pop_rungs = (buckets or {}).get("pop_rungs") if isinstance(
        buckets, dict
    ) else None
    width_rungs = (buckets or {}).get("width_rungs") if isinstance(
        buckets, dict
    ) else None
    for i, e in enumerate(entries):
        loc = f"{where}: serving.cache.entries[{i}]"
        if not isinstance(e, dict):
            errors.append(f"{loc} is not an object")
            continue
        src = e.get("source")
        if src not in SERVING_ENTRY_SOURCES:
            errors.append(
                f"{loc}.source {src!r} not in {sorted(SERVING_ENTRY_SOURCES)}"
            )
        # repeat events for one (key, source) aggregate into a single
        # record's `repeats` count (the cache's unbounded-growth guard)
        repeats = e.get("repeats", 1)
        if not isinstance(repeats, int) or repeats < 1:
            errors.append(f"{loc}.repeats {repeats!r} is not a positive int")
            repeats = 1
        compiled += (src == "compiled") * repeats
        disk += (src == "disk") * repeats
        b = e.get("bucket")
        if b is not None:
            if (
                not isinstance(b, list)
                or len(b) != 3
                or not all(isinstance(x, int) and x > 0 for x in b)
            ):
                errors.append(
                    f"{loc}.bucket {b!r} is not a [pop, dim, width] triple"
                )
            elif pop_rungs is not None and width_rungs is not None:
                pop, _, width = b
                if pop not in pop_rungs or width not in width_rungs:
                    errors.append(
                        f"{loc}.bucket {b} is off the advertised lattice "
                        f"(pop_rungs={pop_rungs}, width_rungs={width_rungs})"
                        " — router and cache disagree about shapes"
                    )
    # the coherence law: a miss IS a compile event, a disk hit IS a
    # deserialize event — counters that drift from the entry provenance
    # mean the accounting (the leg's whole evidence) is broken
    if isinstance(counters.get("misses"), int) and counters["misses"] != compiled:
        errors.append(
            f"{where}: serving.cache counts {counters['misses']} misses "
            f"but records {compiled} compiled entries — every miss must "
            "be exactly one compile event"
        )
    if isinstance(counters.get("disk_hits"), int) and counters["disk_hits"] != disk:
        errors.append(
            f"{where}: serving.cache counts {counters['disk_hits']} disk "
            f"hits but records {disk} disk-sourced entries"
        )
    if isinstance(buckets, dict):
        for key in ("pop_rungs", "width_rungs"):
            rungs = buckets.get(key)
            if (
                not isinstance(rungs, list)
                or not rungs
                or not all(isinstance(r, int) and r > 0 for r in rungs)
                or rungs != sorted(rungs)
            ):
                errors.append(
                    f"{where}: serving.buckets.{key} is not a sorted "
                    "positive-int list"
                )
    return errors


def _validate_histogram(h: Any, loc: str) -> List[str]:
    """One histogram snapshot: strictly-increasing buckets, cumulative
    counts (non-decreasing across `le`, capped by the +Inf `count`)."""
    errors: List[str] = []
    if not isinstance(h, dict):
        return [f"{loc} is not an object"]
    le = h.get("le")
    counts = h.get("counts")
    if not isinstance(le, list) or not le or le != sorted(le) or len(
        set(le)
    ) != len(le):
        errors.append(f"{loc}.le missing or not strictly increasing")
    if not isinstance(counts, list) or (
        isinstance(le, list) and len(counts) != len(le)
    ):
        errors.append(f"{loc}.counts missing or length != le")
    elif any(not isinstance(c, int) or c < 0 for c in counts):
        errors.append(f"{loc}.counts not non-negative ints")
    elif any(b < a for a, b in zip(counts, counts[1:])):
        errors.append(f"{loc}.counts not cumulative (a bucket decreased)")
    total = h.get("count")
    if not isinstance(total, int) or total < 0:
        errors.append(f"{loc}.count missing or negative")
    elif isinstance(counts, list) and counts and isinstance(
        counts[-1], int
    ) and counts[-1] > total:
        errors.append(f"{loc}: last bucket exceeds the +Inf count")
    if not _num(h.get("sum")):
        errors.append(f"{loc}.sum missing or non-numeric")
    return errors


def _validate_metrics_section(metrics: Any, where: str) -> List[str]:
    """The ``metrics`` section (schema v11, workflows/flightrec.py
    FlightRecorder.report()): the registry snapshot plus ring/stream
    accounting."""
    errors: List[str] = []
    if not isinstance(metrics, dict):
        return [f"{where}: metrics is not an object"]
    if metrics.get("enabled") is not True:
        errors.append(f"{where}: metrics.enabled missing or not true")
    for key in ("process_id", "process_count", "ring_len", "ring_capacity"):
        v = metrics.get(key)
        if not isinstance(v, int) or v < 0:
            errors.append(
                f"{where}: metrics.{key} missing or not a non-negative int"
            )
    for group in ("counters", "gauges"):
        d = metrics.get(group)
        if not isinstance(d, dict):
            errors.append(f"{where}: metrics.{group} missing")
            continue
        for name, v in d.items():
            if not _num(v) or (group == "counters" and v < 0):
                errors.append(f"{where}: metrics.{group}.{name} non-numeric")
    hists = metrics.get("histograms")
    if not isinstance(hists, dict):
        errors.append(f"{where}: metrics.histograms missing")
    else:
        for name, h in hists.items():
            errors += _validate_histogram(h, f"{where}: metrics.histograms.{name}")
    stream = metrics.get("stream")
    if stream is not None:
        if not isinstance(stream, dict):
            errors.append(f"{where}: metrics.stream is not an object")
        else:
            if not isinstance(stream.get("path"), str):
                errors.append(f"{where}: metrics.stream.path missing")
            for key in ("records", "torn_tail_dropped"):
                v = stream.get(key)
                if not isinstance(v, int) or v < 0:
                    errors.append(
                        f"{where}: metrics.stream.{key} missing or negative"
                    )
            events = stream.get("events")
            if not isinstance(events, dict):
                errors.append(f"{where}: metrics.stream.events missing")
            else:
                for kind in events:
                    if kind not in STREAM_KINDS:
                        errors.append(
                            f"{where}: metrics.stream.events kind {kind!r} "
                            f"not in {sorted(STREAM_KINDS)}"
                        )
    return errors


def _validate_slo_ledger(slo: Any, where: str) -> List[str]:
    """The top-level ``slo`` section (schema v11,
    FlightRecorder.slo_ledger()): all keys present, non-negative, and
    the derived rate arithmetically coherent with its numerator and
    denominator."""
    errors: List[str] = []
    if not isinstance(slo, dict):
        return [f"{where}: slo is not an object"]
    for key in SLO_KEYS:
        v = slo.get(key)
        if not _num(v) or v < 0:
            errors.append(f"{where}: slo.{key} missing or negative")
    if all(_num(slo.get(k)) for k in ("tenant_gens", "elapsed_s", "tenant_gens_per_s")):
        elapsed = max(float(slo["elapsed_s"]), 1e-9)
        expect = float(slo["tenant_gens"]) / elapsed
        got = float(slo["tenant_gens_per_s"])
        if abs(got - expect) > max(0.01 * expect, 0.01):
            errors.append(
                f"{where}: slo.tenant_gens_per_s {got} incoherent with "
                f"tenant_gens/elapsed_s ({expect:.6f})"
            )
    return errors


def validate_metrics_stream(
    records: List[Any], where: str = "metrics_stream"
) -> List[str]:
    """A metrics stream (``metrics.jsonl``, or the merged pod stream):
    known record kinds, exactly the stream schema tag on every record, a
    ``meta`` identity record, counters monotonically non-decreasing
    across samples — with the baseline RESET at ``queue.recover`` events
    (crash recovery replays a rolled-back stretch, so replayed counts
    legally rewind) — and every sample's SLO ledger coherent with both
    its own registry snapshot (exact: one registry, one instant) and any
    ``queue`` context it carries (dominance: the recorder may serve
    several bucket queues)."""
    errors: List[str] = []
    if not records:
        return [f"{where}: empty stream"]
    saw_meta = False
    # per-process counter baselines: merged streams tag each record with
    # its process_id; a single stream is one implicit process
    baselines: dict = {}
    for i, rec in enumerate(records):
        loc = f"{where}: records[{i}]"
        if not isinstance(rec, dict):
            errors.append(f"{loc} is not an object")
            continue
        schema = rec.get("schema")
        if not isinstance(schema, str) or not schema.startswith(
            METRICS_STREAM_SCHEMA_PREFIX
        ):
            errors.append(
                f"{loc}: schema {schema!r} is not a "
                f"'{METRICS_STREAM_SCHEMA_PREFIX}*' tag"
            )
        kind = rec.get("kind")
        if kind not in STREAM_KINDS:
            errors.append(f"{loc}: kind {kind!r} not in {sorted(STREAM_KINDS)}")
            continue
        errors += [f"{loc}: non-finite number at {p}" for p in find_nonfinite(rec)]
        proc = rec.get("process_id") if kind != "meta" else None
        if kind == "meta":
            saw_meta = True
            for key in ("process_id", "process_count"):
                if not isinstance(rec.get(key), int):
                    errors.append(f"{loc}: meta.{key} missing")
            continue
        if not _num(rec.get("tm")) or rec["tm"] < 0:
            errors.append(f"{loc}: tm missing/negative")
        if kind == "event":
            if not isinstance(rec.get("name"), str):
                errors.append(f"{loc}: event name missing")
            elif rec["name"] == "queue.recover":
                # the recovered process re-counts the replayed stretch
                # from the restored sample (or zero): every counter may
                # rewind past samples the crash rolled back
                baselines[proc] = {}
            continue
        if kind == "barrier":
            if not isinstance(rec.get("name"), str):
                errors.append(f"{loc}: barrier name missing")
            if not _num(rec.get("t_wall")):
                errors.append(f"{loc}: barrier t_wall missing")
            continue
        # kind == "sample"
        counters = rec.get("counters")
        if not isinstance(counters, dict):
            errors.append(f"{loc}: sample.counters missing")
            continue
        base = baselines.setdefault(proc, {})
        for name, v in counters.items():
            if not _num(v) or v < 0:
                errors.append(f"{loc}: counter {name!r} non-numeric/negative")
                continue
            if v < base.get(name, 0):
                errors.append(
                    f"{loc}: counter {name!r} decreased ({base[name]} -> "
                    f"{v}) with no queue.recover between samples"
                )
            base[name] = v
        for name, h in (rec.get("histograms") or {}).items():
            errors += _validate_histogram(h, f"{loc}: histograms.{name}")
        slo = rec.get("slo")
        if slo is not None:
            errors += _validate_slo_ledger(slo, loc)
            if isinstance(slo, dict):
                for short, name in (
                    ("tenant_gens", "slo.tenant_gens"),
                    ("admissions", "slo.admissions"),
                    ("preemptions", "slo.preemptions"),
                    ("deadline_hits", "slo.deadline_hits"),
                    ("deadline_misses", "slo.deadline_misses"),
                ):
                    if _num(slo.get(short)) and slo[short] != counters.get(
                        name, 0
                    ):
                        errors.append(
                            f"{loc}: slo.{short} {slo[short]} disagrees "
                            f"with counter {name} {counters.get(name, 0)}"
                        )
        queue = rec.get("queue")
        if isinstance(queue, dict) and isinstance(slo, dict):
            for short, qkey in (
                ("admissions", "admitted"),
                ("preemptions", "preempted"),
            ):
                if (
                    _num(slo.get(short))
                    and _num(queue.get(qkey))
                    and slo[short] < queue[qkey]
                ):
                    errors.append(
                        f"{loc}: slo.{short} {slo[short]} < queue.{qkey} "
                        f"{queue[qkey]}"
                    )
    if not saw_meta:
        errors.append(f"{where}: no meta record — stream lacks its identity")
    return errors


def validate_chrome_trace(trace: Any, where: str = "trace") -> List[str]:
    errors: List[str] = []
    if not isinstance(trace, dict) or not isinstance(
        trace.get("traceEvents"), list
    ):
        return [f"{where}: no traceEvents array"]
    errors += [f"{where}: non-finite number at {p}" for p in find_nonfinite(trace)]
    counters_last_ts: dict = {}
    for i, ev in enumerate(trace["traceEvents"]):
        loc = f"{where}: traceEvents[{i}]"
        ph = ev.get("ph")
        if ph not in {"X", "B", "E", "C", "M", "i", "I"}:
            errors.append(f"{loc}: unknown phase {ph!r}")
            continue
        if ph == "M":
            continue
        if not _num(ev.get("ts")) or ev["ts"] < 0:
            errors.append(f"{loc}: ts missing/negative")
            continue
        if ph == "X" and (not _num(ev.get("dur")) or ev["dur"] < 0):
            errors.append(f"{loc}: X event dur missing/negative")
        if ev.get("cat") == "supervisor":
            # supervisor decisions are POINTS in time, not spans — the
            # exporter must emit them as instant markers
            if ph not in {"i", "I"}:
                errors.append(
                    f"{loc}: supervisor event {ev.get('name')!r} must be an "
                    f"instant marker (ph 'i'), got ph {ph!r}"
                )
            name = ev.get("name") or ""
            if not str(name).startswith("supervisor:"):
                errors.append(
                    f"{loc}: supervisor marker name {name!r} must start "
                    "with 'supervisor:'"
                )
            elif str(name).startswith("supervisor:pod:"):
                # pod chaos markers (schema v9): the kind after the
                # prefix must be a known pod event
                kind = str(name)[len("supervisor:pod:"):]
                if kind not in POD_EVENTS:
                    errors.append(
                        f"{loc}: pod marker kind {kind!r} not in "
                        f"{sorted(POD_EVENTS)}"
                    )
        if ph == "C":
            key = (ev.get("pid"), ev.get("name"))
            if ev["ts"] < counters_last_ts.get(key, float("-inf")):
                errors.append(
                    f"{loc}: counter track {ev.get('name')!r} ts not "
                    "monotonic"
                )
            counters_last_ts[key] = ev["ts"]
    return errors


def _strict_loads(line: str) -> Any:
    # strict: bare NaN/Infinity tokens must fail, exactly as they would
    # in jq / JSON.parse
    return json.loads(
        line, parse_constant=lambda c: (_ for _ in ()).throw(
            ValueError(f"non-strict JSON constant {c}")
        )
    )


def _sniff_stream_jsonl(path: str) -> bool:
    """True when a .jsonl file's first record carries the metrics-stream
    schema tag — the dispatch key between run-report lines and a
    FlightRecorder stream."""
    try:
        with open(path) as f:
            for line in f:
                if not line.strip():
                    continue
                obj = json.loads(line)
                return isinstance(obj, dict) and str(
                    obj.get("schema", "")
                ).startswith(METRICS_STREAM_SCHEMA_PREFIX)
    except ValueError:
        pass
    return False


def validate_file(path: str) -> List[str]:
    if path.endswith(".jsonl"):
        errors: List[str] = []
        if _sniff_stream_jsonl(path):
            records: List[Any] = []
            lines = open(path).read().split("\n")
            nonempty = [
                (i + 1, ln) for i, ln in enumerate(lines) if ln.strip()
            ]
            for pos, (lineno, line) in enumerate(nonempty):
                try:
                    records.append(_strict_loads(line))
                except ValueError as e:
                    if pos == len(nonempty) - 1:
                        # a torn TAIL is the expected crash artifact —
                        # adoption truncates it; the validator tolerates
                        # it (the chain above it is still judged)
                        continue
                    errors.append(f"{path}:{lineno}: {e}")
            errors += [
                f"{path}: {e}"
                for e in validate_metrics_stream(records, where="stream")
            ]
            return errors
        with open(path) as f:
            for lineno, line in enumerate(f, start=1):
                if not line.strip():
                    continue
                try:
                    obj = _strict_loads(line)
                except ValueError as e:
                    errors.append(f"{path}:{lineno}: {e}")
                    continue
                errors += [
                    f"{path}:{lineno}: {e}"
                    for e in validate_run_report(obj, where="run_report")
                ]
        return errors
    with open(path) as f:
        try:
            obj = json.load(f)
        except ValueError as e:
            return [f"{path}: invalid JSON: {e}"]
    if isinstance(obj, dict) and "traceEvents" in obj:
        errors = validate_chrome_trace(obj)
    else:
        errors = validate_run_report(obj)
    return [f"{path}: {e}" for e in errors]


#: every schema surface this validator understands — what ``--schema``
#: prints so drivers/tests can pin it without parsing the module
SUPPORTED_SCHEMAS = (
    RUN_REPORT_SCHEMA,
    "evox_tpu.metrics_stream/v1",
    "chrome trace (traceEvents)",
)


def detect_schema(path: str) -> str:
    """Best-effort schema tag of one file (what validate_file would
    dispatch it as) — the ``--schema`` per-file answer."""
    try:
        if path.endswith(".jsonl"):
            with open(path) as f:
                for line in f:
                    if not line.strip():
                        continue
                    obj = json.loads(line)
                    if isinstance(obj, dict) and isinstance(
                        obj.get("schema"), str
                    ):
                        return obj["schema"]
                    return "unknown (.jsonl, first record has no schema)"
            return "unknown (empty .jsonl)"
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError) as e:
        return f"unreadable ({e})"
    if isinstance(obj, dict):
        if "traceEvents" in obj:
            return "chrome trace"
        if isinstance(obj.get("schema"), str):
            return obj["schema"]
    return "unknown"


def main(argv: List[str]) -> int:
    if "--schema" in argv:
        paths = [a for a in argv if a != "--schema"]
        if not paths:
            for s in SUPPORTED_SCHEMAS:
                print(s)
            return 0
        for path in paths:
            print(f"{path}: {detect_schema(path)}")
        return 0
    if not argv:
        print(__doc__)
        return 2
    failed = False
    for path in argv:
        errors = validate_file(path)
        if errors:
            failed = True
            for e in errors:
                print(e)
        else:
            print(f"{path}: OK")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
