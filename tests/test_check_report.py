"""tools/check_report.py — the report-shape gate: run_report(), the
metrics stream and the Chrome trace must stay valid against the schema
validator, and the validator must actually catch the regressions it
exists for (missing keys, non-strict JSON numbers, a report of another
version than the one the program emits)."""

import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp

from evox_tpu import CostAnalyzer, StdWorkflow, instrument, run_report
from evox_tpu.algorithms.so.es import CMAES
from evox_tpu.monitors import TelemetryMonitor
from evox_tpu.problems.numerical import Sphere

REPO = pathlib.Path(__file__).resolve().parent.parent

_spec = importlib.util.spec_from_file_location(
    "check_report", REPO / "tools" / "check_report.py"
)
check_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_report)

#: the stamp every hand-built report below carries: the one version the
#: program emits and the validator takes
_STAMP = {
    "schema": check_report.RUN_REPORT_SCHEMA,
    "schema_version": check_report.RUN_REPORT_SCHEMA_VERSION,
}


def _fresh_report(analyze):
    tm = TelemetryMonitor(capacity=8)
    wf = StdWorkflow(
        CMAES(center_init=jnp.zeros(4), init_stdev=1.0, pop_size=8),
        Sphere(),
        monitors=(tm,),
    )
    rec = instrument(wf)
    state = wf.init(jax.random.PRNGKey(0))
    state = wf.run(state, 4)
    # the CPU has no entry in CHIP_CEILINGS (no default peak exists):
    # the analyzer is handed stand-in peaks
    analyzer = (
        CostAnalyzer(ceilings={"mxu_bf16_tflops": 1.0, "hbm_gbps": 10.0})
        if analyze
        else None
    )
    return run_report(wf, state, recorder=rec, analyzer=analyzer)


def test_fresh_run_report_validates():
    for analyze in (False, True):
        report = _fresh_report(analyze)
        assert check_report.validate_run_report(report) == [], analyze


def test_validator_catches_regressions():
    report = _fresh_report(True)
    bad = json.loads(json.dumps(report))
    del bad["schema"]
    bad["dispatch"]["entry_points"]["step"]["calls"] = None
    bad["roofline"]["entries"]["step"]["classification"] = "gpu-bound"
    bad["telemetry"][0]["best_fitness"] = float("nan")
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "schema" in errors
    assert "step.calls" in errors
    assert "classification" in errors
    assert "non-finite" in errors


def test_validator_sharding_subsection_rules():
    """v5 roofline.sharding (PR 10): a well-formed gather-free section
    passes; per-device peak >= full-pop bytes (a gathered step), a denied
    gather_free flag, or missing fields fail."""
    report = _fresh_report(True)
    good = json.loads(json.dumps(report))
    good["roofline"]["sharding"] = {
        "axis": "pop",
        "n_devices": 8,
        "pop_size": 1 << 15,
        "entry": "step",
        "per_device_peak_bytes": 5_000_000,
        "full_pop_bytes": 8_388_608,
        "gather_free": True,
    }
    assert check_report.validate_run_report(good) == []
    bad = json.loads(json.dumps(good))
    bad["roofline"]["sharding"]["per_device_peak_bytes"] = 9_000_000
    bad["roofline"]["sharding"]["gather_free"] = False
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "not gather-free" in errors and "gather_free" in errors
    bad2 = json.loads(json.dumps(good))
    del bad2["roofline"]["sharding"]["n_devices"]
    assert any(
        "sharding.n_devices" in e
        for e in check_report.validate_run_report(bad2)
    )


def test_validator_cli_detects_jsonl(tmp_path):
    good = _fresh_report(False)
    p = tmp_path / "runs.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write('{"schema": "evox_tpu.run_report/v14", "x": NaN}\n')
    errors = check_report.validate_file(str(p))
    assert len(errors) == 1 and "runs.jsonl:2" in errors[0]
    assert check_report.main([str(p)]) == 1
    ok = tmp_path / "ok.jsonl"
    ok.write_text(json.dumps(good) + "\n")
    assert check_report.main([str(ok)]) == 0


def _serving_tenancy():
    """A well-formed v6 serving tenancy section: journaled queue with
    its WAL counters plus a fleet_health action log — the shape
    RunQueue.report()/health_report() emit after a journaled sweep."""
    return {
        "n_tenants": 2,
        "leading_axes": [2],
        "per_tenant": [{"tenant": 0}, {"tenant": 1}],
        "queue": {
            "capacity": 2,
            "chunk": 3,
            "counters": {
                "submitted": 3,
                "admitted": 3,
                "retired": 2,
                "evicted": 1,
            },
            "results": [
                {"tag": "a", "status": "completed", "generations": 5},
                {
                    "tag": "b",
                    "status": "evicted",
                    "generations": 3,
                    "checkpoint": "/tmp/ckpts/b",
                },
            ],
            "journal": {
                "path": "/tmp/journal/journal.jsonl",
                "records": 11,
                "last_seq": 10,
                "events": {
                    "submit": 3,
                    "start": 1,
                    "admit": 3,
                    "chunk_complete": 2,
                    "retire": 1,
                    "evict": 1,
                },
                "recovered": False,
                "torn_tail_dropped": 0,
            },
        },
        "fleet_health": {
            "policy": {
                "on_nonfinite": "evict",
                "on_trigger": None,
                "stagnation_limit": None,
                "on_stagnation": "restart",
                "max_restarts_per_slot": 2,
            },
            "events": [
                {
                    "health_seq": 0,
                    "chunk": 1,
                    "slot": 1,
                    "tag": "b",
                    "action": "evict",
                    "reason": "nonfinite_state",
                    "generation": 3,
                }
            ],
        },
    }


def test_validator_v6_serving_sections_pass():
    report = _fresh_report(False)
    report["tenancy"] = _serving_tenancy()
    assert check_report.validate_run_report(report) == []


def test_validator_v6_journal_rules():
    """The WAL counters must be known kinds summing to the ledger total
    (monotonicity), and the recovered flag must agree with the recover
    event count."""
    report = _fresh_report(False)
    report["tenancy"] = _serving_tenancy()
    journal = report["tenancy"]["queue"]["journal"]
    journal["events"]["reticulate"] = 1
    journal["events"]["submit"] = 5  # sum 14 != records 11
    journal["recovered"] = True  # but no recover event
    journal["last_seq"] = 3  # != records - 1
    errors = "\n".join(check_report.validate_run_report(report))
    assert "unknown kind 'reticulate'" in errors
    assert "not monotonic with the ledger" in errors
    assert "incoherent with its recover event count" in errors
    assert "last_seq" in errors


def test_validator_v6_fleet_health_rules():
    """Every health event must name a real slot and a known action, in
    chunk order."""
    report = _fresh_report(False)
    report["tenancy"] = _serving_tenancy()
    events = report["tenancy"]["fleet_health"]["events"]
    events.append(
        {
            "health_seq": 1,
            "chunk": 0,  # decreasing vs the seeded chunk-1 event
            "slot": 7,  # out of range for n_tenants=2
            "action": "defenestrate",
            "reason": "because",
            "generation": 4,
        }
    )
    errors = "\n".join(check_report.validate_run_report(report))
    assert "events[1].action" in errors
    assert "events[1].slot" in errors
    assert "chunk not non-decreasing" in errors


def test_validator_v6_journaled_evict_needs_checkpoint():
    """A journaled eviction's whole point is the resumable artifact: an
    evicted/frozen result without a checkpoint path is rejected — but
    only under a journal (plain queues may run checkpoint-less)."""
    report = _fresh_report(False)
    report["tenancy"] = _serving_tenancy()
    del report["tenancy"]["queue"]["results"][1]["checkpoint"]
    errors = "\n".join(check_report.validate_run_report(report))
    assert "names no checkpoint path" in errors
    # checkpoint-less evictions are fine on an unjournaled queue
    del report["tenancy"]["queue"]["journal"]
    assert check_report.validate_run_report(report) == []


def test_validator_multihost_subsection_rules():
    """v8 roofline.multihost (ISSUE 13): a well-formed pod section
    passes; an incoherent per-process/per-device product, a per-device
    peak at/above full-pop bytes, or missing fields fail."""
    report = _fresh_report(True)
    good = json.loads(json.dumps(report))
    good["roofline"]["multihost"] = {
        "process_count": 2,
        "n_local_devices": 4,
        "entry": "step",
        "per_device_peak_bytes": 5_000_000,
        "per_process_peak_bytes": 20_000_000,
        "full_pop_bytes": 8_388_608,
        "collective_bytes_estimate": 300_000,
        "collective_model": "2*pop*4 + psum moment tree",
    }
    assert check_report.validate_run_report(good) == []
    bad = json.loads(json.dumps(good))
    bad["roofline"]["multihost"]["per_process_peak_bytes"] = 19_999_999
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "per_process_peak_bytes" in errors and "!=" in errors
    bad2 = json.loads(json.dumps(good))
    bad2["roofline"]["multihost"]["per_device_peak_bytes"] = 9_000_000
    bad2["roofline"]["multihost"]["per_process_peak_bytes"] = 36_000_000
    errors = "\n".join(check_report.validate_run_report(bad2))
    assert "materializes the full population" in errors
    bad3 = json.loads(json.dumps(good))
    del bad3["roofline"]["multihost"]["process_count"]
    assert any(
        "multihost.process_count" in e
        for e in check_report.validate_run_report(bad3)
    )


def _pod_section():
    """A coherent failed-pod section (the worker-dead shape)."""
    return {
        "process_id": 0,
        "process_count": 2,
        "epoch": 0,
        "deadline_s": 5.0,
        "heartbeat_interval_s": 0.2,
        "outcome": "failed",
        "counters": {
            "heartbeats": 40,
            "censuses": 1,
            "barriers": 3,
            "barrier_timeouts": 1,
            "supervised_calls": 2,
            "failures": 1,
            "drains": 0,
            "reforms": 0,
            "resumes": 0,
        },
        "events": [
            {"t": 0.0, "event": "join", "process_id": 0,
             "process_count": 2, "epoch": 0},
            {"t": 5.1, "event": "barrier_timeout",
             "name": "evox_tpu/pod/e0/gen4", "missing": [1], "arrived": [0]},
            {"t": 5.8, "event": "census", "alive": [0], "dead": [1]},
            {"t": 5.9, "event": "failure", "entry": "barrier:gen4",
             "classification": "worker_dead", "detect_s": 5.9,
             "error": "BarrierTimeoutError: ..."},
        ],
    }


def test_validator_pod_supervisor_rules():
    """v9 pod_supervisor (ISSUE 14): a coherent failed section passes;
    unknown event kinds, unknown classifications, a GROWING census, and
    reform-without-resume incoherence all fail."""
    report = _fresh_report(False)
    good = json.loads(json.dumps(report))
    good["pod_supervisor"] = _pod_section()
    assert check_report.validate_run_report(good) == []

    bad = json.loads(json.dumps(good))
    bad["pod_supervisor"]["events"][1]["event"] = "heartbeat_missed"
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "heartbeat_missed" in errors

    bad = json.loads(json.dumps(good))
    bad["pod_supervisor"]["events"][3]["classification"] = "gremlins"
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "gremlins" in errors

    bad = json.loads(json.dumps(good))
    bad["pod_supervisor"]["events"].append(
        {"t": 6.0, "event": "census", "alive": [0, 1], "dead": []}
    )
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "grew" in errors and "monotonic" in errors

    bad = json.loads(json.dumps(good))
    bad["pod_supervisor"]["outcome"] = "exploded"
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "exploded" in errors


def test_validator_pod_reform_resume_coherence():
    """A reform without its completing resume (or a 'resumed' outcome
    without a resume event) is the half-healed pod the validator must
    reject; the full reform→resume pair passes."""
    report = _fresh_report(False)
    good = json.loads(json.dumps(report))
    pod = _pod_section()
    pod["outcome"] = "resumed"
    pod["counters"]["reforms"] = 1
    pod["counters"]["resumes"] = 1
    pod["events"] = [
        {"t": 0.0, "event": "join", "process_id": 0,
         "process_count": 1, "epoch": 1},
        {"t": 0.1, "event": "reform", "survivors": [0], "from_epoch": 0},
        {"t": 2.0, "event": "resume", "generation": 4},
    ]
    good["pod_supervisor"] = pod
    assert check_report.validate_run_report(good) == []

    bad = json.loads(json.dumps(good))
    bad["pod_supervisor"]["events"] = bad["pod_supervisor"]["events"][:2]
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "reform but no resume" in errors
    assert "'resumed' without a resume event" in errors

    bad = json.loads(json.dumps(good))
    bad["pod_supervisor"]["events"][2]["generation"] = -3
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "generation missing/negative" in errors


def test_validator_pod_trace_markers():
    """Chrome-trace rule: supervisor:pod:* markers must be instants
    with a KNOWN pod event kind after the prefix."""
    trace = {
        "traceEvents": [
            {"ph": "i", "cat": "supervisor", "pid": 5, "tid": 1,
             "ts": 1.0, "name": "supervisor:pod:failure", "s": "p"},
        ]
    }
    assert check_report.validate_chrome_trace(trace) == []
    trace["traceEvents"].append(
        {"ph": "i", "cat": "supervisor", "pid": 5, "tid": 1,
         "ts": 2.0, "name": "supervisor:pod:kaboom", "s": "p"}
    )
    errors = "\n".join(check_report.validate_chrome_trace(trace))
    assert "kaboom" in errors

    trace = {
        "traceEvents": [
            {"ph": "X", "cat": "supervisor", "pid": 5, "tid": 1,
             "ts": 1.0, "dur": 2.0, "name": "supervisor:pod:failure"},
        ]
    }
    errors = "\n".join(check_report.validate_chrome_trace(trace))
    assert "instant marker" in errors


def test_validator_journal_pod_kinds():
    """The WAL validator accepts the pod membership kinds (v9) and
    still rejects unknown ones."""
    journal = {
        "path": "j/journal.jsonl",
        "records": 3,
        "last_seq": 2,
        "events": {"pod_join": 1, "pod_failure": 1, "pod_resume": 1},
        "recovered": False,
        "torn_tail_dropped": 0,
    }
    assert check_report._validate_journal(journal, "t") == []
    journal["events"] = {"pod_join": 2, "pod_detonate": 1}
    errors = "\n".join(check_report._validate_journal(journal, "t"))
    assert "pod_detonate" in errors


def _surrogate_section():
    return {
        "enabled": True,
        "model": "gp",
        "screen_frac": 0.125,
        "archive": {"capacity": 256, "fill": 128, "writes": 128},
        "refit": {
            "count": 8,
            "every": 1,
            "last_generation": 8,
            "max_staleness_gens": 1,
        },
        "counters": {
            "candidates_seen": 512,
            "true_evals": 128,
            "screened_out": 384,
            "generations": 8,
            "screened_gens": 6,
            "fallback_gens": 1,
            "warmup_gens": 1,
        },
        "health": {
            "rank_floor": 0.3,
            "unc_ceiling": None,
            "last_rank_corr": 0.9,
            "last_uncertainty": 0.1,
            "fallback_armed": False,
        },
        "fallback_events": [{"generation": 5, "reason": 1}],
    }


def test_validator_v10_surrogate_section_rules():
    """The v10 surrogate section: a coherent ledger passes; a broken
    counter sum, an over-full archive, out-of-order events, and unknown
    reason bits all fail loudly."""
    good = {**_STAMP, "surrogate": _surrogate_section()}
    assert check_report.validate_run_report(good) == []
    # disabled sections stay minimal and valid
    assert check_report.validate_run_report(
        {
            **_STAMP,
            "surrogate": {"enabled": False, "model": None, "screen_frac": 1.0},
        }
    ) == []

    bad = json.loads(json.dumps(good))
    bad["surrogate"]["counters"]["screened_out"] = 1
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "candidates_seen" in errors

    bad = json.loads(json.dumps(good))
    bad["surrogate"]["counters"]["warmup_gens"] = 5
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "partition" in errors

    bad = json.loads(json.dumps(good))
    bad["surrogate"]["archive"]["fill"] = 400
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "capacity" in errors

    bad = json.loads(json.dumps(good))
    bad["surrogate"]["fallback_events"] = [
        {"generation": 5, "reason": 1},
        {"generation": 3, "reason": 2},
    ]
    bad["surrogate"]["counters"]["fallback_gens"] = 2
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "chronological" in errors

    bad = json.loads(json.dumps(good))
    bad["surrogate"]["fallback_events"][0]["reason"] = 8
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "bitmask" in errors

    bad = json.loads(json.dumps(good))
    bad["surrogate"]["fallback_events"] = [
        {"generation": 2, "reason": 1},
        {"generation": 5, "reason": 1},
    ]  # two events but only 1 fallback generation counted
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "fallback" in errors


# ------------------------------------------------ v11 metrics plane (PR 16)


def test_validator_v11_schema_version_rules():
    """A report carries the schema tag and the schema_version int of the
    one version the program emits; any other version, in either place,
    is refused."""
    report = _fresh_report(False)
    assert report["schema"] == "evox_tpu.run_report/v14"
    assert report["schema_version"] == 14
    bad = json.loads(json.dumps(report))
    del bad["schema_version"]
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "schema_version" in errors
    bad = json.loads(json.dumps(report))
    bad["schema_version"] = 10
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "disagrees" in errors
    # a report of a version other than the one the program emits is
    # refused, whether it is older, newer or agrees with itself
    assert check_report.RUN_REPORT_SCHEMA == report["schema"]
    for version in (1, 10, 13, 15):
        other = json.loads(json.dumps(report))
        other["schema"] = f"evox_tpu.run_report/v{version}"
        other["schema_version"] = version
        errors = "\n".join(check_report.validate_run_report(other))
        assert "the version the program emits" in errors, version
        assert "disagrees" in errors, version
    # an old capture's shape (no int, no roofline provenance) gets no
    # exemption either
    old = {"schema": "evox_tpu.run_report/v1", "roofline": {
        "ceilings": {"mxu_bf16_tflops": 1.0, "hbm_gbps": 1.0},
        "entries": {"step": {"static": {}, "classification": None}},
    }}
    errors = "\n".join(check_report.validate_run_report(old))
    assert "schema_version missing" in errors
    assert "roofline.dtype_policy missing" in errors
    assert "roofline.donation missing" in errors


def _metrics_report():
    """A minimal v11 report with live metrics + slo sections, built from
    a real FlightRecorder (the shape run_report(metrics=...) emits)."""
    from evox_tpu import FlightRecorder

    fr = FlightRecorder()
    fr.count("slo.tenant_gens", 40)
    fr.count("slo.admissions", 4)
    fr.set("queue.pending", 2)
    fr.observe("dispatch.ms", 12.0)
    return run_report(metrics=fr)


def test_validator_v11_metrics_and_slo_rules():
    report = _metrics_report()
    assert check_report.validate_run_report(report) == []

    bad = json.loads(json.dumps(report))
    bad["metrics"]["enabled"] = False
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "metrics.enabled" in errors

    bad = json.loads(json.dumps(report))
    bad["metrics"]["counters"]["slo.tenant_gens"] = -1
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "slo.tenant_gens" in errors

    bad = json.loads(json.dumps(report))
    bad["metrics"]["histograms"]["dispatch.ms"]["counts"] = [99]
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "histograms.dispatch.ms" in errors

    # the slo ledger and the registry counters come from one registry:
    # a disagreement is corruption, not rounding
    bad = json.loads(json.dumps(report))
    bad["slo"]["admissions"] = 9
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "disagree" in errors or "admissions" in errors

    bad = json.loads(json.dumps(report))
    bad["slo"]["tenant_gens_per_s"] = 1e9
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "incoherent" in errors


def _stream_rec(kind, **fields):
    return {
        "schema": "evox_tpu.metrics_stream/v1",
        "kind": kind,
        "tm": fields.pop("tm", 0.5),
        **fields,
    }


def _stream_sample(gens, tm=0.5, **extra):
    slo = {
        "tenant_gens": gens,
        "elapsed_s": 10.0,
        "tenant_gens_per_s": gens / 10.0,
        "admissions": extra.pop("admissions", 0),
        "preemptions": 0,
        "deadline_hits": 0,
        "deadline_misses": 0,
    }
    counters = {
        "slo.tenant_gens": gens,
        "slo.admissions": slo["admissions"],
    }
    return _stream_rec(
        "sample", tm=tm, counters=counters, slo=slo, **extra
    )


def _stream_meta():
    rec = _stream_rec("meta", process_id=0, process_count=1, pid_base=0)
    del rec["tm"]
    return rec


def test_validator_metrics_stream_rules():
    good = [_stream_meta(), _stream_sample(12), _stream_sample(24, tm=1.0)]
    assert check_report.validate_metrics_stream(good) == []

    # counters are monotone across samples...
    dec = [_stream_meta(), _stream_sample(24), _stream_sample(12, tm=1.0)]
    errors = "\n".join(check_report.validate_metrics_stream(dec))
    assert "decreased" in errors

    # ...except across a queue.recover baseline reset (crash replay)
    healed = [
        _stream_meta(),
        _stream_sample(24),
        _stream_rec("event", name="queue.recover", tm=0.9),
        _stream_sample(12, tm=1.0),
    ]
    assert check_report.validate_metrics_stream(healed) == []

    # the ledger must agree with the registry snapshot it rode in on
    lying = [_stream_meta(), _stream_sample(12)]
    lying[1]["slo"]["tenant_gens"] = 99
    errors = "\n".join(check_report.validate_metrics_stream(lying))
    assert "disagrees" in errors

    # ...and dominate any queue context it carries
    starved = [
        _stream_meta(),
        _stream_sample(12, admissions=1, queue={"admitted": 3}),
    ]
    errors = "\n".join(check_report.validate_metrics_stream(starved))
    assert "queue.admitted" in errors

    unknown = [_stream_meta(), _stream_rec("vibe", name="x")]
    errors = "\n".join(check_report.validate_metrics_stream(unknown))
    assert "kind" in errors

    anonymous = [_stream_sample(12)]
    errors = "\n".join(check_report.validate_metrics_stream(anonymous))
    assert "identity" in errors


def test_validate_file_sniffs_metrics_stream(tmp_path):
    """validate_file dispatches a metrics .jsonl to the stream
    validator and tolerates ONLY a torn FINAL line — the one artifact a
    crash mid-append can leave."""
    from evox_tpu import FlightRecorder

    fr = FlightRecorder(directory=str(tmp_path))
    for g in (2, 4):
        fr.count("slo.tenant_gens", 8)
        fr.sample(generation=g)
    path = fr.stream.path
    assert check_report.validate_file(str(path)) == []
    with open(path, "ab") as f:
        f.write(b'{"kind": "sample", "tm"')  # the crash artifact
    assert check_report.validate_file(str(path)) == []
    with open(path, "ab") as f:
        f.write(b'\n{"kind": "event"}\n')  # torn line NOT final: corrupt
    assert check_report.validate_file(str(path)) != []


def test_schema_flag_lists_and_detects(tmp_path, capsys):
    assert check_report.main(["--schema"]) == 0
    out = capsys.readouterr().out
    assert out.splitlines() == [
        "evox_tpu.run_report/v14",
        "evox_tpu.metrics_stream/v1",
        "chrome trace (traceEvents)",
    ]
    from evox_tpu import FlightRecorder

    fr = FlightRecorder(directory=str(tmp_path))
    fr.sample(generation=1)
    assert check_report.main(["--schema", str(fr.stream.path)]) == 0
    out = capsys.readouterr().out
    assert "evox_tpu.metrics_stream/v1" in out


# ------------------------------------------------ v12: control plane rules


def _control_plane_section():
    return {
        "pods": {
            "opened": 2,
            "live": ["pod01"],
            "dead": ["pod00"],
            "closed": [],
            "draining": [],
        },
        "tenants": {
            "submitted": 3,
            "placed": 3,
            "stolen": 1,
            "steal_dedup": 0,
            "results": 3,
        },
        "events": {
            "submit": 3,
            "place": 3,
            "steal": 1,
            "pod_open": 2,
            "pod_dead": 1,
        },
        "ledger": {"records": 10, "rotations": 0, "recoveries": 1},
        "exactly_once": {"audited_tags": 3, "duplicate_admissions": {}},
        "steals": [
            {
                "tag": "t0",
                "from_pod": "pod00",
                "to_pod": "pod01",
                "bucket": "pop8_dim4_w2",
                "checkpoint": None,
            }
        ],
        "autoscale": {"policy": None, "events": []},
    }


def test_validator_v12_control_plane_rules():
    report = {**_STAMP, "control_plane": _control_plane_section()}
    assert check_report.validate_run_report(report) == []

    # ANY duplicate admission is a violated law, not a warning
    bad = json.loads(json.dumps(report))
    bad["control_plane"]["exactly_once"]["duplicate_admissions"] = {
        "t0": 2
    }
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "admitted twice" in errors

    # ledger-vs-counter coherence: a stolen counter the WAL never saw
    bad = json.loads(json.dumps(report))
    bad["control_plane"]["tenants"]["stolen"] = 2
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "disagrees with ledger steal" in errors

    # the census must be disjoint, and only live pods drain
    bad = json.loads(json.dumps(report))
    bad["control_plane"]["pods"]["closed"] = ["pod00"]
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "both dead and closed" in errors
    bad = json.loads(json.dumps(report))
    bad["control_plane"]["pods"]["draining"] = ["pod00"]
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "only a live pod can drain" in errors

    # the kind histogram must cover the ledger exactly, with known kinds
    bad = json.loads(json.dumps(report))
    bad["control_plane"]["events"]["submit"] = 4
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "sum" in errors and "ledger.records" in errors
    bad = json.loads(json.dumps(report))
    bad["control_plane"]["events"]["vanish"] = 0
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "unknown ledger kind" in errors

    # a steal that moved nothing, and a steal stream out of step with
    # its counter
    bad = json.loads(json.dumps(report))
    bad["control_plane"]["steals"][0]["to_pod"] = "pod00"
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "moved nothing" in errors
    bad = json.loads(json.dumps(report))
    bad["control_plane"]["steals"] = []
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "tenants.stolen" in errors


# ---------------------------------------------------------------- v13


def _search_section():
    """Minimal coherent v13 ``search`` section (ISSUE 19): 3 gens × 2
    slots, gen 0 credited to init, one restart-free epoch."""
    return {
        "enabled": True,
        "generations": 3,
        "capacity": 4,
        "width": 2,
        "num_objectives": 1,
        "epoch": 0,
        "restarts": 0,
        "ledger": {
            "init": {"attempts": 2, "successes": 2, "improvement": 1.0},
            "de_rand_1": {"attempts": 4, "successes": 1, "improvement": 0.5},
        },
        "ancestry": [
            {"generation": 3, "slot": 0, "parent": 1, "op": "de_rand_1", "epoch": 0},
            {"generation": 2, "slot": 1, "parent": 0, "op": "de_rand_1", "epoch": 0},
            {"generation": 1, "slot": 0, "parent": 0, "op": "init", "epoch": 0},
        ],
        "age": {"max": 2, "mean": 1.0},
        "trajectory": {
            "generation": [1, 2, 3],
            "best_slot": [0, 1, 0],
            "best_fitness": [5.0, 3.0, 1.0],
            "delta": [0.0, 2.0, 2.0],
            "epoch": [0, 0, 0],
        },
    }


def test_validator_v13_search_section_rules():
    base = _fresh_report(False)
    base["search"] = _search_section()
    assert check_report.validate_run_report(base) == []

    # degraded + disabled forms are valid and minimal
    ok = json.loads(json.dumps(base))
    ok["search"] = {"error": "boom"}
    assert check_report.validate_run_report(ok) == []
    ok["search"] = {"enabled": False}
    assert check_report.validate_run_report(ok) == []

    # ledger accounting: attempts must sum to generations*width, a
    # success needs an attempt, operators come from the shared vocabulary
    bad = json.loads(json.dumps(base))
    bad["search"]["ledger"]["de_rand_1"]["attempts"] = 5
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "attempts sum" in errors
    bad = json.loads(json.dumps(base))
    bad["search"]["ledger"]["de_rand_1"]["successes"] = 99
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "cannot succeed without being attempted" in errors
    bad = json.loads(json.dumps(base))
    bad["search"]["ledger"]["warp_drive"] = bad["search"]["ledger"].pop(
        "de_rand_1"
    )
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "not a known operator tag" in errors

    # ancestry: in-range indices, consecutive descent, one epoch
    bad = json.loads(json.dumps(base))
    bad["search"]["ancestry"][0]["slot"] = 7
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "not in [0, width=2)" in errors
    bad = json.loads(json.dumps(base))
    bad["search"]["ancestry"][1]["generation"] = 1
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "descend consecutively" in errors
    bad = json.loads(json.dumps(base))
    bad["search"]["ancestry"][2]["epoch"] = 1
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "across a restart/exploit boundary is fiction" in errors

    # trajectory: delta non-negative, epochs only advance, track lengths
    bad = json.loads(json.dumps(base))
    bad["search"]["trajectory"]["delta"][1] = -0.5
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "delta has negative entries" in errors
    bad = json.loads(json.dumps(base))
    bad["search"]["trajectory"]["epoch"] = [1, 0, 0]
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "epoch decreases" in errors
    bad = json.loads(json.dumps(base))
    bad["search"]["trajectory"]["best_slot"] = [0, 1]
    errors = "\n".join(check_report.validate_run_report(bad))
    assert "length mismatch" in errors

    # MO runs must carry the churn/front-size rings, coherently
    mo = json.loads(json.dumps(base))
    mo["search"]["num_objectives"] = 2
    errors = "\n".join(check_report.validate_run_report(mo))
    assert "front_size" in errors and "churn" in errors
    mo["search"]["trajectory"]["front_size"] = [1, 2, 2]
    mo["search"]["trajectory"]["churn"] = [0.0, 0.1, 0.05]
    assert check_report.validate_run_report(mo) == []
    mo["search"]["trajectory"]["front_size"] = [1, 2, 9]
    errors = "\n".join(check_report.validate_run_report(mo))
    assert "front_size out of" in errors


