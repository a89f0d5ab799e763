"""Telemetry export contracts: registry schema, Prometheus/JSON
round-trips, Chrome trace validity, artifact provenance, and the run
directory as one validated unit.

The export layer is pure host-side code, so these tests drive it with
small synthetic inputs plus ONE real (tiny) simulator run that flows
through ``collect_stream`` -> ``write_run`` -> ``load_run`` ->
``report.render`` end to end.
"""
import dataclasses
import json
import math
import os

import jax
import numpy as np
import pytest

from repro.continuum import (SimConfig, compile_scenario, get_library,
                             make_topology, run_sim_stream)
from repro.obs import (RecorderConfig, provenance, registry, report,
                       runlog, trace)
from repro.obs.registry import Metric, MetricSet

K, M = 8, 3


@pytest.fixture(scope="module")
def storm_out():
    cfg = SimConfig(horizon=8.0, tau=0.150, attempt_timeout=0.090,
                    max_retries=2, retry_backoff=0.002,
                    breaker_threshold=5, breaker_cooldown=1.0,
                    recorder=RecorderConfig(capacity=512))
    rtt = make_topology(jax.random.PRNGKey(1), K, M).lb_instance_rtt()
    drv = compile_scenario(get_library(cfg.horizon, K, M)["retry_storm"],
                           cfg, jax.random.PRNGKey(7))
    out = run_sim_stream("qedgeproxy", rtt, cfg, jax.random.PRNGKey(11),
                         drivers=drv, warmup_steps=20)
    return cfg, out


# -- registry ----------------------------------------------------------

def test_metric_validation():
    with pytest.raises(ValueError, match="kind"):
        Metric("x", 1.0, kind="histogram")
    with pytest.raises(ValueError, match="name"):
        Metric("2bad", 1.0)
    with pytest.raises(ValueError, match="label"):
        Metric("ok", 1.0, labels={"bad-label": "v"})
    ms = MetricSet()
    ms.add("repro_x", 1.0, instance="0")
    ms.add("repro_x", 2.0, instance="1")    # same name, new labels: fine
    with pytest.raises(ValueError, match="duplicate"):
        ms.add("repro_x", 3.0, instance="0")


def test_json_round_trip_preserves_nan():
    ms = MetricSet()
    ms.add("repro_a", float("nan"), help="a nan gauge")
    ms.add("repro_b", 2.5, kind="counter")
    ms.add("repro_s", [1.0, float("nan"), 3.0], kind="series")
    doc = ms.to_json()
    # strict-JSON parseable: no bare NaN tokens
    doc2 = json.loads(json.dumps(doc, allow_nan=False))
    assert registry.validate_metrics_json(doc2) == []
    back = registry.metricset_from_json(doc2)
    vals = {m.name: m for m in back}
    assert math.isnan(vals["repro_a"].value)
    assert vals["repro_b"].value == 2.5
    assert math.isnan(vals["repro_s"].value[1])
    assert vals["repro_s"].value[2] == 3.0


def test_json_round_trip_preserves_inf():
    """+/-Infinity must export under allow_nan=False like NaN does —
    a ratio with a zero denominator must not kill the write."""
    ms = MetricSet()
    ms.add("repro_pos", float("inf"))
    ms.add("repro_neg", float("-inf"))
    ms.add("repro_s", [float("inf"), 2.0, float("-inf")], kind="series")
    doc = json.loads(json.dumps(ms.to_json(), allow_nan=False))
    assert registry.validate_metrics_json(doc) == []
    vals = {m.name: m for m in registry.metricset_from_json(doc)}
    assert vals["repro_pos"].value == float("inf")
    assert vals["repro_neg"].value == float("-inf")
    assert vals["repro_s"].value[0] == float("inf")
    assert vals["repro_s"].value[2] == float("-inf")


def test_prometheus_format_and_validator():
    ms = MetricSet()
    ms.add("repro_qos", 93.5, help="QoS satisfaction")
    ms.add("repro_rate", float("nan"), instance="2")
    ms.add("repro_series", [1, 2], kind="series")
    text = ms.to_prometheus()
    assert registry.validate_prometheus(text) == []
    assert "# TYPE repro_qos gauge" in text
    assert 'repro_rate{instance="2"} NaN' in text
    assert "repro_series" not in text       # series have no prom sample
    assert registry.validate_prometheus("not a metric line\n")
    assert registry.validate_metrics_json({"schema": "other"})


def test_collect_stream_covers_the_run(storm_out):
    cfg, out = storm_out
    ms = registry.collect_stream(out, rho=cfg.rho, dt=cfg.dt,
                                 bucket_s=cfg.ev_bucket)
    s = ms.scalars()
    assert 0.0 <= s["repro_qos_satisfaction_pct"] <= 100.0
    assert 0.0 <= s["repro_jain_fairness"] <= 1.0
    assert s["repro_recorder_events_appended"] > 0
    names = {m.name for m in ms}
    assert "repro_step_succ" in names           # series rode along
    assert registry.validate_metrics_json(ms.to_json()) == []
    assert registry.validate_prometheus(ms.to_prometheus()) == []


def test_stream_cell_matches_legacy_shape(storm_out):
    """The registry cell builder reproduces the scenario_suite payload
    key sets exactly — the artifact contract the figures read."""
    cfg, out = storm_out
    base = registry.stream_cell(out, rho=cfg.rho, bucket_s=cfg.ev_bucket,
                                jain=True, n_events=True)
    assert {"qos_sat_pct", "jain", "events"} <= set(base)
    deg = registry.stream_cell(out, rho=cfg.rho, bucket_s=cfg.ev_bucket,
                               resilience=True, breaker_frac=True,
                               max_recovery=False)
    assert {"qos_sat_pct", "drop_rate", "timeout_rate",
            "breaker_open_frac"} <= set(deg)
    assert "max_recovery_s" not in deg
    assert "jain" not in deg
    ctl = registry.stream_cell(out, rho=cfg.rho, bucket_s=cfg.ev_bucket,
                               jain=True, tenants=True, drop_rate=True,
                               control=True)
    assert {"tenant_qos_spread", "tenant_qos_min", "drop_rate"} <= set(ctl)
    # open-loop run: no controller counters in the cell
    assert "scale_up" not in ctl


# -- trace -------------------------------------------------------------

def test_recorder_trace_and_host_timeline(storm_out):
    cfg, out = storm_out
    evs = trace.recorder_trace_events(out.rec, cfg.dt)
    tl = trace.HostTimeline()
    with trace.span("phase", tl, "test"):
        pass
    doc = trace.chrome_trace(evs, tl.events, meta={"run": "t"})
    assert trace.validate_chrome_trace(doc) == []
    insts = [e for e in doc["traceEvents"] if e["ph"] == "i"
             and e.get("cat") == "recorder"]
    assert insts, "storm run must emit recorder instants"
    # simulated µs timestamps: ts / (dt * 1e6) is an integer step
    for e in insts:
        assert abs(e["ts"] / (cfg.dt * 1e6) - e["args"]["step"]) < 1e-6
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert spans and spans[0]["dur"] >= 0
    assert trace.validate_chrome_trace({"traceEvents": [{"ph": "?"}]})


# -- provenance --------------------------------------------------------

def test_provenance_stamp_and_validate(tmp_path):
    payload = {"cell": {"x": 1.0}}
    provenance.stamp(payload, SimConfig(horizon=6.0),
                     extra={"benchmark": "t"})
    pv = payload["provenance"]
    assert pv["schema_version"] == provenance.ARTIFACT_SCHEMA_VERSION
    assert pv["benchmark"] == "t"
    assert len(pv["config_hash"]) == 16
    assert payload["cell"] == {"x": 1.0}     # additive, not an envelope
    p = tmp_path / "t.json"
    p.write_text(json.dumps(payload))
    assert provenance.validate_artifact(str(p)) == []
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"cell": 1}))
    assert provenance.validate_artifact(str(bad))
    res = provenance.validate_all(str(tmp_path))
    assert res["t.json"] == [] and res["bad.json"]


def test_config_hash_is_stable_and_sensitive():
    a = provenance.config_hash(SimConfig(horizon=6.0))
    assert a == provenance.config_hash(SimConfig(horizon=6.0))
    assert a != provenance.config_hash(SimConfig(horizon=7.0))
    assert a != provenance.config_hash(
        dataclasses.replace(SimConfig(horizon=6.0),
                            recorder=RecorderConfig()))


def test_committed_artifacts_carry_provenance():
    """Every benchmark artifact in the repo must validate — the CI obs
    lane runs the same check on freshly generated ones."""
    d = "results/benchmarks"
    res = provenance.validate_all(d)
    assert res, f"no artifacts under {d}"
    bad = {f: p for f, p in res.items() if p}
    assert not bad, bad


# -- run directory -----------------------------------------------------

def test_write_load_validate_report_run(tmp_path, storm_out):
    cfg, out = storm_out
    ms = registry.collect_stream(out, rho=cfg.rho, dt=cfg.dt,
                                 bucket_s=cfg.ev_bucket)
    tl = trace.HostTimeline()
    with tl.span("export", "host"):
        pass
    d = str(tmp_path / "run")
    runlog.write_run(d, metrics=ms, rec=out.rec, dt=cfg.dt, timeline=tl,
                     config=cfg, manifest_extra={"label": "export-test"})
    for f in ("manifest.json", "metrics.json", "metrics.prom",
              "events.json", "trace.json"):
        assert os.path.exists(os.path.join(d, f)), f
    assert {k: v for k, v in runlog.validate_run(d).items() if v} == {}
    run = runlog.load_run(d)
    assert run["manifest"]["label"] == "export-test"
    assert run["events"], "storm events must export"
    text = report.render(d)
    assert "export-test" in text
    assert "qos_satisfaction" in text
    assert "flight recorder" in text.lower()
    # corruption is caught, not rendered over: load_run degrades (no
    # parsed MetricSet, raw doc kept) and validate_run reports instead
    # of raising
    with open(os.path.join(d, "metrics.json"), "w") as f:
        json.dump({"schema": "wrong"}, f)
    run = runlog.load_run(d)
    assert "metrics" not in run and "metrics_doc" in run
    assert any(runlog.validate_run(d).values())
