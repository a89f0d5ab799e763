"""BENCHMARK.json against the files it names, and against the limits
the benchmark's format sets on names, units and cells."""
import importlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_top_level_keys():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    for p in m["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert os.path.isdir(os.path.join(ROOT, p))
        assert not p.startswith("/") and ".." not in p.split("/")
    assert m["command"][:3] == ["python3", "-m", "bench.run"]


@pytest.mark.parametrize("w", manifest()["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(w):
    """Every cell has a config, a mix, an entry, limits and readers."""
    from bench import cell as bcell
    m = manifest()
    names = {c["name"]: c for c in m["configs"]}
    assert w["config"] in names
    assert os.path.isfile(os.path.join(ROOT, names[w["config"]]["file"]))
    c = bcell.Cell(w["name"], 0)
    importlib.import_module(f"bench.entries.{c.mix['entry']}").Entry
    assert c.limits and all(v >= 0 for v in c.limits.values())
    for ev in c.mix.get("events", []):
        mod = importlib.import_module(f"bench.events.{ev['kind']}")
        assert callable(mod.apply) and callable(mod.program_event)
    per_layer = [p for p in m["per_layer"]
                 if w["name"] in p.get("workloads", [w["name"]])]
    assert per_layer, "every cell reports a per-layer metric"
    for p in per_layer:
        assert callable(importlib.import_module(
            f"bench.metrics.{p['name']}").read)
    e2e = [e["name"] for e in m["end_to_end"]
           if w["name"] in e.get("workloads", [w["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_names_units_and_chips():
    m = manifest()
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in m[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    for w in m["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in m["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_bounds_and_metric_sources():
    m = manifest()
    assert {e["name"] for e in m["end_to_end"]} == {"steps_per_s", "setup_s"}
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in m["workloads"]}
    e2e = {e["name"] for e in m["end_to_end"]}
    for p in m["per_layer"]:
        assert p["moves"] in e2e
        assert set(p.get("workloads", [])) <= cells
        assert p["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in p["layer"] and 1 <= len(p["layer"]) <= 200
