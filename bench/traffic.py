"""The benchmark's one traffic generator: a mix file's load and event
timeline, lowered twice from the same parameters.

* ``program_drivers`` builds the program's own ``Scenario`` from the
  mix and lowers it with the program's public ``compile_scenario``,
  its input format.
* ``reference_drivers`` writes the same timeline into plain numpy
  arrays, with no program code, for the reference and for the check
  that both agree.

Every proxy runs ``base_clients`` clients (one request per client per
step) unless an event changes it. Event kinds live in
``bench/events/<kind>.py``.
"""
from __future__ import annotations

import importlib

import numpy as np

FIELDS = ("n_clients", "active", "rtt_scale", "rtt_cut_k", "rtt_cut_m",
          "s_m")
MIN_SERVICE_TIME = 1e-4


def _kind(ev: dict):
    return importlib.import_module(f"bench.events.{ev['kind']}")


def program_drivers(mix: dict, cfg, K: int, M: int, key):
    from repro.continuum import Scenario, compile_scenario
    events = tuple(_kind(ev).program_event(ev, cfg.horizon, K, M)
                   for ev in mix.get("events", ()))
    scn = Scenario(mix["name"], events, n_nodes=K, n_instances=M,
                   base_clients=mix["base_clients"])
    return compile_scenario(scn, cfg, key)


def reference_drivers(mix: dict, horizon: float, dt: float, C: int,
                      service_time: float, K: int, M: int) -> dict:
    """Numpy driver rows: (T, K) client counts, (T, M) liveness, RTT
    modulation and service times, and the sorted event-onset steps."""
    T = int(round(horizon / dt))
    arrs = dict(
        n_clients=np.full((T, K), mix["base_clients"], np.int64),
        active=np.ones((T, M), bool),
        rtt_scale=np.ones((T, M), np.float64),
        rtt_cut_k=np.zeros((T, K), np.float64),
        rtt_cut_m=np.zeros((T, M), np.float64),
        s_m=np.full((T, M), service_time, np.float64),
        marks=[])
    for ev in mix.get("events", ()):
        _kind(ev).apply(arrs, ev, horizon, dt, K, M)
    return dict(
        n_clients=np.clip(arrs["n_clients"], 0, C).astype(np.int32),
        active=arrs["active"],
        rtt_scale=arrs["rtt_scale"].astype(np.float32),
        rtt_cut_k=arrs["rtt_cut_k"].astype(np.float32),
        rtt_cut_m=arrs["rtt_cut_m"].astype(np.float32),
        s_m=np.maximum(arrs["s_m"], MIN_SERVICE_TIME).astype(np.float32),
        marks=sorted(set(m for m in arrs["marks"] if 0 <= m < T)))


def drivers_gap(program, reference: dict) -> float:
    """Largest difference between the program's lowered drivers and
    the benchmark's own (0 when the two timelines agree)."""
    gap = 0.0
    for f in FIELDS:
        a = np.asarray(getattr(program, f), np.float64)
        b = np.asarray(reference[f], np.float64)
        if a.shape != b.shape:
            return float("inf")
        gap = max(gap, float(np.max(np.abs(a - b))) if a.size else 0.0)
    marks = [int(m) for m in np.asarray(program.marks) if m >= 0]
    return gap if marks == reference["marks"] else float("inf")
