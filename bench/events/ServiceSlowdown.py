"""Throttle the first ``instances_frac`` of the instances by ``factor``
from ``start_frac`` to ``stop_frac`` of the job horizon."""
from __future__ import annotations


def instances(ev: dict, M: int) -> tuple[int, ...]:
    return tuple(range(max(1, int(round(ev["instances_frac"] * M)))))


def program_event(ev: dict, horizon: float, K: int, M: int):
    from repro.continuum.scenarios import ServiceSlowdown
    return ServiceSlowdown(start=ev["start_frac"] * horizon,
                           stop=ev["stop_frac"] * horizon,
                           instances=instances(ev, M), factor=ev["factor"])


def apply(arrs: dict, ev: dict, horizon: float, dt: float, K: int, M: int):
    T = arrs["s_m"].shape[0]
    lo = max(0, min(T, int(round(ev["start_frac"] * horizon / dt))))
    hi = max(lo, min(T, int(round(ev["stop_frac"] * horizon / dt))))
    arrs["s_m"][lo:hi, list(instances(ev, M))] *= ev["factor"]
    arrs["marks"].append(lo)
