"""The comparison that decides ``correct``: what the timed path produced
for a job, against the plain reference run on the same inputs.

A job is one deterministic function of its key, and the program and
the reference compute it with the same float32 arithmetic, so the two
follow one trajectory: every count of the job agrees exactly, step by
step and in the accumulator, and the regret, a sum of success
probabilities, to float rounding. Once one near-tie in a proxy's SWRR
credits goes the other way the two runs part for good, so any fault
or change of precision that moves one decision shows as a gap in the
counts. The limits (``bench/limits/<cell>.json``, and PERF.md for the
readings they were set from) sit at or above what sound runs of the
program read over many seeds and below what the control (the
reference computed in bfloat16) reads.

Every number is the widest over the job's lanes.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np


class Answer(NamedTuple):
    """A job's statistics under the accumulator's field names, for a
    reference run put in the program's place (the control)."""
    n_kc: np.ndarray
    succ_kc: np.ndarray
    arrivals_m: np.ndarray
    choice_counts: np.ndarray
    proc_hist: np.ndarray
    regret_k: np.ndarray
    att_k: np.ndarray
    timeout_k: np.ndarray
    drop_k: np.ndarray
    open_km: np.ndarray

    @classmethod
    def from_stats(cls, s) -> "Answer":
        f = np.asarray
        return cls(n_kc=f(s.issued), succ_kc=f(s.succ),
                   arrivals_m=f(s.arrivals), choice_counts=f(s.choices),
                   proc_hist=f(s.proc_hist), regret_k=f(s.regret),
                   att_k=f(s.attempts), timeout_k=f(s.timeouts),
                   drop_k=f(s.drops), open_km=f(s.open_steps))


PREFIX_SERIES = (("prefix_succ_gap", "succ", "succ_steps"),
                 ("prefix_attempts_gap", "attempts", "attempts_steps"),
                 ("prefix_regret_gap", "regret", "regret_steps"))


def prefix_numbers(series, ref, K: int, n: int) -> dict:
    """Gaps over the job's first ``n`` steps, where sound runs follow
    the reference's trajectory: the largest per-step gap of the fleet's
    QoS successes and attempts (counts, exact while the two agree) and
    of its regret per proxy (float rounding while they agree)."""
    out = {}
    for name, mine, theirs in PREFIX_SERIES:
        gap = _max_gap(series.get(mine), getattr(ref, theirs), n)
        out[name] = gap / K if mine == "regret" else gap
    return out


def _max_gap(got, want, n: int) -> float:
    if got is None or want is None:
        return math.inf
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.ndim != 1 or len(got) < n or len(want) < n:
        return math.inf
    gap = np.abs(got[:n] - want[:n])
    return float(gap.max()) if n else 0.0


def job_numbers(acc, ref) -> dict:
    """Whole-job gaps of one lane's accumulator against the reference's
    statistics, each the largest over its entries: requests that met
    tau, routing per (proxy, instance), arrivals per instance, the
    processing-latency sketch, attempts, timeouts, drops and
    breaker-open steps (counts, exact while the trajectories agree),
    and each proxy's regret relative to the largest."""
    def gap(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if a.shape != b.shape:
            return math.inf
        return float(np.max(np.abs(a - b))) if a.size else 0.0

    scale = max(float(np.max(np.abs(np.asarray(ref.regret)))), 1e-30)
    out = {
        "job_succ_gap": gap(np.sum(acc.succ_kc), ref.succ),
        "job_routing_gap": gap(acc.choice_counts, ref.choices),
        "job_arrivals_gap": gap(acc.arrivals_m, ref.arrivals),
        "job_latency_gap": gap(acc.proc_hist, ref.proc_hist),
        "job_attempts_gap": gap(np.sum(acc.att_k), ref.attempts),
        "job_timeouts_gap": gap(np.sum(acc.timeout_k), ref.timeouts),
        "job_drops_gap": gap(np.sum(acc.drop_k), ref.drops),
        "job_breaker_gap": gap(np.sum(acc.open_km), ref.open_steps),
        "job_regret_gap": gap(acc.regret_k, ref.regret) / scale,
    }
    finite = all(np.all(np.isfinite(np.asarray(getattr(acc, f))))
                 for f in acc._fields)
    return out if finite else {k: math.inf for k in out}


def widest(per_lane: list[dict]) -> dict:
    return {k: max(d[k] for d in per_lane) for k in per_lane[0]}


def judge(numbers: dict, limits: dict) -> tuple[bool, list[tuple]]:
    """``correct`` and the (name, value, limit) rows that decided it. A
    number is within its limit when it is not above it; every name in
    the limits must have been measured."""
    rows = [(name, numbers.get(name, math.inf), float(lim))
            for name, lim in limits.items()]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
