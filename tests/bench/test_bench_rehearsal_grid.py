"""Rehearsal of the testbed-grid5 cell (the paper testbed itself, 5
lanes, a short job) on the CPU: a sound run is correct; the control,
and a program broken inside its step, are not."""
from rehearsal import (altered_latency, control, half_batch, rehearse,
                       round_state_unchanged)

CELL = "testbed-grid5"


def failing(r):
    return {k for k, c in r["checks"].items() if not c["value"] <= c["limit"]}


def test_sound_run_is_correct():
    r = rehearse(CELL, kernels="interpret")
    assert r["correct"], r["checks"]
    assert r["metrics"]["steps_per_s"]["value"] > 0


def test_control_is_not_correct():
    r = rehearse(CELL, hooks=control)
    assert not r["correct"]
    assert {"job_routing_gap", "prefix_succ_gap"} <= failing(r)


def test_state_returned_unchanged_is_not_correct():
    """The round kernel hands back the routing state it was given."""
    r = rehearse(CELL, fault=round_state_unchanged)
    assert not r["correct"] and "job_routing_gap" in failing(r)


def test_half_batch_is_not_correct():
    """Maintenance skips the upper half of every lane's proxies."""
    r = rehearse(CELL, fault=half_batch)
    assert not r["correct"] and "job_routing_gap" in failing(r)


def test_altered_answer_is_not_correct():
    """One request per step reported slower than it was served."""
    r = rehearse(CELL, fault=altered_latency)
    assert not r["correct"] and "job_succ_gap" in failing(r)
