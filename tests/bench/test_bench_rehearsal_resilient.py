"""Rehearsal of the testbed-retry_storm cell's resilience on the CPU:
the compared job holds the throttle, its timeouts and open breakers,
and the program still agrees with the reference on every counter; the
control does not."""
import numpy as np

from rehearsal import (SEED, SMALL, altered_reward, chunk_state_unchanged,
                       control, rehearse)

CELL = "testbed-retry_storm"
RESILIENCE = {"job_timeouts_gap", "job_drops_gap", "job_breaker_gap"}


def test_sound_run_is_correct():
    """Every job of the run times requests out and opens breakers
    during the throttle, and the check compares those counters."""
    from bench.cell import Cell
    from bench.entries.stream import Entry
    from repro.kernels import ops

    cell = Cell(CELL, SEED, dict(SMALL[CELL]))
    with ops.mode("ref"):
        accs, _, _ = Entry(cell).job(0)
    assert float(np.sum(accs[0].timeout_k)) > 0
    assert float(np.sum(accs[0].open_km)) > 0
    r = rehearse(CELL)
    assert r["correct"], r["checks"]
    assert RESILIENCE <= set(r["checks"])


def test_control_is_not_correct():
    r = rehearse(CELL, hooks=control)
    assert not r["correct"]
    assert any(r["checks"][k]["value"] > r["checks"][k]["limit"]
               for k in RESILIENCE)


def test_state_returned_unchanged_is_not_correct():
    assert not rehearse(CELL, hooks=chunk_state_unchanged)["correct"]


def test_altered_answer_is_not_correct():
    """One request's outcome per step flipped where the step scores it."""
    r = rehearse(CELL, fault=altered_reward)
    assert not r["correct"]
    assert r["checks"]["job_succ_gap"]["value"] > 0
