"""Rehearsal of the streamed cell (testbed-retry_storm, a short job) on
the CPU: the window ends at a drained chunk, a sound run is correct;
the control and each fault the stream cell can have are not."""
import math

import numpy as np

from rehearsal import (SEED, SMALL, altered_count, chunk_state_unchanged,
                       control, half_batch, rehearse)

CELL = "testbed-retry_storm"


def test_sound_run_is_correct():
    r = rehearse(CELL, kernels="interpret")
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    steps = r["metrics"]["steps_per_s"]
    assert steps["unit"] == "steps/s" and steps["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0
    assert list(r)[-1] == "checks"


def test_job_cut_by_the_clock_ends_at_a_drained_chunk():
    """Past ``stop_at`` a job stops after the chunk in flight: every
    step it reports was run, drained and counted."""
    from bench.cell import Cell
    from bench.entries.stream import Entry
    from repro.kernels import ops

    cell = Cell(CELL, SEED, dict(SMALL[CELL]))
    with ops.mode("ref"):
        entry = Entry(cell)
        accs, series, done = entry.job(0, stop_at=0.0)
    assert done == 2 * cell.chunk < cell.T
    assert len(series[0]["succ"]) == done
    n = cell.reference_drivers()["n_clients"]
    assert float(np.sum(accs[0].n_kc)) == float(n[cell.warmup:done].sum())


def test_traced_run_reads_the_window():
    r = rehearse(CELL, trace=True)
    assert r["correct"], r["checks"]
    assert r["device"]["window_s"] > 0
    assert set(r["metrics"]) <= {"idle_share", "step_device_us",
                                 "maint_kernel_roofline"}


def test_control_is_not_correct():
    r = rehearse(CELL, hooks=control)
    assert not r["correct"], r["checks"]


def test_state_returned_unchanged_is_not_correct():
    r = rehearse(CELL, hooks=chunk_state_unchanged)
    assert not r["correct"] and r["failed"] >= 1


def test_half_batch_is_not_correct():
    r = rehearse(CELL, fault=half_batch)
    assert not r["correct"]


def test_altered_answer_is_not_correct():
    r = rehearse(CELL, hooks=altered_count)
    assert not r["correct"]
    assert any(c["value"] > c["limit"] or not math.isfinite(c["value"])
               for c in r["checks"].values())
