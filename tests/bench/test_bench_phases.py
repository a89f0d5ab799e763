"""The per-phase readers: device time per step of the ops whose op path
names a phase, as the union of their intervals, control flow left out,
and nothing read where no op is in the phase. The paths come from the
compiled programs' HLO, lowered as the stream entry lowers them."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from rehearsal import SEED, SMALL  # noqa: E402

from bench import cell as bcell  # noqa: E402
from bench import trace  # noqa: E402
from bench.entries import stream  # noqa: E402
from bench.metrics import (accumulator_device_us, idle_share,  # noqa: E402
                           maint_device_us, phases, rounds_device_us,
                           step_device_us)

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "retry_storm_scopes_extract.json")
STEP = "jit(chunk_fn)/while/body/closed_call"
CELL = types.SimpleNamespace(K=30, M=10, mix={"entry": "stream"})
READERS = {"sim.rounds": rounds_device_us,
           "sim.accumulator": accumulator_device_us,
           "sim.maintenance": maint_device_us}


def scoped_view(ops, paths, lane_steps=2, events=None):
    """A view of ``ops`` in the window [0, 1000] ns whose instructions
    have the op ``paths`` given."""
    ev = events or trace.Events({0: ops}, [("bench.traced_jobs", 0, 1000)])
    view = trace.TraceView(None, CELL, lane_steps, None, events=ev)
    view.phase_paths = paths
    return view


def test_union_not_sum_and_containers_left_out():
    ops = [("%while.1", 0, 900), ("%fusion.1", 100, 300),
           ("%fusion.2", 200, 400),        # overlaps the first: once
           ("%fusion.3", 500, 540)]
    paths = {"%while.1": f"{STEP}/sim.rounds/while",
             "%fusion.1": f"{STEP}/sim.rounds/while/body/mul",
             "%fusion.2": f"{STEP}/sim.rounds/while/body/add",
             "%fusion.3": f"{STEP}/sim.accumulator/add"}
    v = scoped_view(ops, paths)
    assert rounds_device_us.read(v) == pytest.approx(300 / 1e3 / 2)
    assert accumulator_device_us.read(v) == pytest.approx(40 / 1e3 / 2)
    # the step's busy time, read as before, holds the container
    assert step_device_us.read(v) == pytest.approx(900e-9 / 2 * 1e6)


def test_an_op_under_two_phases_counts_in_each():
    paths = {"%fusion.1": f"{STEP}/sim.maintenance/jit(f)/sim.rounds/x"}
    v = scoped_view([("%fusion.1", 0, 100)], paths, lane_steps=1)
    assert maint_device_us.read(v) == pytest.approx(0.1)
    assert rounds_device_us.read(v) == pytest.approx(0.1)
    # a component must match whole: sim.roundsx is not sim.rounds
    v = scoped_view([("%fusion.1", 0, 100)],
                    {"%fusion.1": f"{STEP}/sim.roundsx/y"})
    assert rounds_device_us.read(v) is None


def test_clipped_to_the_window_and_none_outside_it():
    ops = [("%fusion.1", 950, 1100), ("%fusion.2", 1200, 1300)]
    paths = {"%fusion.1": f"{STEP}/sim.rounds/y",
             "%fusion.2": f"{STEP}/sim.maintenance/y"}
    v = scoped_view(ops, paths, lane_steps=1)
    assert rounds_device_us.read(v) == pytest.approx(0.05)
    assert maint_device_us.read(v) is None


def test_none_without_scopes_never_zero():
    ops = [("%fusion.1", 0, 100), ("%fusion.2", 100, 200)]
    v = scoped_view(ops, {"%fusion.1": f"{STEP}/mul"})
    for reader in READERS.values():
        assert reader.read(v) is None
    assert idle_share.read(v) == pytest.approx(80.0)
    # no lane-steps, or no device plane (a CPU run): nothing to read
    paths = {"%fusion.1": f"{STEP}/sim.rounds/y"}
    assert rounds_device_us.read(scoped_view(ops, paths, 0)) is None
    cpu = scoped_view(ops, paths, events=trace.Events(
        {}, [("bench.traced_jobs", 0, 1000)]))
    assert rounds_device_us.read(cpu) is None


def test_op_paths_from_compiled_hlo():
    """Real lines of the chip's compiled chunk program: an instruction
    without metadata (an XLA copy) has no path, and a name two programs
    give different paths has none either."""
    with open(RECORDED) as fh:
        rec = json.load(fh)
    paths = phases.op_paths(rec["hlo_lines"])
    assert paths["%fusion.883"] == f"{STEP}/sim.rings/scatter"
    assert paths["%fused_maintenance.13"] == (
        f"{STEP}/sim.maintenance/jit(fused_maintenance)/pallas_call")
    assert "%copy-done.78" not in paths
    other = ('  %fusion.883 = f32[2] fusion(), '
             'metadata={op_name="jit(init_fn)/x"}')
    assert phases.op_paths([*rec["hlo_lines"], other])["%fusion.883"] == ""


def test_recorded_chip_step():
    """One step of a chip trace with the compiled program's own paths:
    the readers give the hand-summed durations of the phase's ops, the
    round loop and the accumulator's searchsorted loop left out."""
    with open(RECORDED) as fh:
        rec = json.load(fh)
    ev = trace.Events.from_json(rec["events"])
    v = scoped_view(None, phases.op_paths(rec["hlo_lines"]),
                    rec["lane_steps"], events=ev)
    for phase, reader in READERS.items():
        assert reader.read(v) == pytest.approx(rec["phase_us"][phase])
    assert rec["phase_us"]["sim.rounds"] == pytest.approx(
        (2186 + 2183) / 1e3)
    # the harness's own readings of the same ops stay what they were
    assert v.breakdown()["device_ops"][0] == ["%fusion.884",
                                              pytest.approx(54777e-9)]
    assert "%while.209" not in [n for n, _ in v.breakdown()["device_ops"]]
    assert step_device_us.read(v) == pytest.approx(v.busy_s * 1e6)


def test_programs_lowered_as_the_entry_lowers_them():
    """The reader compiles the programs the stream entry runs: lowered
    here, they are the entry's own, and their HLO names the phases."""
    cell = bcell.Cell("testbed-retry_storm", SEED,
                      SMALL["testbed-retry_storm"])
    entry = stream.Entry(cell)
    low = phases.lowered(entry)
    assert [x.as_text() for x in low] == entry.lowered_texts()
    view = types.SimpleNamespace(cell=cell)
    named = {c for p in phases.phase_paths(view).values()
             for c in p.split("/") if c.startswith("sim.")}
    assert named == {"sim.placement", "sim.maintenance", "sim.true_mu",
                     "sim.rounds", "sim.rings", "sim.accumulator"}
