"""The trace reduction: busy time as the union of device-op intervals,
idle gaps named by the host span open in them, and the kernel time the
roofline readers sum."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402
from bench.metrics import (idle_share, round_kernel_roofline,  # noqa: E402
                           step_device_us)

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "k1000_trace_extract.json")


def test_union_and_gaps():
    ivs = [(0, 10), (5, 20), (30, 40), (35, 38), (50, 60)]
    assert trace.union_ns(ivs, 0, 100) == 20 + 10 + 10
    assert trace.union_ns(ivs, 8, 55) == 12 + 10 + 5
    assert trace.gaps_ns(ivs, 0, 100) == [(20, 30), (40, 50), (60, 100)]
    assert trace.gaps_ns([], 0, 5) == [(0, 5)]


def synthetic():
    # window [100, 1100] ns; two chunk dispatches, a drain, a readout
    ops = [("%while.2", 100, 900), ("%fusion.3", 100, 300),
           ("%round_step_swrr.1", 300, 500), ("%fusion.3", 450, 520),
           ("%round_step_swrr.1", 700, 900)]
    spans = [("bench.traced_jobs", 100, 1100),
             ("bench.chunk_dispatch", 100, 150),
             ("bench.drain", 520, 700), ("bench.readout", 900, 1100)]
    return trace.Events({0: ops}, spans)


def test_view_busy_idle_and_breakdown():
    cell = types.SimpleNamespace(K=30, M=10)
    v = trace.TraceView(None, cell, 4, None, events=synthetic())
    assert v.window_s == pytest.approx(1000e-9)
    # the while loop holds the others: busy 100-900
    assert v.busy_s == pytest.approx(800e-9)
    assert idle_share.read(v) == pytest.approx(20.0)
    assert step_device_us.read(v) == pytest.approx(800e-9 / 4 * 1e6)
    secs, n = v.op_seconds(round_kernel_roofline.match)
    assert (secs, n) == (pytest.approx(400e-9), 2)
    b = v.breakdown()
    assert b["device_ops"][0] == ["%round_step_swrr.1",
                                  pytest.approx(400e-9)]
    assert "%while.2" not in [n for n, _ in b["device_ops"]]
    assert b["idle_gaps"] == [["bench.readout", pytest.approx(200e-9)]]


def test_missing_device_kind_is_an_error():
    with pytest.raises(KeyError):
        trace.peaks_for("not a chip")
    assert trace.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_recorded_chip_trace():
    """A real trace's first 3 ms on a TPU v5e: nine device ops whose
    durations, summed by hand, make 2,193 ns of busy time."""
    with open(RECORDED) as fh:
        rec = json.load(fh)
    ev = trace.Events.from_json(rec["events"])
    cell = types.SimpleNamespace(**rec["cell"])
    v = trace.TraceView(None, cell, rec["lane_steps"], None, events=ev)
    assert v.window_s == pytest.approx(3e-3) == pytest.approx(rec["window_s"])
    assert v.busy_s == pytest.approx(2193e-9) == pytest.approx(rec["busy_s"])
    assert len(v.ops()) == 9
    assert idle_share.read(v) == pytest.approx(100 * (1 - 2193e-9 / 3e-3))
