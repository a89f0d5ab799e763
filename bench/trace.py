"""Reduce a JAX profiler trace of the traced window to what the
per-layer readers in ``bench/metrics/`` need.

* The window is the host span ``bench.traced_jobs`` that the harness
  opens around the traced jobs.
* Device busy time is the union of the intervals in which an operation
  ran on a chip (events of its ``XLA Ops`` line), clipped to the
  window, averaged over the chips the cell uses.
* The breakdown lists the device operations that took most time and
  the longest idle gaps on the first chip, each named by the host span
  (``bench.*``, from the harness's own files) that was open during it.
"""
from __future__ import annotations

import collections
import glob
import json
import os
import re

BENCH = os.path.dirname(os.path.abspath(__file__))
WINDOW_SPAN = "bench.traced_jobs"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]*$")


def peaks_for(device_kind: str) -> dict:
    """The chip's peaks from ``bench/peaks.json``; a device missing from
    the table is an error, not a default."""
    with open(os.path.join(BENCH, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of (start, end) intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle (start, end) gaps of [lo, hi] between the intervals."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


class Events:
    """The trace as plain lists: device ops per chip and host spans."""

    def __init__(self, device_ops: dict, host_spans: list):
        self.device_ops = device_ops      # {chip: [(name, start, end)]}
        self.host_spans = host_spans      # [(name, start, end)]

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Events":
        from jax.profiler import ProfileData
        paths = sorted(glob.glob(os.path.join(trace_dir, "**",
                                              "*.xplane.pb"), recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        pd = ProfileData.from_file(paths[-1])
        device_ops, host_spans = {}, []
        for plane in pd.planes:
            chip = DEVICE_PLANE.match(plane.name)
            if chip:
                chip = int(chip.group(1))
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        # an op's name is its HLO text: keep "%name.N"
                        device_ops[chip] = [
                            (e.name.split(" = ", 1)[0], e.start_ns, e.end_ns)
                            for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            host_spans.append((e.name, e.start_ns, e.end_ns))
        return cls(device_ops, host_spans)

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        """From ``{"device_ops": {chip: [[name, start, end]]},
        "host_spans": [[name, start, end]]}`` (a recorded trace)."""
        return cls({int(k): [tuple(x) for x in v]
                    for k, v in d["device_ops"].items()},
                   [tuple(x) for x in d["host_spans"]])


class TraceView:
    """What a per-layer reader sees: the window, busy time, ops by name,
    the lane-steps drained in the window, the cell and the peaks."""

    def __init__(self, trace_dir, cell, lane_steps: int, peaks: dict | None,
                 chips: int = 1, events: Events | None = None):
        self.events = events or Events.from_dir(trace_dir)
        self.cell, self.lane_steps, self.peaks = cell, lane_steps, peaks
        win = [s for s in self.events.host_spans if s[0] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
        self.lo, self.hi = win[0][1], win[0][2]
        self.window_s = (self.hi - self.lo) / 1e9
        # a trace with no device plane (a CPU run) reads as never busy
        self.chips = sorted(self.events.device_ops)[:chips]
        busy = [union_ns([(s, e) for _, s, e in self.events.device_ops[c]],
                         self.lo, self.hi) for c in self.chips]
        self.busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0

    def ops(self, chip: int | None = None):
        """(name, start, end) of the device ops inside the window."""
        if not self.chips:
            return []
        chip = self.chips[0] if chip is None else chip
        return [(n, s, e) for n, s, e in self.events.device_ops[chip]
                if s >= self.lo and e <= self.hi]

    def op_seconds(self, match, chip: int | None = None) -> tuple[float, int]:
        """Summed device seconds and count of the window's ops whose name
        ``match(name)`` accepts."""
        hits = [(e - s) for n, s, e in self.ops(chip) if match(n)]
        return sum(hits) / 1e9, len(hits)

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most time, control flow (``while``,
        ``conditional``, ``call``, which hold the others) left out, and
        the longest idle gaps with the harness span open in each."""
        tot = collections.Counter()
        for n, s, e in self.ops():
            if not CONTAINER.match(n):
                tot[n] += (e - s) / 1e9
        ivs = [(s, e) for _, s, e in self.ops()]
        gaps = sorted(gaps_ns(ivs, self.lo, self.hi),
                      key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, v] for n, v in tot.most_common(top)],
                "idle_gaps": [[self.host_span_at((a + b) / 2), (b - a) / 1e9]
                              for a, b in gaps]}

    def host_span_at(self, t: float) -> str:
        """The innermost harness span open at time ``t``."""
        best = None
        for n, s, e in self.events.host_spans:
            if n != WINDOW_SPAN and s <= t <= e:
                if best is None or e - s < best[2] - best[1]:
                    best = (n, s, e)
        return best[0] if best else "between spans"
