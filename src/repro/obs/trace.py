"""Named phases of a run, and Chrome trace-event export of recorder
events and host-side timings as a Perfetto-loadable timeline.

* :data:`STEP_PHASES` names the parts of one simulator step. The engine
  wraps each in ``jax.named_scope``, so every instruction of the
  compiled program carries its phase in its ``op_name`` metadata.
* :func:`span` names a stretch of host work: a profiler annotation on
  the device trace's clock (next to free when no profiler runs), also
  recorded into a :class:`HostTimeline` when one is given.

Two time domains share one trace, on separate process lanes:

* **Simulated time** — flight-recorder events at ``ts = step * dt`` (in
  trace microseconds). One process (``pid``) per player shard, one
  thread (``tid``) per event-kind class, so Perfetto renders lanes like
  ``shard 0 / breaker_trip``. Fleet-level events (scenario marks,
  control actions) get their own ``fleet`` process.
* **Host wall time** — spans (:func:`span`) recorded into a
  :class:`HostTimeline` (chunk dispatch, export, …) as duration events
  on a ``host`` process, re-based to the timeline's construction.

The emitted document is the standard JSON object format
(``{"traceEvents": [...]}``) with ``ph`` "i" instant events for
recorder records, "X" complete events for host spans and "M" metadata
events naming the lanes — loads in ``ui.perfetto.dev`` and
``chrome://tracing`` as-is. :func:`validate_chrome_trace` is the schema
gate CI runs on every exported trace.
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager

import jax

from repro.obs import recorder as obr

TRACE_SCHEMA_VERSION = 1

# The phases of one simulator step, in the order the step runs them;
# ``continuum.simulator`` names each with ``jax.named_scope``.
STEP_PHASES = ("sim.control", "sim.placement", "sim.maintenance",
               "sim.true_mu", "sim.rounds", "sim.rings", "sim.accumulator",
               "sim.recorder")

# fixed pid blocks so lanes sort stably in the UI
_PID_FLEET = 1
_PID_SHARD0 = 10
_PID_HOST = 1000

_FLEET_KINDS = frozenset({obr.KIND_MARK, obr.KIND_SCALE_UP,
                          obr.KIND_SCALE_DOWN, obr.KIND_MIGRATE})


def _meta(pid, tid, key, name) -> dict:
    return {"ph": "M", "pid": pid, "tid": tid, "name": key,
            "args": {"name": name}}


def recorder_trace_events(rec_or_events, dt: float) -> list[dict]:
    """Lower recorder events to Chrome instant events (+ lane
    metadata). Accepts a ``RecorderState`` or a pre-decoded event
    list."""
    events = (rec_or_events if isinstance(rec_or_events, list)
              else obr.recorder_events(rec_or_events))
    out = []
    lanes_named: set[tuple] = set()

    def name_lane(pid, tid, pname, tname):
        if (pid, None) not in lanes_named:
            out.append(_meta(pid, 0, "process_name", pname))
            lanes_named.add((pid, None))
        if (pid, tid) not in lanes_named:
            out.append(_meta(pid, tid, "thread_name", tname))
            lanes_named.add((pid, tid))

    for ev in events:
        fleet = ev.kind in _FLEET_KINDS
        pid = _PID_FLEET if fleet else _PID_SHARD0 + ev.shard
        tid = ev.kind + 1
        name_lane(pid, tid,
                  "fleet" if fleet else f"player shard {ev.shard}",
                  ev.kind_str)
        out.append({
            "ph": "i", "s": "g" if fleet else "t",
            "pid": pid, "tid": tid,
            "name": ev.kind_str,
            "cat": "recorder",
            "ts": ev.step * dt * 1e6,       # simulated µs
            "args": {"step": ev.step, "entity": ev.entity,
                     "value": ev.value, "seq": ev.seq},
        })
    return out


class HostTimeline:
    """Wall-clock span sink for the host side of a run: each
    :func:`span` given this timeline becomes a ph="X" complete event on
    the ``host`` process lane, re-based to the timeline's construction
    time."""

    def __init__(self):
        self._t0 = time.perf_counter()
        self.events: list[dict] = [
            _meta(_PID_HOST, 0, "process_name", "host"),
            _meta(_PID_HOST, 1, "thread_name", "driver"),
        ]

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def span(self, name: str, cat: str = "host", **args):
        return span(name, self, cat, **args)


@contextmanager
def span(name: str, timeline: HostTimeline | None = None,
         cat: str = "host", **args):
    """A named stretch of host work. It is a ``jax.profiler``
    annotation, so a profiler trace shows it on the host plane, on the
    same clock as the device ops; without a profiler session it costs
    next to nothing. With ``timeline`` it is also recorded there."""
    t0 = timeline._now_us() if timeline is not None else 0.0
    with jax.profiler.TraceAnnotation(name):
        try:
            yield
        finally:
            if timeline is not None:
                timeline.events.append({
                    "ph": "X", "pid": _PID_HOST, "tid": 1, "name": name,
                    "cat": cat, "ts": t0, "dur": timeline._now_us() - t0,
                    **({"args": args} if args else {})})


def chrome_trace(*event_lists, meta: dict | None = None) -> dict:
    """Assemble event lists into one trace document."""
    events = [e for lst in event_lists for e in lst]
    doc = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"schema": "repro.obs.trace",
                      "schema_version": TRACE_SCHEMA_VERSION},
    }
    if meta:
        doc["otherData"].update(meta)
    return doc


def write_chrome_trace(path, *event_lists, meta: dict | None = None) -> dict:
    doc = chrome_trace(*event_lists, meta=meta)
    with open(path, "w") as f:
        json.dump(doc, f)
    return doc


_PHASES = {"i", "X", "M", "B", "E", "b", "e", "n", "C"}


def validate_chrome_trace(doc) -> list[str]:
    """Schema check; returns a list of problems (empty = valid)."""
    problems = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["not a JSON-object-format trace (no traceEvents)"]
    evs = doc["traceEvents"]
    if not isinstance(evs, list):
        return ["traceEvents is not a list"]
    for i, e in enumerate(evs):
        if not isinstance(e, dict):
            problems.append(f"event {i}: not an object")
            continue
        ph = e.get("ph")
        if ph not in _PHASES:
            problems.append(f"event {i}: bad ph {ph!r}")
            continue
        if "name" not in e or "pid" not in e:
            problems.append(f"event {i}: missing name/pid")
        if ph in ("i", "X") and not isinstance(e.get("ts"), (int, float)):
            problems.append(f"event {i}: missing numeric ts")
        if ph == "X" and not isinstance(e.get("dur"), (int, float)):
            problems.append(f"event {i}: X event missing dur")
        if ph == "i" and e.get("s") not in (None, "g", "p", "t"):
            problems.append(f"event {i}: bad instant scope {e.get('s')!r}")
    return problems
