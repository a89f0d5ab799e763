"""Shared arithmetic of the per-phase readers: the device time a
simulated step spends in one named phase of the engine step [us].

The engine wraps each phase of its step in ``jax.named_scope``
(``repro.obs.trace.STEP_PHASES``: ``sim.rounds``, ``sim.rings``,
``sim.accumulator``, ``sim.maintenance``, ...), which lands in the
``op_name`` metadata of every HLO instruction of the phase, e.g.
``jit(chunk_fn)/while/body/closed_call/sim.rings/scatter``. A TPU trace
names each device op by its HLO instruction (``%fusion.883``) and gives
it no op path, so the paths come from the programs themselves: the
cell's entry is built again, its programs are compiled (the run has
just put them in the persistent cache) and each instruction's
``op_name`` is read from the compiled HLO text. A name that two of the
programs give different paths, and an instruction XLA added without
metadata (a copy), is in no phase.

An op is in a phase when one component of its path is the phase's name;
an op under two phases counts in each. A phase's time is the union of
the intervals of its ops, clipped to the window and averaged over the
cell's chips, as busy time is, over the window's lane-steps. Control
flow (``while``, ``conditional``, ``call``), which holds the other ops,
is left out, as the breakdown leaves it out. A trace in which no op is
in the phase reads ``None``. The paths are kept on the view as
``phase_paths``, found once for all the per-phase readers.
"""
from __future__ import annotations

import re

from bench import trace

INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?(%[^\s=]+) = .*?\bop_name="([^"]*)"', re.M)


def op_paths(hlo_texts) -> dict:
    """``{instruction name: op path}`` from compiled HLO texts; a name
    the texts give different paths maps to ``""``."""
    paths = {}
    for text in hlo_texts:
        for name, path in INSTRUCTION.findall(text):
            paths[name] = path if paths.get(name, path) == path else ""
    return paths


def lowered(entry) -> list:
    """Every program a job of a stream ``entry`` runs, lowered as
    ``Entry.lowered_texts`` lowers them: init, then one chunk program
    per chunk length."""
    import jax
    args = (entry.rtt, entry.drivers.active[0], entry.cell.warm_key())
    carry, keys = jax.eval_shape(entry.init, *args)
    out, seen = [entry.init.lower(*args)], set()
    for (lo, hi), (t_idx, drv) in zip(entry.slices, entry.xs):
        if hi - lo not in seen:
            seen.add(hi - lo)
            ks = jax.ShapeDtypeStruct((hi - lo,) + keys.shape[1:], keys.dtype)
            out.append(entry.run_chunk.lower(entry.rtt, carry, t_idx, drv,
                                             ks))
    return out


def phase_paths(view) -> dict:
    """The op path of each HLO instruction the view's cell runs; ``{}``
    for an entry kind other than ``stream``."""
    if not hasattr(view, "phase_paths"):
        view.phase_paths = {}
        if view.cell.mix["entry"] == "stream":
            from bench.entries import stream
            entry = stream.Entry(view.cell)
            view.phase_paths = op_paths(
                low.compile().as_text() for low in lowered(entry))
            entry.close()
    return view.phase_paths


def device_us(view, phase: str) -> float | None:
    """Device time per lane-step of the window's ops in ``phase``."""
    if not view.lane_steps or not view.chips:
        return None
    paths = phase_paths(view)
    per_chip, hits = [], 0
    for chip in view.chips:
        ivs = [(s, e) for n, s, e in view.events.device_ops[chip]
               if s < view.hi and e > view.lo and not trace.CONTAINER.match(n)
               and phase in paths.get(n, "").split("/")]
        hits += len(ivs)
        per_chip.append(trace.union_ns(ivs, view.lo, view.hi))
    if not hits:
        return None
    return sum(per_chip) / len(per_chip) / 1e3 / view.lane_steps
