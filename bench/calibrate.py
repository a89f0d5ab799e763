"""Readings for the limits of the correctness check, in one process.

    python3 -m bench.calibrate --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 4 5 6] [--out readings.json]

For every seed: one job of the program through the cell's own compiled
entry (the timed path, at the timed sizes), checked by
``bench.run.check`` as a run checks it: the readings of sound runs. For
every control seed: the reference computed in bfloat16 put in the
program's place, checked the same way: the control's readings. The
lower reading of a number is the largest over the sound seeds, its
upper reading the smallest over the control seeds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import importlib

    import jax
    import jax.numpy as jnp

    from bench import compare, reference
    from bench.cell import Cell
    from bench.run import check, enable_compile_cache

    enable_compile_cache(jax)
    first = Cell(args.workload, (args.seeds or args.control_seeds)[0])
    entry = importlib.import_module(
        f"bench.entries.{first.mix['entry']}").Entry(first)
    params = first.reference_params()
    rdrv = first.reference_drivers()
    drv = {f: jnp.asarray(rdrv[f]) for f in reference.DRIVER_FIELDS}
    out = {"workload": args.workload, "sound": {}, "control": {}}

    for seed in args.seeds:
        cell = Cell(args.workload, seed)
        entry.load(cell)
        t0 = time.perf_counter()
        accs, series, done = entry.job(0)
        t_prog = time.perf_counter() - t0
        t0 = time.perf_counter()
        out["sound"][seed] = check(cell, [(accs, series, done, t_prog)], 0)
        print(f"sound seed={seed} prog_s={t_prog} ref_s="
              f"{time.perf_counter() - t0} " + " ".join(
                  f"{k}={v}" for k, v in out["sound"][seed].items()),
              flush=True)
    for seed in args.control_seeds:
        cell = Cell(args.workload, seed)
        t0 = time.perf_counter()
        low = [jax.device_get(reference.run_job(
            params, cell.rtt(lane), drv, cell.job_key(0, lane),
            dtype=jnp.bfloat16)) for lane in range(cell.lanes)]
        job = ([compare.Answer.from_stats(s) for s in low],
               [{"succ": s.succ_steps, "attempts": s.attempts_steps,
                 "regret": s.regret_steps} for s in low], cell.T, 0.0)
        out["control"][seed] = check(cell, [job], 0)
        print(f"control seed={seed} s={time.perf_counter() - t0} " + " ".join(
            f"{k}={v}" for k, v in out["control"][seed].items()), flush=True)
    names = list(next(iter({**out["sound"], **out["control"]}.values())))
    for side, pick in (("sound", max), ("control", min)):
        if out[side]:
            got = {k: pick(d[k] for d in out[side].values()) for k in names}
            print(("lower " if side == "sound" else "upper ") + " ".join(
                f"{k}={v}" for k, v in got.items()))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
