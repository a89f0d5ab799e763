"""Helpers of the benchmark's CPU rehearsals: each cell run end to end
through ``bench.run.run_cell`` on jobs short enough for a test, the
sound runs with the Pallas kernels in interpret mode, and the timed
path broken in the ways the correctness check has to catch. The faults
are planted inside the program's step, where its state and answers
are made, not on what a job reads back."""
import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import compare, reference, run  # noqa: E402

SEED = 2**31 + 987_654_321          # larger than 32 signed bits hold
SECONDS = 1.0
SMALL = {
    # the paper testbed itself; 30 s jobs keep the tests short, and
    # the retry storm's throttle (35% to 65% of the job) inside them
    "testbed-grid5": dict(horizon_s=30.0, warmup_s=10.0),
    "testbed-retry_storm": dict(horizon_s=30.0, warmup_s=10.0,
                                chunk_steps=60),
}


# cells kept out of BENCHMARK.json whose files stay (PERF.md, Open
# questions): the paper suite's grid, out while the Pallas round kernel
# rounds differently from its jnp oracle on the chip
KEPT_OUT = {
    "testbed-grid5": {"name": "testbed-grid5", "config": "testbed_k30_m10",
                      "traffic": "paper_grid", "chips": 1},
}


def rehearse(cell: str, hooks=None, trace: bool = False,
             kernels: str = "ref", overrides: dict | None = None,
             fault=None) -> dict:
    """One run of ``cell``; ``kernels="interpret"`` runs the Pallas
    kernel bodies, the default their jnp oracles (bit-identical, and
    much faster on the CPU). ``fault`` is a context manager that breaks
    the program while the run builds and runs it."""
    from repro.kernels import ops
    with ops.mode(kernels), (fault or contextlib.nullcontext)():
        return run.run_cell(cell, SEED, SECONDS, trace, require_tpu=False,
                            overrides=overrides or SMALL[cell],
                            hooks=hooks, entry=KEPT_OUT.get(cell))


def control(entry):
    """The control: the reference computed in bfloat16, in the
    program's place."""
    cell = entry.cell
    params, rdrv = cell.reference_params(), cell.reference_drivers()
    drv = {f: jnp.asarray(rdrv[f]) for f in reference.DRIVER_FIELDS}

    def job(j, stop_at=None):
        low = [jax.device_get(reference.run_job(
            params, cell.rtt(lane), drv, cell.job_key(j, lane),
            dtype=jnp.bfloat16)) for lane in range(cell.lanes)]
        return ([compare.Answer.from_stats(s) for s in low],
                [{"succ": s.succ_steps, "attempts": s.attempts_steps,
                  "regret": s.regret_steps} for s in low], cell.T)

    entry.warm = lambda: None
    entry.job = job


@contextlib.contextmanager
def _patched(obj, name, wrap):
    orig = getattr(obj, name)
    setattr(obj, name, wrap(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def round_state_unchanged():
    """The fused round kernel hands back the routing state it was
    given: every step's rounds leave no trace in the bandit state."""
    from repro.kernels import ops

    def wrap(orig):
        def round_step(weights, cw, err, cooldown_until, in_pool, active,
                       lat_buf, ts_buf, ptr, r_buf, rts_buf, rptr, *a, **kw):
            out = orig(weights, cw, err, cooldown_until, in_pool, active,
                       lat_buf, ts_buf, ptr, r_buf, rts_buf, rptr, *a, **kw)
            return out._replace(
                weights=weights, cw=cw, err=err,
                cooldown_until=cooldown_until, in_pool=in_pool,
                lat_buf=lat_buf, ts_buf=ts_buf, ptr=ptr, r_buf=r_buf,
                rts_buf=rts_buf, rptr=rptr)
        return round_step

    return _patched(ops, "round_step", wrap)


def chunk_state_unchanged(entry):
    """Every chunk of a streamed job hands back the state it was given."""
    orig = entry.run_chunk

    def chunk(rtt, carry, t_idx, drv, keys):
        _, ys = orig(rtt, jax.tree.map(jnp.copy, carry), t_idx, drv, keys)
        return carry, ys

    entry.run_chunk = chunk


def half_batch():
    """Maintenance leaves out the upper half of the fleet: those
    proxies never recompute their estimates, pools or weights."""
    from repro.core import bandit as qb

    def wrap(orig):
        def maintenance_subset(state, params, rtt, t, player_idx):
            K = state.lat_buf.shape[0]
            keep = jnp.where(player_idx < K // 2, player_idx, K)
            return orig(state, params, rtt, t, keep)
        return maintenance_subset

    return _patched(qb, "maintenance_subset", wrap)


def altered_latency():
    """The first proxy's first request of every step is reported 100 ms
    slower than it was served, where the round produces it."""
    from repro.kernels import ops

    def wrap(orig):
        def round_step(*a, **kw):
            out = orig(*a, **kw)
            return out._replace(lats=out.lats.at[0, 0].add(0.1))
        return round_step

    return _patched(ops, "round_step", wrap)


def altered_reward():
    """The first proxy's first request of every step is scored the other
    way where the step folds its outcomes into the job's answer."""
    from repro.continuum import metrics as qm

    def wrap(orig):
        def update_accumulator(acc, *, rewards, **kw):
            return orig(acc, rewards=rewards.at[0, 0].set(1 - rewards[0, 0]),
                        **kw)
        return update_accumulator

    return _patched(qm, "update_accumulator", wrap)


def altered_count(entry):
    """One request too many is reported for the first proxy's first
    client slot of every job, where the job's answer is read out."""
    orig = entry.run

    def run(*args, **kw):
        accs, series, done = orig(*args, **kw)
        a = accs[0]
        n = np.array(a.n_kc, copy=True)
        n[0, 0] += 1
        accs[0] = a._replace(n_kc=n)
        return accs, series, done

    entry.run = run
