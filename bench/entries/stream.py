"""Streaming entry: one simulation per job, driven in chunks with the
carry donated, as ``run_sim_stream`` drives it.

The programs come from the library's ``build_sim_chunks`` and are
compiled once, in set-up. A job is: init from its key, the chunks in
order, the per-step series drained to the host, and the accumulator
read to the host. One chunk stays queued behind the one the host waits
for, so the device never waits on the host between chunks, and a job
can end at the first chunk drained after ``stop_at``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


class Entry:
    def __init__(self, cell):
        from repro.continuum import build_sim_chunks
        from repro.continuum import scenarios as qs

        init_fn, chunk_fn = build_sim_chunks(
            cell.mix["strategy"], cell.sim_config(), cell.K, cell.M,
            warmup_steps=cell.warmup, **cell.strategy_kwargs())
        self.init = jax.jit(init_fn)
        self.run_chunk = jax.jit(chunk_fn, donate_argnums=(1,))
        T, ch = cell.T, cell.chunk
        self.slices = [(lo, min(lo + ch, T)) for lo in range(0, T, ch)]
        self._slice = qs.slice_drivers
        self.load(cell)

    def load(self, cell):
        """The inputs of ``cell``: same shapes, so the programs stay."""
        self.cell = cell
        self.rtt = cell.rtt()
        self.drivers = cell.program_drivers()
        self.xs = [(jnp.arange(lo, hi), self._slice(self.drivers, lo, hi))
                   for lo, hi in self.slices]

    def lowered_texts(self) -> list[str]:
        """The lowered text of every program a job runs, one per shape."""
        carry, keys = jax.eval_shape(self.init, self.rtt,
                                     self.drivers.active[0],
                                     self.cell.warm_key())
        texts = [self.init.lower(self.rtt, self.drivers.active[0],
                                 self.cell.warm_key()).as_text()]
        seen = set()
        for (lo, hi), (t_idx, drv) in zip(self.slices, self.xs):
            if hi - lo in seen:
                continue
            seen.add(hi - lo)
            ks = jax.ShapeDtypeStruct((hi - lo,) + keys.shape[1:], keys.dtype)
            texts.append(self.run_chunk.lower(self.rtt, carry, t_idx, drv,
                                              ks).as_text())
        return texts

    def run(self, key, stop_at=None):
        """One job, or its chunks up to the first one drained after
        ``stop_at`` (a ``time.perf_counter`` reading). Returns
        ``([accumulator], [series], steps)`` on the host."""
        with jax.profiler.TraceAnnotation("bench.init"):
            carry, keys = self.init(self.rtt, self.drivers.active[0], key)
        parts, steps = [], 0
        for (lo, hi), (t_idx, drv) in zip(self.slices, self.xs):
            with jax.profiler.TraceAnnotation("bench.chunk_dispatch"):
                carry, ys = self.run_chunk(self.rtt, carry, t_idx, drv,
                                           keys[lo:hi])
            parts.append(ys)
            steps = hi
            if len(parts) > 1:
                with jax.profiler.TraceAnnotation("bench.chunk_wait"):
                    parts[-2].regret.block_until_ready()
                if stop_at is not None and time.perf_counter() >= stop_at:
                    break
        with jax.profiler.TraceAnnotation("bench.drain"):
            parts = jax.device_get(parts)
        with jax.profiler.TraceAnnotation("bench.readout"):
            acc = jax.device_get(carry[3])
        series = {f: np.concatenate([np.asarray(getattr(p, f)) for p in parts])
                  for f in parts[0]._fields}
        return [acc], [series], steps

    def warm(self):
        """Compile and run init and one chunk of every length a job
        uses, not a whole job."""
        carry, keys = self.init(self.rtt, self.drivers.active[0],
                                self.cell.warm_key())
        seen = set()
        for (lo, hi), (t_idx, drv) in zip(self.slices, self.xs):
            keys[lo:hi].block_until_ready()     # each slice compiles once
            if hi - lo not in seen:
                seen.add(hi - lo)
                carry, ys = self.run_chunk(self.rtt, carry, t_idx, drv,
                                           keys[lo:hi])
        jax.device_get((ys, carry[3]))

    def job(self, j: int, stop_at=None):
        return self.run(self.cell.job_key(j), stop_at)

    def close(self):
        self.rtt = self.drivers = self.xs = None
