"""Grid entry: the evaluation grid's lanes, vmapped into one program
built by the library's ``build_sim_grid_fn``, one call per job.

Each lane is one seed of the suite: its own topology, scenario and run
key. A job is one call over every lane, its outputs read to the host.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class Entry:
    def __init__(self, cell):
        from repro.continuum import build_sim_grid_fn, stack_drivers
        from repro.launch.mesh import make_grid_mesh

        run_grid, _ = build_sim_grid_fn(
            cell.mix["strategy"], cell.sim_config(), cell.K, cell.M,
            mesh=make_grid_mesh(jax.devices()[:cell.chips]),
            warmup_steps=cell.warmup, **cell.strategy_kwargs())
        self.run_grid = jax.jit(run_grid)
        self._stack = stack_drivers
        self.load(cell)

    def load(self, cell):
        """The inputs of ``cell``: same shapes, so the program stays."""
        self.cell = cell
        lanes = range(cell.lanes)
        self.rtts = jnp.stack([cell.rtt(lane) for lane in lanes])
        self.drivers = self._stack([cell.program_drivers(lane)
                                    for lane in lanes])

    def keys(self, key_fn):
        return jnp.stack([key_fn(lane) for lane in range(self.cell.lanes)])

    def lowered_texts(self) -> list[str]:
        return [self.run_grid.lower(self.rtts, self.drivers,
                                    self.keys(self.cell.warm_key)).as_text()]

    def run(self, keys):
        """One job over every lane: ``(accumulators, series, steps)``
        on the host, ``steps`` per lane."""
        with jax.profiler.TraceAnnotation("bench.grid_dispatch"):
            out = self.run_grid(self.rtts, self.drivers, keys)
        with jax.profiler.TraceAnnotation("bench.readout"):
            out = jax.device_get(out)
        accs = [jax.tree.map(lambda x, i=i: np.asarray(x)[i], out.acc)
                for i in range(self.cell.lanes)]
        series = [{f: np.asarray(getattr(out.series, f))[i]
                   for f in out.series._fields}
                  for i in range(self.cell.lanes)]
        return accs, series, self.cell.T

    def warm(self):
        self.run(self.keys(self.cell.warm_key))

    def job(self, j: int, stop_at=None):
        """A job is one call, so it always runs whole."""
        return self.run(self.keys(lambda lane: self.cell.job_key(j, lane)))

    def close(self):
        self.rtts = self.drivers = None
