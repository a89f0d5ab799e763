"""Named phases of the simulator: the step's named scopes only add op
metadata (the compiled program is otherwise the one built without
them), every phase a configuration turns on is named in its
program, and the streaming driver's host spans land in a profiler
trace in the order the driver runs them."""
import contextlib
import glob
import os
import re
import sys

import jax
import pytest

from repro.continuum import (SimConfig, TenancyConfig, build_sim_chunks,
                             compile_scenario, get_library)
from repro.continuum import scenarios as qs
from repro.continuum.control import ControlConfig
from repro.obs import RecorderConfig
from repro.obs.trace import STEP_PHASES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_META = re.compile(r', metadata=\{(?:[^{}"]|"[^"]*")*\}')
_FRAME_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(.+\n)*\n",
    re.M)


def _strip(hlo: str) -> str:
    """Compiled HLO text without op metadata or the source-frame tables
    the metadata points into."""
    return _FRAME_TABLES.sub("", _META.sub("", hlo))


def _retry_storm():
    """The benchmark's ``testbed-retry_storm`` chunk program."""
    from bench.cell import Cell
    cell = Cell("testbed-retry_storm", 7)
    drv = cell.program_drivers()
    args = (cell.rtt(), drv, cell.chunk, cell.warm_key())
    return (cell.mix["strategy"], cell.sim_config(), cell.K, cell.M,
            dict(warmup_steps=cell.warmup, **cell.strategy_kwargs()), args)


def _small(**cfg_kw):
    def build():
        cfg = SimConfig(horizon=3.0, **cfg_kw)
        K, M = (30, 10) if cfg.tenancy is None else (8, 3)
        rtt = jax.random.uniform(jax.random.PRNGKey(1), (K, M),
                                 minval=0.005, maxval=0.05)
        if cfg.tenancy is None:
            drv = qs.neutral_drivers(cfg, K, M)
        else:
            drv = qs.tenant_neutral_drivers(cfg, cfg.tenancy.S, K, M)
        return ("qedgeproxy", cfg, K, M, {}, (rtt, drv, 10,
                                               jax.random.PRNGKey(2)))
    return build


PROGRAMS = {
    # resilience on: the per-round attempt scan, breakers, the rings
    "testbed-retry_storm": (_retry_storm, {
        "sim.placement", "sim.maintenance", "sim.true_mu", "sim.rounds",
        "sim.rings", "sim.accumulator"}),
    # the paper testbed's size with neutral drivers: the fused round
    "neutral_fused_k30_m10": (_small(), {
        "sim.placement", "sim.maintenance", "sim.true_mu", "sim.rounds",
        "sim.accumulator"}),
    # the closed loop and the flight recorder
    "control_recorder": (_small(
        control=ControlConfig(managed=2, admit=True),
        recorder=RecorderConfig(capacity=64)),
        set(STEP_PHASES) - {"sim.rings"}),
    # two services on one fleet: the tenant engine's own step
    "tenants": (_small(tenancy=TenancyConfig(taus=(0.08, 0.15))), {
        "sim.placement", "sim.maintenance", "sim.true_mu", "sim.rounds",
        "sim.rings", "sim.accumulator"}),
}


def _chunk_program(build):
    strategy, cfg, K, M, kw, (rtt, drv, n, key) = build()
    init_fn, chunk_fn = build_sim_chunks(strategy, cfg, K, M, **kw)
    carry, keys = jax.eval_shape(init_fn, rtt, drv.active[0], key)
    sl = qs.slice_drivers(drv, 0, n)
    ks = jax.ShapeDtypeStruct((n,) + keys.shape[1:], keys.dtype)
    return jax.jit(chunk_fn).lower(rtt, carry, jax.numpy.arange(n), sl, ks)


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_scopes_change_only_metadata(name, monkeypatch):
    build, phases = PROGRAMS[name]
    lowered = _chunk_program(build)
    # the lowered program's locations name each scope as a component
    text = lowered.as_text(debug_info=True)
    named = {p for p in STEP_PHASES
             if re.search(rf'"(?:[^"/]*/)*{re.escape(p)}[/"]', text)}
    assert named == phases, f"{name}: phases named {sorted(named)}"
    scoped = lowered.compile().as_text()
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda _: contextlib.nullcontext())
        plain = _chunk_program(build).compile().as_text()
    assert "sim." not in plain
    assert _strip(scoped) == _strip(plain)


def test_stream_host_spans_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    from repro.continuum import make_topology, run_sim_stream
    from repro.obs.trace import span

    K, M = 30, 10
    cfg = SimConfig(horizon=3.0)                 # 30 steps: 3 chunks of 10
    rtt = make_topology(jax.random.PRNGKey(1), K, M).lb_instance_rtt()
    drv = compile_scenario(get_library(cfg.horizon, K, M)["retry_storm"],
                           cfg, jax.random.PRNGKey(7))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        with span("test.window"):
            out = run_sim_stream("qedgeproxy", rtt, cfg,
                                 jax.random.PRNGKey(3), drivers=drv,
                                 chunk_steps=10,
                                 checkpoint_dir=str(tmp_path / "ckpt"))
            jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                     recursive=True)[-1]
    lines = []                  # one per host thread: [(start, end, name)]
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            lines += [sorted((e.start_ns, e.end_ns, e.name)
                             for e in line.events
                             if e.name.startswith(("sim.", "test.")))
                      for line in plane.lines]
    evs = [e for line in lines for e in line]
    (lo, hi, _), = [e for e in evs if e[2] == "test.window"]
    assert all(lo <= s <= e <= hi for s, e, _ in evs)
    main, = [line for line in lines if line and line[0][2] == "test.window"]
    assert [n for _, _, n in main] == [
        "test.window", "sim.init",
        "sim.chunk_dispatch", "sim.checkpoint_save", "sim.drain",
        "sim.chunk_dispatch", "sim.checkpoint_save", "sim.drain",
        "sim.chunk_dispatch", "sim.drain", "sim.checkpoint_wait"]
    # a checkpoint's snapshot drains the chunks before it
    for (s0, e0, n0), (s1, e1, n1) in zip(main, main[1:]):
        if n0 == "sim.checkpoint_save":
            assert n1 == "sim.drain" and s0 <= s1 <= e1 <= e0
    # the writes run on the checkpointer's own thread, each after the
    # snapshot that hands it the arrays
    saves = sorted(e for e in evs if e[2] == "sim.checkpoint_save")
    writes = sorted(e for e in evs if e[2] == "sim.checkpoint_write")
    assert len(writes) == 2
    assert all(w[0] >= s[0] for s, w in zip(saves, writes))
    assert not set(writes) & set(main)
