"""The chip benchmark of the QEdgeProxy fleet simulator.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. One run builds the cell named in
``BENCHMARK.json`` from its files (``bench/cell.py`` says which), warms
up every program the cell's jobs use, then runs back-to-back jobs for
``--seconds``: each job is one fixed-horizon evaluation a user would
submit, from a key derived from (``--seed``, job index). The window
ends at the first chunk drained after ``--seconds`` (a grid job is one
chunk), but never inside the first job, so that the check has a whole
job to compare; the steps of a job cut there count, to the time they
drained.

``--trace 0`` reports the end-to-end metrics: ``steps_per_s``
(simulated fleet steps drained to the host over the window's elapsed
time; a grid of L lanes counts L per step) and ``setup_s`` (process
start to the first timed step, compilation included). ``--trace 1``
runs the same loop with a profiler trace of its second job, whole,
and reports the per-layer metrics that
``bench/metrics/<name>.py`` read from that trace.

After the window, every job's issued-request count is checked exactly
against the drivers, the program's lowered drivers against the
benchmark's own, and one job drawn from the seed against the plain
reference (``bench/reference.py``) run on the same inputs, step by
step and over the whole job (``check``, ``bench/compare.py``); that
decides ``correct``. The last line of
stdout is one JSON object; the numbers compared, each with its limit,
are the last lines of stderr.

It exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell asks for, when ``REPRO_KERNEL_MODE`` asks for
anything but ``auto`` or ``pallas``, when a cell whose mix runs a
kernel has a program without one, or when the program is missing.
"""
from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Refused(Exception):
    """The run cannot measure what the cell asks for."""


def process_start_wall() -> float:
    """Wall-clock time the process started (Linux), else this module's
    import time."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = float(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return _T_IMPORT


class CompileStats:
    """Backend compiles and persistent-cache hits and misses, from JAX's
    monitoring events. One instance per process (``get``): JAX keeps
    every listener it is given."""

    _one = None

    @classmethod
    def get(cls) -> "CompileStats":
        if cls._one is None:
            import jax
            cls._one = cls()
            jax.monitoring.register_event_listener(cls._one._event)
            jax.monitoring.register_event_duration_secs_listener(
                cls._one._duration)
        cls._one.reset()
        return cls._one

    def __init__(self):
        self.reset()

    def reset(self):
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def line(self) -> str:
        return (f"compiles={self.compiles} compile_s={self.seconds} "
                f"cache_hits={self.hits} cache_misses={self.misses}")


def enable_compile_cache(jax) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache`` (a fixed path, since the
    path is part of every cache key)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def log(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class Tracer:
    """A profiler trace of one whole job of the window, its second (the
    first may still warm caches), run by the same loop as the timed
    jobs; ``steps`` counts its lane-steps. A job's steps are fixed, so
    the trace's size, and the time to write and read it, are too."""

    def __init__(self, jax, trace_dir: str):
        self.jax, self.dir = jax, trace_dir
        self.steps, self.on, self.done, self.span = 0, False, False, None

    def before_job(self, j: int):
        if j != 1:
            return
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.span = self.jax.profiler.TraceAnnotation("bench.traced_jobs")
        self.span.__enter__()
        self.on = True

    def after_job(self, lane_steps: int):
        if not self.on:
            return
        self.steps = lane_steps
        self.span.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        self.on, self.done = False, True


def per_layer_metrics(manifest: dict, cell_name: str, view) -> dict:
    """Every per-layer metric that applies to this cell and that its
    reader found something to read for."""
    out = {}
    for m in manifest["per_layer"]:
        if cell_name not in m.get("workloads", [cell_name]):
            continue
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, overrides: dict | None = None,
             hooks=None, keep_trace: str | None = None,
             entry: dict | None = None) -> dict:
    """One run of one cell; returns the result object.

    ``require_tpu=False``, ``overrides``, ``entry`` and ``hooks`` are
    for the CPU tests only: they let a run proceed on the CPU at a small
    size, name a cell kept out of ``BENCHMARK.json``, and let a test
    break the timed path (``hooks(loop)``, the entry's job loop) to see
    the check fail; ``keep_trace`` keeps the raw trace in that
    directory for a look by hand. The command line never sets them."""
    mode = os.environ.get("REPRO_KERNEL_MODE", "auto")
    if require_tpu and mode not in ("auto", "pallas"):
        raise Refused(f"REPRO_KERNEL_MODE={mode}: the benchmark runs "
                      "compiled kernels only (auto or pallas)")
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    from bench import cell as bcell
    from bench import compare
    from bench import trace as btrace

    # the CPU tests leave JAX's cache settings as they found them
    cache_dir = enable_compile_cache(jax) if require_tpu else "off"
    manifest = bcell.manifest()
    cell = bcell.Cell(name, seed, overrides, entry)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < cell.chips:
        raise Refused(f"the cell asks for {cell.chips} chips, JAX found "
                      f"{len(devs)}")
    peaks = btrace.peaks_for(devs[0].device_kind) if require_tpu else None
    log(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__} cache={cache_dir}")
    stats = CompileStats.get()

    loop = importlib.import_module(
        f"bench.entries.{cell.mix['entry']}").Entry(cell)
    if hooks is not None:
        hooks(loop)
    kernels = cell.mix.get("kernels", [])
    if require_tpu and kernels:
        calls = sum(t.count("tpu_custom_call") for t in loop.lowered_texts())
        if calls < len(kernels):
            raise Refused(f"the mix runs kernels {kernels}, the programs "
                          f"hold {calls} tpu_custom_call: a kernel fell "
                          "back to its oracle")
    loop.warm()
    setup_s = time.time() - process_start_wall()
    log(f"setup_s={setup_s} {stats.line()}")

    # --- the measured window ------------------------------------------
    stats.reset()
    tracer = None
    if trace:
        tracer = Tracer(jax, keep_trace or tempfile.mkdtemp(
            prefix="bench-trace-"))
    jobs = []           # (accumulators, series, steps per lane, seconds)
    t0 = time.perf_counter()
    stop_at = t0 + seconds
    while True:
        if tracer:
            tracer.before_job(len(jobs))
        t_job = time.perf_counter()
        whole = not jobs or (tracer and tracer.on)
        accs, series, done = loop.job(len(jobs), None if whole else stop_at)
        jobs.append((accs, series, done, time.perf_counter() - t_job))
        if tracer:
            tracer.after_job(done * cell.lanes)
        if time.perf_counter() >= stop_at and not (tracer and
                                                   not tracer.done):
            break
    elapsed = time.perf_counter() - t0
    steps = sum(j[2] for j in jobs) * cell.lanes
    log(f"window jobs={len(jobs)} elapsed_s={elapsed} steps={steps} "
        f"job_s={[round(j[3], 4) for j in jobs]} in-window {stats.line()}")
    mem = [d.memory_stats() or {} for d in devs[:cell.chips]]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    loop.close()
    del loop
    gc.collect()

    # --- correctness ----------------------------------------------------
    t_ref = time.perf_counter()
    numbers = check(cell, jobs, seed)
    ok, rows = compare.judge(numbers, cell.limits)
    log(f"reference ref_s={time.perf_counter() - t_ref} all numbers "
        + " ".join(f"{k}={v}" for k, v in numbers.items()))
    failed_jobs = numbers["jobs_failed"]

    metrics, device = {}, {"platform": devs[0].platform,
                           "kind": devs[0].device_kind, "count": len(devs),
                           "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        view = btrace.TraceView(tracer.dir, cell, tracer.steps, peaks,
                                chips=cell.chips)
        device.update(busy_s=view.busy_s, window_s=view.window_s)
        metrics = per_layer_metrics(manifest, name, view)
        breakdown = view.breakdown()
        if not keep_trace:
            shutil.rmtree(tracer.dir, ignore_errors=True)
    else:
        metrics = {
            "steps_per_s": {"value": steps / elapsed, "unit": "steps/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result = {"correct": bool(ok),
              "attempted": len(jobs),
              "failed": int(failed_jobs) + (0 if ok else 1),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    return result


def check(cell, jobs: list, seed: int) -> dict:
    """The numbers that decide ``correct``. Every job: its issued
    requests against the drivers, exactly, and finite statistics. The
    drivers the program lowered against the benchmark's own. One job
    drawn from the seed, on every lane, against the plain reference run
    on the same inputs: over the first ``cell.prefix_steps`` steps
    (``compare.prefix_numbers``), and over the whole job where the
    cell's limits name a whole-job number (``compare.job_numbers``)."""
    import jax
    import numpy as np

    from bench import compare, reference, traffic

    ref_drv = cell.reference_drivers()
    n_drv = ref_drv["n_clients"]
    numbers = {"drivers": max(
        traffic.drivers_gap(cell.program_drivers(lane), ref_drv)
        for lane in range(cell.lanes))}
    failed = 0
    for accs, _, done, _ in jobs:
        expected = float(n_drv[cell.warmup:done].sum())
        failed += any(float(np.sum(a.n_kc)) != expected
                      or not all(np.all(np.isfinite(np.asarray(x)))
                                 for x in a)
                      for a in accs)
    numbers["jobs_failed"] = float(failed)

    n = min(cell.prefix_steps, cell.T)
    whole = any(k.startswith("job_") for k in cell.limits)
    need = cell.T if whole else n
    full = [j for j, job in enumerate(jobs) if job[2] >= need]
    if not full:
        return {**numbers, **{k: math.inf for k in cell.limits
                              if k not in numbers}}
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
    sample = full[int(rng.integers(len(full)))]
    params = cell.reference_params()
    drv = {f: jax.numpy.asarray(ref_drv[f]) for f in reference.DRIVER_FIELDS}
    rtts = jax.numpy.stack([cell.rtt(lane) for lane in range(cell.lanes)])
    keys = jax.numpy.stack([cell.job_key(sample, lane)
                            for lane in range(cell.lanes)])
    refs = jax.device_get(jax.vmap(
        lambda r, k: reference.run_job(params, r, drv, k,
                                       steps=None if whole else n))(
        rtts, keys))
    per_lane = []
    for lane in range(cell.lanes):
        ref = jax.tree.map(lambda x, i=lane: x[i], refs)
        got = compare.prefix_numbers(jobs[sample][1][lane], ref, cell.K, n)
        if whole:
            got.update(compare.job_numbers(jobs[sample][0][lane], ref))
        per_lane.append(got)
    log(f"reference job={sample} lanes={cell.lanes} steps={need}")
    return {**numbers, **compare.widest(per_lane)}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        log(f"refused: {e}")
        sys.exit(2)
    for name, c in result["checks"].items():
        print(f"check {name} value={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
