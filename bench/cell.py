"""One benchmark cell: its configuration and mix files, resolved into
the shapes, keys and inputs both the program and the reference use.

Files are found by name: ``BENCHMARK.json`` names the cell's config and
traffic; ``bench/configs/<config>.json`` holds the deployment and
``bench/mixes/<traffic>.json`` the mix; ``bench/entries/<entry>.py``
holds the job loop the mix asks for; ``bench/limits/<cell>.json``
holds the correctness check: the steps of a job compared step by step
and the limit of every number compared.

Every input comes from ``--seed``: the topology of lane ``l`` from
``(seed, 1, l)``, the scenario key from ``(seed, 2, l)``, job ``j``'s
run key from ``(seed, 3, j, l)`` and the warm-up job's from
``(seed, 4, l)``.
"""
from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

# SimConfig fields a config or mix file may set
SIM_FIELDS = ("dt", "maint_every", "max_clients", "service_time",
              "proc_sigma", "tau", "rho", "window", "ring", "reward_ring")
RESILIENCE_FIELDS = ("attempt_timeout", "max_retries", "retry_backoff",
                     "retry_deadline", "breaker_threshold",
                     "breaker_cooldown")
BANDIT_FIELDS = ("gamma", "eta", "err_thresh", "cooldown", "min_bandwidth")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def manifest() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def workload(name: str) -> dict:
    for w in manifest()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    import jax
    if seed < 0 or seed >= 1 << 64:
        raise SystemExit(f"--seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


class Cell:
    """``overrides`` replace config or mix keys, and ``entry`` stands in
    for the cell's ``BENCHMARK.json`` entry; only tests pass them, to
    run a cell at a size a CPU can hold, or one kept out of the
    manifest."""

    def __init__(self, name: str, seed: int, overrides: dict | None = None,
                 entry: dict | None = None):
        self.workload = entry or workload(name)
        self.name = name
        self.seed = seed
        ov = dict(overrides or {})
        self.config = load_json(BENCH, "configs",
                                self.workload["config"] + ".json")
        self.mix = load_json(BENCH, "mixes",
                             self.workload["traffic"] + ".json")
        for src in (self.config, self.mix):
            for k in list(ov):
                if k in src:
                    src[k] = ov.pop(k)
        if ov:
            raise ValueError(f"unknown override keys {sorted(ov)}")
        check = load_json(BENCH, "limits", name + ".json")
        self.prefix_steps = int(check["prefix_steps"])
        self.limits = check["limits"]
        self.chips = int(self.workload["chips"])
        self.K, self.M = int(self.config["K"]), int(self.config["M"])
        self.lanes = int(self.mix.get("lanes", 1))
        self.dt = float(self.config["dt"])
        self.horizon = float(self.mix["horizon_s"])
        self.T = int(round(self.horizon / self.dt))
        self.warmup = int(round(self.mix["warmup_s"] / self.dt))
        self.chunk = int(self.mix.get("chunk_steps") or self.T)

    # --- keys ------------------------------------------------------------
    def key(self, *path: int):
        import jax
        k = seed_key(self.seed)
        for p in path:
            k = jax.random.fold_in(k, p)
        return k

    def job_key(self, job: int, lane: int = 0):
        return self.key(3, job, lane)

    def warm_key(self, lane: int = 0):
        return self.key(4, lane)

    # --- the program's inputs ---------------------------------------------
    def sim_config(self):
        from repro.continuum import SimConfig
        res = self.mix.get("resilience") or {}
        return SimConfig(
            horizon=self.horizon,
            **{f: self.config[f] for f in SIM_FIELDS},
            **{f: res[f] for f in RESILIENCE_FIELDS if f in res})

    def strategy_kwargs(self) -> dict:
        return {f: self.config[f] for f in BANDIT_FIELDS}

    def rtt(self, lane: int = 0):
        from bench import topology
        return topology.lb_instance_rtt(self.key(1, lane), self.K, self.M,
                                        self.config.get("topology", "dense"))

    def program_drivers(self, lane: int = 0):
        from bench import traffic
        return traffic.program_drivers(self.mix, self.sim_config(), self.K,
                                       self.M, self.key(2, lane))

    # --- the reference's inputs -------------------------------------------
    def reference_drivers(self) -> dict:
        from bench import traffic
        c = self.config
        return traffic.reference_drivers(self.mix, self.horizon, self.dt,
                                         c["max_clients"], c["service_time"],
                                         self.K, self.M)

    def reference_params(self):
        from bench import reference
        c, res = self.config, self.mix.get("resilience") or {}
        return reference.Params(
            K=self.K, M=self.M, C=c["max_clients"], R=c["ring"],
            Rq=c["reward_ring"], T=self.T, warmup=self.warmup, dt=self.dt,
            maint_every=c["maint_every"], sigma=c["proc_sigma"],
            tau=c["tau"], rho=c["rho"], window=c["window"],
            gamma=c["gamma"], eta=c["eta"], err_thresh=c["err_thresh"],
            cooldown=c["cooldown"], min_bw=c["min_bandwidth"],
            attempt_timeout=res.get("attempt_timeout", 0.0),
            max_retries=res.get("max_retries", 0),
            retry_backoff=res.get("retry_backoff", 0.0),
            retry_deadline=res.get("retry_deadline", True),
            breaker_threshold=res.get("breaker_threshold", 0),
            breaker_cooldown=res.get("breaker_cooldown", 0.0))
