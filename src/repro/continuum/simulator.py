"""Discrete-time CC simulator (paper §VII testbed, fully on-device).

Time advances in steps of ``dt`` (default 100 ms = one client period, so
every client issues exactly one request per step, matching the paper's
10 req/s PilotNet clients). Within a step, requests are issued in
*rounds* (round r = client r of every LB) so that same-round requests
from different LBs collide on instance queues — the paper's "implicit
collisions".

Instance model: single-worker queue. A request arriving when the queue
holds q requests observes processing latency ``(q+1) * s_m * Z`` with
``Z ~ LogNormal(0, sigma^2)``; the queue drains at ``dt / s_m`` requests
per step. End-to-end latency is ``rtt[k,m] + proc`` (client↔LB latency
is negligible per §IV-A; RTTs are fixed Istio-style injected delays).

The *true* per-arm success probability used for oracle regret has the
closed form ``mu = Phi(ln((tau - rtt)/((q+1) s_m)) / sigma)``.

The whole horizon runs in one ``lax.scan``; strategies are closures
chosen at trace time (QEdgeProxy / proxy-mity / Dec-SARSA).

Engine structure (streaming-first):

* **Rounds are a ``lax.scan``**, not a Python unroll: the round body is
  traced/compiled once instead of C times, which is most of the old
  compile wall. Selection, the queue recursion and the cheap (K, M)
  feedback control stay interleaved round by round, so an in-step trip
  steers the remaining rounds exactly as before; with the fused request
  path the expensive (K, M, R)/(K, Rq) ring writes are still deferred
  into ONE dense ``record_rings_batch`` write per step (its rank/offset
  arithmetic is round-order-free; a masked select, not a scatter, which
  a TPU would apply one index at a time). Fused and sequential paths remain
  bit-for-bit identical (tests/test_bandit_batch.py).
* **Metrics stream by default-capable mode**: with ``trace=False`` the
  scan carries a ``MetricAccumulator`` (O(K·M) sufficient statistics
  for Figs 3-9 + regret + variation budget) and emits only O(T) scalar
  ``StepSeries`` — memory is O(K·M), independent of the horizon.
  ``trace=True`` is the explicit debug mode that materializes the full
  (T, K, C)/(T, K, M) ``SimOutputs`` trajectories as before.
* **Donated inputs / chunked horizons**: ``run_sim``/``run_sim_batch``
  donate the O(T) input buffers (n_clients, active, key) to XLA, and
  ``run_sim_stream(chunk_steps=...)`` drives the scan in fixed-size
  time chunks with a donated carry, so arbitrarily long horizons run
  in bounded device memory.
* Maintenance runs on a fixed-size player group per step (balanced
  staggered clocks), so the O(K·M·R) estimate is paid for ~K/H_d
  players instead of all K.
* **Scenarios drive every run**: the engine consumes a ``Drivers``
  pytree of dense per-step schedules (client counts, instance
  liveness, factored RTT modulation, per-instance service times)
  compiled from a declarative event timeline
  (``repro.continuum.scenarios``; named library in
  ``repro.continuum.library``). Legacy ``n_clients``/``active``
  kwargs wrap into neutral drivers that reproduce the pre-scenario
  engine bit-for-bit; the streaming accumulator keys time-to-recover
  windows off the scenario's event marks.
* **The evaluation grid shards across devices**: scenario/seed lanes
  are independent simulations (the MP-MAB players never communicate,
  and neither do grid cells), so ``run_sim_grid`` /
  ``build_sim_grid_fn`` ``shard_map`` the vmapped scenario axis of a
  streaming run over the ``data`` mesh axis. Each device scans only
  its shard and carries its own O(K·M) accumulators; the host touches
  nothing until the (tiny) metric pytree is read. One real device
  falls back to the plain vmap — the exact same program ``get_suite``
  always ran.
* **The player axis shards *inside* one simulation**: the bandit state
  factorizes over players; the ONLY cross-player coupling is the
  instance-queue recursion (same-round requests from different LBs
  collide on shared (M,) queues). ``run_sim_players`` /
  ``build_sim_players_fn`` therefore ``shard_map`` a streaming run
  over the ``players`` axis of a 2-D (``data``, ``players``) mesh
  (``launch.mesh.make_continuum_mesh``): each device holds K/D
  players' rings/weights/KDE stats and maintenance groups, rounds
  ``psum`` the (M,) arrival vector before the shared queue drain, and
  the fleet-level accumulator fields are ``psum``-reduced once after
  the scan. Two engine invariants make the sharded schedule decompose
  exactly: every per-player random draw is keyed by global player id
  (``repro.core.prand``), and the staggered maintenance clocks assign
  phases per contiguous player *block* (``_stagger_groups``), so a
  shard's due-players are a static-shape shard-local gather. Sharded
  and unsharded runs match — counting statistics exactly, psum-reduced
  float series to f32 reassociation tolerance
  (tests/test_sharded_players.py); a 1-way player axis falls back to
  the plain streaming program. Composes with the grid axis:
  ``build_sim_grid_fn`` on a 2-D mesh shards lanes over ``data`` and
  every lane's players over ``players``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import warnings
from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.continuum import control as qc
from repro.continuum import metrics as qm
from repro.continuum import scenarios as qs
from repro.continuum import tenancy as qt
from repro.continuum.metrics import (MetricAccumulator, StepSeries,
                                     StreamOutputs)
from repro.continuum.scenarios import Drivers
from repro.core import bandit as qb
from repro.core import baselines as bl
from repro.core import prand
from repro.core.kde import normal_cdf
from repro.core.oracle import step_regret
from repro.kernels import ops as kernel_ops
from repro.obs import recorder as obr
from repro.obs.trace import STEP_PHASES, span

# the step's phases, each a ``jax.named_scope`` (repro.obs.trace)
(PH_CONTROL, PH_PLACEMENT, PH_MAINTENANCE, PH_TRUE_MU, PH_ROUNDS, PH_RINGS,
 PH_ACCUMULATOR, PH_RECORDER) = STEP_PHASES
# The phases live in op metadata, which JAX's persistent compilation
# cache leaves out of its key by default: an executable cached before
# the scopes changed would be loaded with the old names, and a profile
# would attribute its ops to them. Keying on metadata keeps them true.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


@dataclass(frozen=True)
class SimConfig:
    dt: float = 0.1                  # step length [s] = client period
    horizon: float = 300.0           # simulated seconds
    maint_every: int = 10            # QEdgeProxy decision interval H_d [steps]
    max_clients: int = 8             # per-LB client slots (rounds per step)
    service_time: float = 0.0055     # s_m: idle per-request processing [s]
    # 0.0055 keeps the system well-provisioned (paper §IV-A assumption:
    # an oracle allocation satisfying tau exists): 1200 req/s demand vs
    # ~1800 req/s capacity, but any 4-5+ LBs herding on one instance
    # still overload it — the proxy-mity failure mode.
    proc_sigma: float = 0.25         # lognormal sigma of processing noise
    tau: float = 0.080
    rho: float = 0.9
    window: float = 10.0
    ring: int = 64
    reward_ring: int = 512
    # Event-relative recovery windows (scenario engine): the streaming
    # accumulator keeps, per scenario event mark, one pre-event
    # baseline window of ev_pre seconds and ev_buckets consecutive
    # post-event buckets of ev_bucket seconds each (metrics.ev_succ).
    ev_pre: float = 10.0
    ev_bucket: float = 2.0
    ev_buckets: int = 30
    # --- request-lifecycle resilience (all off by default; the neutral
    # config traces the exact pre-resilience program). An attempt
    # exceeding ``attempt_timeout`` seconds is abandoned by the client
    # (the instance still does the work — the arrival is not recalled)
    # and observed only as a censored latency lower bound; with
    # ``max_retries`` > 0 it is retried on a re-selected instance after
    # exponential backoff (``retry_backoff * 2^(a-1)`` before attempt
    # a), as long as the elapsed budget stays inside the request's QoS
    # deadline tau (``retry_deadline=False`` drops that guard — the
    # naive retry policy that amplifies overload). ``breaker_threshold``
    # consecutive timeouts on one (player, arm) open an Envoy-style
    # circuit breaker for ``breaker_cooldown`` seconds; see
    # ``core.bandit.BreakerState``. ---
    attempt_timeout: float = 0.0     # per-attempt client timeout [s]; 0 = off
    max_retries: int = 0             # R: retry attempts after a timeout
    retry_backoff: float = 0.005     # base backoff [s] before attempt a >= 1
    retry_deadline: bool = True      # budget retries against tau (False = naive)
    breaker_threshold: int = 0       # consecutive timeouts to open; 0 = off
    breaker_cooldown: float = 2.0    # open -> half-open probe after this [s]
    # --- closed-loop control plane (reactive autoscaling, admission
    # shedding, capacity migration; ``repro.continuum.control``). None
    # or a neutral ControlConfig (``enabled == False``) traces the
    # byte-identical open-loop program — same parity discipline as the
    # resilience knobs above. ---
    control: "qc.ControlConfig | None" = None
    # --- flight recorder (repro.obs.recorder): a fixed-capacity ring
    # of structured (step, kind, entity, value) events in the scan
    # carry — breaker trips/resets, retry exhaustions, control actions,
    # scenario marks, QoS-miss spikes. None or a disabled
    # RecorderConfig traces the byte-identical program — same parity
    # discipline as the resilience and control knobs. ---
    recorder: "obr.RecorderConfig | None" = None
    # --- fused round megakernel (kernels/ops.round_step): collapse the
    # C-round scan body to one fused call with the bandit block's state
    # resident across rounds (VMEM on the Pallas path, an unrolled
    # XLA fusion on CPU). Bit-identical to the scan by construction
    # (tests/test_round_fused.py); auto-falls-back to the scan whenever
    # a feature needs the per-round structure (resilience attempts,
    # player sharding's per-round arrival psum, sequential strategies).
    fused_round: bool = True
    # --- multi-tenant continuum (repro.continuum.tenancy): S services
    # sharing the one instance fleet, each with its own QoS deadline
    # tau_s, client population and bandit fleet; the queue recursion
    # gains a leading (S,) service axis and cross-service interference
    # folds into the effective service row. ``None`` — or a degenerate
    # S=1 config — keeps the engine on the untouched single-service
    # path (byte-identical HLO; tests/test_tenancy.py). ---
    tenancy: "qt.TenancyConfig | None" = None

    @property
    def num_steps(self) -> int:
        return int(round(self.horizon / self.dt))

    @property
    def tenancy_on(self) -> bool:
        return qt.tenancy_enabled(self)

    @property
    def resilience_on(self) -> bool:
        return self.attempt_timeout > 0.0

    @property
    def control_on(self) -> bool:
        return qc.control_enabled(self)

    @property
    def recorder_on(self) -> bool:
        return obr.recorder_enabled(self)


class PlayerSharding(NamedTuple):
    """Static spec: split the (K,) player axis over mesh axis ``axis``.

    Passed to ``build_sim_parts``/``build_sim_fn`` when the returned
    program will run *inside* a ``shard_map`` whose mesh carries
    ``axis`` with ``shards`` devices. The traced program then works on
    K/shards players, keys randomness and maintenance clocks by global
    player id, and ``psum``s the per-round arrival vector over
    ``axis``. ``build_sim_players_fn``/``build_sim_grid_fn`` construct
    this; it is exposed for harnesses that wrap the run themselves.
    """
    axis: str
    shards: int


class SimOutputs(NamedTuple):
    """Per-step trajectories (leading axis T) — ``trace=True`` only."""
    rewards: jax.Array      # (T, K, C) 1/0 QoS success per client slot
    issued: jax.Array       # (T, K, C) request-issued mask
    choices: jax.Array      # (T, K, C) selected instance
    latency: jax.Array      # (T, K, C) end-to-end latency
    proc_lat: jax.Array     # (T, K, C) processing component
    arrivals: jax.Array     # (T, M) requests per instance
    queue: jax.Array        # (T, M) queue length at step start
    weights: jax.Array      # (T, K, M) routing distribution
    true_mu: jax.Array      # (T, K, M) oracle success probabilities
    regret: jax.Array       # (T, K) per-step oracle regret
    eps: jax.Array          # (T, K) exploration rate (qedgeproxy) or 0
    attempts: jax.Array     # (T, K, C) attempts per request (1 + retries)
    dropped: jax.Array      # (T, K, C) deadline exhausted without completing


def _true_mu_tau(rtt, q, tau, sigma, service_time):
    """Closed-form P(rtt + (q+1) s Z <= tau), Z ~ LogNormal(0, sigma^2).

    Parameterized on the deadline so the multi-tenant engine can score
    each tenant against its own tau_s; ``_true_mu`` is the
    single-service view (identical traced ops)."""
    margin = (tau - rtt) / ((q[None, :] + 1.0) * service_time)
    safe = jnp.maximum(margin, 1e-9)
    mu = normal_cdf(jnp.log(safe) / sigma)
    return jnp.where(margin > 0, mu, 0.0)


def _true_mu(rtt, q, cfg: SimConfig, service_time):
    return _true_mu_tau(rtt, q, cfg.tau, cfg.proc_sigma, service_time)


# ---------------------------------------------------------------------------
# Strategy adapters: dicts of closures with a common signature.
#
# ``init``/``select`` take ``pids`` — the (K,) i32 *global* ids of the
# players this program instance holds (``arange(K)`` unsharded, the
# shard's slice under player sharding). Strategies key every per-player
# random draw off it (repro.core.prand), which is what makes a
# player-sharded run reproduce the unsharded stream bit-for-bit.
# ---------------------------------------------------------------------------

def qedgeproxy_strategy(params: qb.BanditParams, cfg: SimConfig, K: int, M: int):
    def init(rtt, active, key, pids):
        return qb.init_state(K, M, params, cfg.ring, cfg.reward_ring, active,
                             key=key, pids=pids)

    def select(state, key, t, active, pids):
        choice, state, valid = qb.select(state)
        return choice, state

    def record(state, choice, lat, t, mask):
        return qb.record(state, params, choice, lat, t, mask)

    def maintain(state, rtt, t, lb_mask=None):
        return qb.maintenance(state, params, rtt, t, lb_mask)

    def maintain_subset(state, rtt, t, player_idx):
        return qb.maintenance_subset(state, params, rtt, t, player_idx)

    def record_feedback(state, choice, lat, t, mask):
        return qb.record_feedback(state, params, choice, lat, t, mask)

    def record_rings(state, choices, lats, t, mask):
        return qb.record_rings_batch(state, params, choices, lats, t, mask)

    def on_activity(state, new_active, rtt, t):
        return qb.sync_active(state, params, new_active)

    def weights(state):
        return state.weights

    def eps(state):
        return state.eps

    def fused_round(state, q, nc, act, t, rtt_t, s_m, served, k_step, pids):
        # all C rounds in one fused call: the per-round PRNG stream is
        # batched up front (each element is exactly the draw the scan
        # makes — a pure function of (step key, round, player id)), and
        # kernels/ops.round_step replays selection, queue recursion,
        # feedback control and the ring scatter bit-identically.
        C = cfg.max_clients
        ks = jax.vmap(
            lambda r: jax.random.split(jax.random.fold_in(k_step, r))
        )(jnp.arange(C))
        z = jnp.exp(cfg.proc_sigma * jax.vmap(
            lambda kk: prand.player_normal(kk, pids))(ks[:, 1]))
        out = kernel_ops.round_step(
            state.weights, state.cw, state.err, state.cooldown_until,
            state.in_pool, state.active,
            state.lat_buf, state.ts_buf, state.ptr,
            state.r_buf, state.rts_buf, state.rptr,
            q, nc, z, rtt_t, s_m, served, t,
            tau=params.tau, err_thresh=params.err_thresh,
            cooldown=params.cooldown)
        state = state._replace(
            weights=out.weights, cw=out.cw, err=out.err,
            cooldown_until=out.cooldown_until, in_pool=out.in_pool,
            lat_buf=out.lat_buf, ts_buf=out.ts_buf, ptr=out.ptr,
            r_buf=out.r_buf, rts_buf=out.rts_buf, rptr=out.rptr)
        return state, out.q, out.arrivals, out.choices, out.lats, out.procs

    return dict(init=init, select=select, record=record, maintain=maintain,
                maintain_subset=maintain_subset,
                record_feedback=record_feedback, record_rings=record_rings,
                on_activity=on_activity, weights=weights, eps=eps,
                fused_round=fused_round)


def proxy_mity_strategy(alpha: float, cfg: SimConfig, K: int, M: int):
    """Static proximity weights; requests sampled i.i.d. from them
    (proxy-mity randomizes per request; there is no SWRR state).
    Selection keys come from the scan's per-round stream, so the state
    carries no PRNG key of its own."""

    class PMState(NamedTuple):
        weights: jax.Array

    def init(rtt, active, key, pids):
        return PMState(bl.proxy_mity_weights(rtt, alpha, active))

    def select(state, key, t, active, pids):
        # per-player categorical via argmax(logits + Gumbel), with the
        # Gumbel row keyed by global player id (shard-invariant)
        g = prand.player_gumbel(key, pids, M)
        choice = jnp.argmax(jnp.log(state.weights + 1e-30) + g, axis=-1)
        return choice, state

    def record(state, choice, lat, t, mask):
        return state

    def record_feedback(state, choice, lat, t, mask):
        return state                     # stateless per request

    def record_rings(state, choices, lats, t, mask):
        return state

    def maintain(state, rtt, t, lb_mask=None):
        return state                     # fixed at initialization (paper)

    def on_activity(state, new_active, rtt, t):
        return state._replace(weights=bl.proxy_mity_weights(rtt, alpha, new_active))

    def weights(state):
        return state.weights

    def eps(state):
        return jnp.zeros((K,), jnp.float32)

    def fused_round(state, q, nc, act, t, rtt_t, s_m, served, k_step, pids):
        # stateless selection from fixed weights: the batched Gumbel
        # rows reproduce the scan's per-round draws exactly, and the
        # scatter-free jnp path is already the fused form.
        C = cfg.max_clients
        ks = jax.vmap(
            lambda r: jax.random.split(jax.random.fold_in(k_step, r))
        )(jnp.arange(C))
        gum = jax.vmap(
            lambda kk: prand.player_gumbel(kk, pids, M))(ks[:, 0])
        z = jnp.exp(cfg.proc_sigma * jax.vmap(
            lambda kk: prand.player_normal(kk, pids))(ks[:, 1]))
        q, arrivals, choices, lats, procs = kernel_ops.round_step_gumbel(
            state.weights, q, nc, z, gum, rtt_t, s_m, served)
        return state, q, arrivals, choices, lats, procs

    return dict(init=init, select=select, record=record, maintain=maintain,
                record_feedback=record_feedback, record_rings=record_rings,
                on_activity=on_activity, weights=weights, eps=eps,
                fused_round=fused_round)


def dec_sarsa_strategy(params: bl.DecSarsaParams, cfg: SimConfig, K: int,
                       M: int, pshard: "PlayerSharding | None" = None):
    class DSState(NamedTuple):
        inner: bl.DecSarsaState
        active: jax.Array
        pend_s: jax.Array      # state bucket used for the pending action

    def init(rtt, active, key, pids):
        # the proximity-normalized optimistic Q init divides by the
        # GLOBAL rtt max — under player sharding that is a pmax over
        # the shards, the baseline's one cross-player reduction
        rtt_max = rtt.max()
        if pshard is not None:
            rtt_max = jax.lax.pmax(rtt_max, pshard.axis)
        return DSState(bl.decsarsa_init(K, M, rtt, params, rtt_max), active,
                       jnp.zeros((K,), jnp.int32))

    def select(state, key, t, active, pids):
        choice, s = bl.decsarsa_select(state.inner, params, active, key,
                                       pids)
        return choice, state._replace(pend_s=s, active=active)

    def record(state, choice, lat, t, mask):
        reward = (lat <= params.tau).astype(jnp.float32)
        inner = bl.decsarsa_update(
            state.inner, params, state.pend_s, choice, reward, lat, mask)
        return state._replace(inner=inner)

    def maintain(state, rtt, t, lb_mask=None):
        return state

    def on_activity(state, new_active, rtt, t):
        return state._replace(active=new_active)

    def weights(state):
        # effective eps-greedy distribution for regret accounting
        K_, S, M_ = state.inner.q.shape
        s = state.pend_s
        qs = state.inner.q[jnp.arange(K_), s]
        neg = jnp.finfo(qs.dtype).min
        qs = jnp.where(state.active[None, :], qs, neg)
        greedy = jax.nn.one_hot(jnp.argmax(qs, -1), M_)
        actf = state.active.astype(jnp.float32)[None, :]
        uni = actf / jnp.maximum(actf.sum(-1, keepdims=True), 1.0)
        e = state.inner.eps[:, None]
        return (1 - e) * greedy + e * uni

    def eps(state):
        return state.inner.eps

    return dict(init=init, select=select, record=record, maintain=maintain,
                on_activity=on_activity, weights=weights, eps=eps)


def make_strategy(name: str, cfg: SimConfig, K: int, M: int,
                  pshard: "PlayerSharding | None" = None, **kw):
    if name == "qedgeproxy":
        params = kw.get("params") or qb.BanditParams(
            tau=cfg.tau, rho=cfg.rho, window=cfg.window,
            **{k: v for k, v in kw.items() if k in qb.BanditParams._fields})
        return qedgeproxy_strategy(params, cfg, K, M)
    if name.startswith("proxy_mity"):
        return proxy_mity_strategy(kw.get("alpha", 1.0), cfg, K, M)
    if name == "dec_sarsa":
        params = kw.get("params") or bl.DecSarsaParams(tau=cfg.tau)
        return dec_sarsa_strategy(params, cfg, K, M, pshard)
    raise ValueError(f"unknown strategy {name!r}")


# ---------------------------------------------------------------------------
# Main simulation loop.
# ---------------------------------------------------------------------------

def _stagger_groups(k_phase, K_global: int, n_phases: int, width: int,
                    lo, K_local: int) -> jax.Array:
    """Balanced staggered maintenance clocks, shard-decomposable.

    Players tile into contiguous *blocks* of ``n_phases``; block ``b``
    assigns its members one phase each through a random bijection keyed
    by ``fold_in(k_phase, b)`` — a pure function of global player id,
    like every other per-player draw (``repro.core.prand``). Row ``p``
    of the result lists the LOCAL indices (player id − ``lo``) of the
    players due at phase ``p``, padded with the sentinel ``K_local``
    that the maintenance scatter drops. Each phase gets exactly one
    player per block, so per-step maintenance work stays balanced (±1
    for the padded last block) for any K and for any contiguous shard
    [lo, lo + K_local) of the player axis — which is what keeps the
    gathers shard-local with a static (n_phases, width) shape under
    ``shard_map``.
    """
    bids = lo // n_phases + jnp.arange(width)          # global block ids

    def block_slots(b):
        # inv[p] = the within-block slot whose player fires at phase p
        perm = jax.random.permutation(jax.random.fold_in(k_phase, b),
                                      n_phases)
        return jnp.argsort(perm)

    inv = jax.vmap(block_slots)(bids)                  # (W, n_phases)
    gplayer = bids[:, None] * n_phases + inv           # global player ids
    local = gplayer - lo
    ok = (gplayer < K_global) & (local >= 0) & (local < K_local)
    return jnp.where(ok, local, K_local).T.astype(jnp.int32)


def build_sim_parts(
    strategy_name: str,
    cfg: SimConfig,
    K: int,
    M: int,
    fused: bool = True,
    trace: bool = True,
    warmup_steps: int = 0,
    pshard: PlayerSharding | None = None,
    **strategy_kw,
):
    """The engine's two traceable halves, shared by every driver.

    Returns ``(init_fn, step_fn)``:

    * ``init_fn(rtt, active0, key, pids=None) -> (carry0, keys)`` —
      strategy state, empty queue/accumulator, the block-staggered
      maintenance groups, and the full-horizon (T, 2) per-step key
      array (small; chunk drivers slice it so chunking never replays
      or forks the PRNG stream). ``pids`` are the global ids of the
      players this program instance holds (defaulted to ``arange(K)``
      unsharded; required under ``pshard``).
    * ``step_fn(rtt, marks, carry, xs) -> (carry, ys)`` — one simulator
      step. ``xs = (t_idx, n_clients_t, active_t, rtt_scale_t,
      rtt_cut_k_t, rtt_cut_m_t, s_m_t, key_t, group_t)`` — one row of
      the scenario ``Drivers`` plus a *global* ``t_idx`` and the
      maintenance-group row due this step (pre-gathered from the
      stagger table by the horizon driver), so a chunked scan is
      bit-identical to one full-horizon scan. The step first forms
      the effective RTT ``rtt * rtt_scale[None, :] + min(cut_k[:,
      None], cut_m[None, :])`` and the (M,) service-time row, and
      threads them through placement events, maintenance, the true-mu
      oracle and the queue recursion; with neutral drivers (scale 1,
      cut 0, constant s_m) every computed float is unchanged from the
      pre-scenario engine. ``ys`` is a full ``SimOutputs`` row in
      trace mode, a ``StepSeries`` row otherwise. ``marks`` are the
      scenario's event-onset steps for the accumulator's recovery
      windows (ignored in trace mode).

    With ``pshard`` the returned halves are the *per-shard* program of
    a player-sharded run (streaming only): ``K`` is still the global
    player count, but every (K,) shape shrinks to K/shards, randomness
    and maintenance clocks key off the shard's global player ids, the
    round loop ``psum``s its (M,) arrival vector over ``pshard.axis``
    before the shared queue drain, and the accumulator's fleet-level
    fields hold shard-local partial sums (reduced once after the scan
    by ``build_sim_fn``). Both halves must then be traced inside a
    ``shard_map`` carrying that axis, and ``init_fn`` must be given the
    shard's ``pids`` — its global player ids, delivered as a sharded
    *operand* (an ``arange(K)`` split by ``P('players')``), the same
    data path that delivers the shard its ``rtt`` rows. Identity is
    deliberately data, not ``lax.axis_index``: the ids then cannot
    disagree with the rows they describe.

    The carry is ``(state, queue, prev_active, acc, groups, pids,
    breaker, control, recorder)`` with ``acc=None`` in trace mode,
    ``breaker=None`` unless the config enables circuit breakers,
    ``control=None`` unless ``cfg.control`` enables a closed-loop
    mechanism, and ``recorder=None`` unless ``cfg.recorder`` enables
    the flight recorder (``repro.obs.recorder`` — a bounded ring of
    structured events appended at step end from already-computed
    shard-local quantities; fleet-level lanes are recorded only by the
    shard holding global player 0, so the players axis costs no new
    collective).

    **Closed-loop control plane** (``cfg.control`` enabled): a
    ``control.ControlCarry`` rides in the scan next to the breaker
    state. At step start ``control_actuate`` advances the policy state
    machine on the replicated observations (per-arm queue depth, the
    EMAs fed back at the previous step end) and swaps in the
    *effective* drivers: controller-masked instance liveness (reactive
    autoscaler over the managed standby pool, with warm-up +
    hysteresis), admitted client slots (per-player token buckets; the
    shed remainder counts as issued QoS misses but never reaches a
    queue or the routing statistics), and the migration-scaled service
    row. Placement events, maintenance, the true-mu oracle, regret and
    the queue recursion all see only the effective values — a
    controller spawn/kill IS a placement event to the bandit. At step
    end ``control_observe`` folds the fleet QoS/timeout totals into
    the rolling EMAs; under player sharding that (4,) observation is
    ``psum``-reduced — the control plane's ONE new in-loop collective
    (every other decision input is already replicated, and per-player
    controller state is shard-local). Like the resilience layer, the
    whole path is gated on *static* config: a ``None``/neutral
    ``ControlConfig`` traces the byte-identical open-loop program
    (tests/test_control.py).

    **Request-lifecycle resilience** (``cfg.attempt_timeout > 0``): the
    round body unrolls ``1 + cfg.max_retries`` attempts per request.
    Attempt 0 is the bandit's own selection (optionally vetoed by an
    open breaker); a timed-out attempt is observed as a censored
    latency (``core.bandit.censored_latency`` — a point mass past tau,
    so the KDE learns "worse than the threshold", never the true
    value), its instance KEEPS the work (the arrival stays in the queue
    recursion — abandoned work is how retry storms amplify), and the
    retry re-routes via ``core.bandit.retry_pick`` over the current
    weights, excluding the failed arm and any breaker-open arms, after
    an exponential backoff charged against the request's tau budget.
    All of this is gated on *static* config flags: the neutral config
    (timeout 0, R=0, breakers off) traces the byte-identical
    pre-resilience program — bit-identity is structural, not numerical
    luck. Every resilience state is per-player ((K,·) breaker counters,
    per-attempt draws keyed by global player id), so it shards on the
    ``players`` axis with no new in-loop collectives: retry arrivals
    fold into the SAME per-round (M,) arrival psum.

    **Multi-tenant continuum** (``cfg.tenancy`` with S >= 2): the
    engine dispatches to ``_build_tenant_parts`` — the same carry
    layout and scan contract, with the strategy state and accumulator
    slots holding S-tuples and the queue a shared (S, M) backlog.
    ``tenancy=None`` or a degenerate S=1 config never reaches that
    path: this function's single-service body is literally the code
    that runs, so the pre-tenant program lowers byte-identically
    (tests/test_tenancy.py).
    """
    tn = cfg.tenancy
    if tn is not None and not tn.enabled:
        # degenerate S=1 config: stays on the single-service path
        # below, so it must not silently disagree with the scalar
        # knobs that path reads
        if abs(tn.taus[0] - cfg.tau) > 1e-12:
            raise ValueError(
                f"S=1 TenancyConfig tau {tn.taus[0]} != cfg.tau "
                f"{cfg.tau}: the single-tenant path reads cfg.tau")
        if tn.scales[0] != 1.0:
            raise ValueError(
                "S=1 TenancyConfig needs a neutral service_scale: the "
                "single-tenant path reads drivers.s_m unscaled")
    if qt.tenancy_enabled(cfg):
        return _build_tenant_parts(
            strategy_name, cfg, K, M, fused=fused, trace=trace,
            warmup_steps=warmup_steps, pshard=pshard, **strategy_kw)
    if pshard is not None and pshard.shards == 1:
        pshard = None
    if pshard is not None:
        if trace:
            raise ValueError(
                "player sharding is streaming-only: trajectories are "
                "O(T*K*...) — the memory the sharding exists to split")
        if K % pshard.shards:
            raise ValueError(
                f"K={K} players must be a multiple of the "
                f"{pshard.shards}-way '{pshard.axis}' mesh axis")
    res_on = cfg.attempt_timeout > 0.0
    if not res_on and (cfg.max_retries or cfg.breaker_threshold):
        raise ValueError(
            "max_retries/breaker_threshold need attempt_timeout > 0: "
            "the per-attempt timeout is the failure signal both "
            "mechanisms respond to")
    brk_on = res_on and cfg.breaker_threshold > 0
    ctl_on = qc.control_enabled(cfg)
    ccfg = cfg.control
    if ctl_on and trace:
        raise ValueError(
            "the control plane is streaming-only: closed-loop runs are "
            "fleet-scale by construction (set trace=False)")
    rcfg = cfg.recorder
    rec_on = obr.recorder_enabled(cfg)
    if rec_on and trace:
        raise ValueError(
            "the flight recorder is streaming-only: trace=True already "
            "materializes full trajectories (set trace=False)")
    n_attempts = 1 + (cfg.max_retries if res_on else 0)
    censor = (qb.censored_latency(cfg.attempt_timeout, cfg.tau)
              if res_on else 0.0)
    K_glob = K
    K = K if pshard is None else K // pshard.shards   # local width below
    T, C = cfg.num_steps, cfg.max_clients
    strat = make_strategy(strategy_name, cfg, K, M, pshard=pshard,
                          **strategy_kw)
    batched_record = fused and strat.get("record_rings") is not None
    subset_maint = fused and strat.get("maintain_subset") is not None
    # The fused-round megakernel replaces the whole C-round scan body
    # (selection, queue recursion, feedback control, ring scatter) with
    # one kernels/ops.round_step call — statically gated, like every
    # other exactness-sensitive fast path, on the features that need
    # per-round structure being off: resilience unrolls attempts inside
    # the round, player sharding needs the per-round (M,) arrival psum
    # (a collective cannot live inside a pallas_call), and sequential
    # strategies read their own state between rounds.
    fused_round_on = (cfg.fused_round and fused and not res_on
                      and pshard is None and batched_record
                      and strat.get("fused_round") is not None)
    n_phases = max(cfg.maint_every, 1)
    n_blocks = -(-K_glob // n_phases)   # ceil: players per decision tick
    # a contiguous K-wide shard touches at most ceil(K/n_phases)+1
    # global blocks (straddling one at each edge)
    group_width = (n_blocks if pshard is None
                   else min(n_blocks, -(-K // n_phases) + 1))
    ev_pre_steps = max(1, int(round(cfg.ev_pre / cfg.dt)))
    ev_bucket_steps = max(1, int(round(cfg.ev_bucket / cfg.dt)))

    def init_fn(rtt, active0, key, pids=None):
        if pids is None:
            if pshard is not None:
                raise ValueError(
                    "player-sharded init needs the shard's global player "
                    "ids (pids) as a sharded operand")
            pids = jnp.arange(K, dtype=jnp.int32)
        k_init, k_phase, k_scan = jax.random.split(key, 3)
        s0 = strat["init"](rtt, active0, k_init, pids)
        q0 = jnp.zeros((M,), jnp.float32)
        # Staggered H_d clocks (asynchronous DaemonSet timers): each
        # n_phases-player block spreads its members over the phases at
        # random (_stagger_groups). Fixed group width is what lets
        # maintenance gather exactly the rows due now instead of
        # running the O(K*M*R) estimate for all K every step, and the
        # block structure keeps that gather shard-local under player
        # sharding; sentinel K marks padding (dropped on scatter).
        groups = _stagger_groups(k_phase, K_glob, n_phases, group_width,
                                 pids[0], K)
        acc = None if trace else qm.init_accumulator(
            K, M, C, n_marks=qs.MAX_MARKS, ev_buckets=cfg.ev_buckets)
        brk = qb.breaker_init(K, M) if brk_on else None
        # K here is the LOCAL width: controller token buckets and shed
        # counters are per-player and stay shard-local
        ctl = qc.control_init(ccfg, K, M) if ctl_on else None
        # the ring is per-shard state: K here is the local width, and
        # each shard retains its own most-recent `capacity` events
        rec = obr.recorder_init(rcfg, K, M, brk_on) if rec_on else None
        keys = jax.random.split(k_scan, T)
        return (s0, q0, active0, acc, groups, pids, brk, ctl, rec), keys

    def step_fn(rtt, marks, carry, xs):
        state, q, prev_active, acc, groups, pids, brk, ctl, rec = carry
        t_idx, nc, act, rtt_scale, cut_k, cut_m, s_m, k_step, group = xs
        t = t_idx.astype(jnp.float32) * cfg.dt

        # --- closed-loop control plane (statically gated): advance the
        # policy state machine on the replicated step-start
        # observations and swap in the EFFECTIVE drivers. Everything
        # downstream — placement events, maintenance, the true-mu
        # oracle, regret, the queue drain — sees only the effective
        # values, so a controller spawn/kill fires the same Alg 3/4
        # trigger as a scenario liveness flip. ``nc`` becomes the
        # ADMITTED slot count (what the rounds execute); ``nc_sched``
        # keeps the scheduled demand for client-facing accounting. ---
        if ctl_on:
            with jax.named_scope(PH_CONTROL):
                measf = (t_idx >= warmup_steps).astype(jnp.float32)
                nc_sched = nc
                ctl_cnt_pre = ctl.counters
                ctl, act, nc, s_m, _shed = qc.control_actuate(
                    ccfg, cfg.dt, t, ctl, q, act, nc, s_m, measf)
                # control actions for the flight recorder: this step's
                # counter increments (already warmup-gated, replicated
                # across shards — no collective needed to observe them)
                ctl_deltas = (
                    ctl.counters.scale_up - ctl_cnt_pre.scale_up,
                    ctl.counters.scale_down - ctl_cnt_pre.scale_down,
                    ctl.counters.migrations - ctl_cnt_pre.migrations,
                ) if rec_on else None

        # --- scenario modulation: effective RTT and service row for
        # THIS step. The partition term is the factored rank-1 AND
        # (only LB-side ∩ instance-side routes pay the cut); with
        # neutral drivers (*1.0, +0.0) every float is unchanged. ---
        with jax.named_scope(PH_PLACEMENT):
            rtt_t = rtt * rtt_scale[None, :] + jnp.minimum(
                cut_k[:, None], cut_m[None, :])

            # --- placement events (paper Alg 3/4 trigger) ---
            changed = jnp.any(act != prev_active)
            if brk_on:
                # liveness flips also clear the affected breaker
                # columns, mirroring how Alg 3/4 purge the arm's data
                state, brk = jax.lax.cond(
                    changed,
                    lambda sb: (strat["on_activity"](sb[0], act, rtt_t, t),
                                qb.breaker_reset_arms(sb[1],
                                                      act != prev_active)),
                    lambda sb: sb,
                    (state, brk))
            else:
                state = jax.lax.cond(
                    changed,
                    lambda s: strat["on_activity"](s, act, rtt_t, t),
                    lambda s: s,
                    state)

        # --- maintenance: only the player group whose clock fires.
        # The row arrives through xs (sliced by the scan machinery from
        # a (T, W) table built once outside the loop) instead of an
        # in-loop `groups[t_idx % n_phases]` gather: under shard_map at
        # ≥4 host devices, XLA:CPU (jax 0.4.37) mis-fuses that gather
        # of the sort-backed stagger table into the loop and some
        # shards read another phase's row — sharded runs then maintain
        # the wrong players (see ROADMAP; tests/test_sharded_players.py
        # is the regression net). ---
        with jax.named_scope(PH_MAINTENANCE):
            if subset_maint:
                state = strat["maintain_subset"](state, rtt_t, t, group)
            else:
                lb_mask = jnp.zeros((K,), bool).at[group].set(
                    True, mode="drop")
                state = strat["maintain"](state, rtt_t, t, lb_mask)

        with jax.named_scope(PH_TRUE_MU):
            mu_true = _true_mu(rtt_t, q, cfg, s_m)   # (K, M) at step start
            w_now = strat["weights"](state)
            reg = step_regret(w_now, mu_true, act)
        q_start = q

        with jax.named_scope(PH_ROUNDS):
            if ctl_on:
                mask_adm = jnp.arange(C)[None, :] < nc[:, None]  # admitted
                mask_all = jnp.arange(C)[None, :] < nc_sched[:, None]
            else:
                mask_all = jnp.arange(C)[None, :] < nc[:, None]  # (K, C)
                mask_adm = mask_all
            # service is continuous: drain dt/C of capacity per round so
            # in-step arrivals and departures interleave (a step-end-only
            # drain would overstate in-step queueing by ~C/2 requests).
            # s_m is an (M,) row, so throttled instances drain slower.
            served_per_round = cfg.dt / (C * s_m)
            kidx = jnp.arange(K)

        # --- request rounds: a scan, traced once instead of C times.
        # Rounds still execute in order — selection, the queue recursion
        # and the cheap (K, M) feedback control are interleaved, so an
        # in-step cooldown trip steers the remaining rounds exactly as
        # with per-round `record`. With the fused path the expensive
        # (K, M, R)/(K, Rq) ring writes are deferred and land in ONE
        # dense masked write per step (`record_rings_batch`); the sequential
        # fallback lets the strategy read its own per-request state
        # between rounds (Dec-SARSA). Bit-for-bit identical paths
        # (tests/test_bandit_batch.py). ---
        if not res_on and fused_round_on:
            with jax.named_scope(PH_ROUNDS):
                state, q, arrivals, choices, lats, procs = \
                    strat["fused_round"](state, q, nc, act, t, rtt_t, s_m,
                                         served_per_round, k_step, pids)
            att_kc = mask_adm.astype(jnp.int32)
            dropped_kc = jnp.zeros_like(mask_all)
            brk_open_step = None
        elif not res_on:
            def round_body(rc, r):
                state, q, arrivals = rc
                k_r = jax.random.fold_in(k_step, r)
                k_sel, k_noise = jax.random.split(k_r)
                mask = r < nc                                  # (K,)
                choice, state = strat["select"](state, k_sel, t, act,
                                                pids)
                # processing noise keyed per global player id (prand),
                # so the draw is invariant to how the K axis is sharded
                z = jnp.exp(
                    cfg.proc_sigma * prand.player_normal(k_noise, pids))
                q_seen = q[choice]
                proc = (q_seen + 1.0) * s_m[choice] * z
                lat = rtt_t[kidx, choice] + proc
                if batched_record:
                    state = strat["record_feedback"](state, choice, lat,
                                                     t, mask)
                else:
                    state = strat["record"](state, choice, lat, t, mask)
                arr_r = jax.ops.segment_sum(
                    mask.astype(jnp.float32), choice, num_segments=M)
                # the ONE cross-player coupling: same-round requests
                # from every LB land on the shared queues, so a
                # player-sharded round psums its local (M,) arrivals
                # before the drain (integer-valued f32 — the psum is
                # exact, and the queue stays replicated across shards).
                # `arrivals` keeps the shard-LOCAL sum: it feeds the
                # accumulator's partial arrivals_m, reduced once after
                # the scan.
                arr_all = (arr_r if pshard is None
                           else jax.lax.psum(arr_r, pshard.axis))
                q = jnp.maximum(q + arr_all - served_per_round, 0.0)
                return (state, q, arrivals + arr_r), (choice, lat, proc)

            with jax.named_scope(PH_ROUNDS):
                (state, q, arrivals), (ch_r, lat_r, proc_r) = jax.lax.scan(
                    round_body, (state, q, jnp.zeros((M,), jnp.float32)),
                    jnp.arange(C))
                choices = ch_r.T                               # (K, C)
                lats = lat_r.T
                procs = proc_r.T
            if batched_record:
                with jax.named_scope(PH_RINGS):
                    state = strat["record_rings"](state, choices, lats, t,
                                                  mask_adm)
            att_kc = mask_adm.astype(jnp.int32)
            dropped_kc = jnp.zeros_like(mask_all)
            brk_open_step = None
        else:
            # --- resilient request lifecycle: 1 + R attempts, every
            # retry re-routed, backed off, budgeted against tau, and
            # fed back into the SAME per-round arrival psum (retry
            # load is real load). Attempt 0 reuses the exact neutral
            # key derivation; retry draws fold fresh salts off the
            # round key. All attempts of a round observe the
            # round-start queue (sub-round-resolution simplification;
            # their arrivals hit the queue at the round boundary). ---
            A = n_attempts
            with jax.named_scope(PH_ROUNDS):
                brk_open_step = (qb.breaker_is_open(brk, t) if brk_on
                                 else None)

            def round_body(rc, r):
                state, q, arrivals, brk_c = rc
                k_r = jax.random.fold_in(k_step, r)
                k_sel, k_noise = jax.random.split(k_r)
                mask = r < nc                                  # (K,)
                choice, state = strat["select"](state, k_sel, t, act,
                                                pids)
                if brk_on:
                    # the bandit's pick stands unless its breaker is
                    # open; the veto re-routes over the closed pool
                    g_veto = prand.player_gumbel(
                        jax.random.fold_in(k_r, 101), pids, M)
                    choice = qb.breaker_veto(
                        choice, brk_c, t, strat["weights"](state), act,
                        g_veto, mask)
                z = jnp.exp(
                    cfg.proc_sigma * prand.player_normal(k_noise, pids))
                proc = (q[choice] + 1.0) * s_m[choice] * z
                lat = rtt_t[kidx, choice] + proc
                timed_out = mask & (lat > cfg.attempt_timeout)
                obs = jnp.where(timed_out, censor, lat)
                # censored samples clip the proc sketch at the timeout
                # (the client never observes past it)
                proc_f = jnp.where(
                    timed_out, jnp.minimum(proc, cfg.attempt_timeout),
                    proc)
                elapsed = jnp.where(
                    mask, jnp.minimum(lat, cfg.attempt_timeout), 0.0)
                if brk_on:
                    brk_c = qb.breaker_update(
                        brk_c, choice, timed_out, mask, t,
                        cfg.breaker_threshold, cfg.breaker_cooldown)
                feed = (strat["record_feedback"] if batched_record
                        else strat["record"])
                state = feed(state, choice, obs, t, mask)
                arr = jax.ops.segment_sum(
                    mask.astype(jnp.float32), choice, num_segments=M)
                att_ch, att_obs, att_m = [choice], [obs], [mask]
                completed = mask & ~timed_out
                choice_f = choice
                pending = timed_out
                for a in range(1, A):
                    p = pending
                    backoff = cfg.retry_backoff * (2.0 ** (a - 1))
                    if cfg.retry_deadline:
                        # bounded policy: no retry that cannot finish
                        # inside the request's QoS deadline
                        p = p & (elapsed + backoff < cfg.tau)
                    k_a = jax.random.fold_in(k_r, 1000 + a)
                    k_pick, k_z = jax.random.split(k_a)
                    g = prand.player_gumbel(k_pick, pids, M)
                    open_now = (qb.breaker_is_open(brk_c, t) if brk_on
                                else None)
                    alt = qb.retry_pick(strat["weights"](state), act,
                                        choice_f, open_now, g)
                    choice_a = jnp.where(p, alt, choice_f)
                    z_a = jnp.exp(cfg.proc_sigma
                                  * prand.player_normal(k_z, pids))
                    proc_a = (q[choice_a] + 1.0) * s_m[choice_a] * z_a
                    lat_a = rtt_t[kidx, choice_a] + proc_a
                    to_a = p & (lat_a > cfg.attempt_timeout)
                    obs_a = jnp.where(to_a, censor, lat_a)
                    elapsed = jnp.where(
                        p,
                        elapsed + backoff
                        + jnp.minimum(lat_a, cfg.attempt_timeout),
                        elapsed)
                    if brk_on:
                        brk_c = qb.breaker_update(
                            brk_c, choice_a, to_a, p, t,
                            cfg.breaker_threshold, cfg.breaker_cooldown)
                    state = feed(state, choice_a, obs_a, t, p)
                    arr = arr + jax.ops.segment_sum(
                        p.astype(jnp.float32), choice_a, num_segments=M)
                    att_ch.append(choice_a)
                    att_obs.append(obs_a)
                    att_m.append(p)
                    choice_f = jnp.where(p, choice_a, choice_f)
                    proc_f = jnp.where(
                        to_a, jnp.minimum(proc_a, cfg.attempt_timeout),
                        jnp.where(p, proc_a, proc_f))
                    completed = completed | (p & ~to_a)
                    pending = to_a

                dropped = mask & ~completed
                # client-perceived latency: total elapsed (attempt
                # costs + backoffs) when the request completed, the
                # censor sentinel (> tau => QoS miss) when it dropped
                lat_out = jnp.where(completed, elapsed, censor)
                att_n = sum(m.astype(jnp.int32) for m in att_m)
                # still ONE psum per round: retries folded in above
                arr_all = (arr if pshard is None
                           else jax.lax.psum(arr, pshard.axis))
                q = jnp.maximum(q + arr_all - served_per_round, 0.0)
                ys = (choice_f, lat_out, proc_f, att_n, dropped,
                      jnp.stack(att_ch), jnp.stack(att_obs),
                      jnp.stack(att_m))
                return (state, q, arrivals + arr, brk_c), ys

            with jax.named_scope(PH_ROUNDS):
                (state, q, arrivals, brk), ys_r = jax.lax.scan(
                    round_body,
                    (state, q, jnp.zeros((M,), jnp.float32), brk),
                    jnp.arange(C))
                (chf_r, lat_r, proc_r, att_r, drop_r,
                 ach_r, aobs_r, am_r) = ys_r
                choices = chf_r.T             # (K, C) final-attempt arm
                lats = lat_r.T
                procs = proc_r.T
                att_kc = att_r.T              # (K, C) i32 attempts
                dropped_kc = drop_r.T         # (K, C) bool
            if batched_record:
                # all C*A attempts land in the step's ONE dense ring
                # write, columns in chronological (round-major,
                # attempt-minor) order — record_rings_batch is generic
                # in its column count
                with jax.named_scope(PH_RINGS):
                    ch_all = jnp.transpose(ach_r, (2, 0, 1)).reshape(
                        K, C * A)
                    obs_all = jnp.transpose(aobs_r, (2, 0, 1)).reshape(
                        K, C * A)
                    m_all = jnp.transpose(am_r, (2, 0, 1)).reshape(
                        K, C * A)
                    state = strat["record_rings"](state, ch_all, obs_all,
                                                  t, m_all)
        # retry exhaustions for the flight recorder: snapshot the
        # deadline-dropped counts BEFORE admission sheds are merged
        # into dropped_kc below (sheds get their own event kind)
        with jax.named_scope(PH_RECORDER):
            retry_drop_k = (dropped_kc.astype(jnp.float32).sum(-1)
                            if rec_on and res_on else None)
        if ctl_on and ccfg.admit:
            # admission-shed slots: issued from the client's view (a
            # denied client is a failed client — shedding can only win
            # by protecting the admitted majority, never by shrinking
            # the QoS denominator) but never served: censor the
            # latency past tau, mark them dropped with zero attempts,
            # and keep them out of the routing/latency statistics.
            with jax.named_scope(PH_CONTROL):
                shed_kc = mask_all & ~mask_adm
                lats = jnp.where(shed_kc, jnp.inf, lats)
                dropped_kc = dropped_kc | shed_kc
                served_kc = mask_adm
        else:
            served_kc = None
        with jax.named_scope(PH_ACCUMULATOR):
            # dropped requests carry the censor sentinel (> tau), so the
            # shared reward rule scores them 0 without a special case
            rewards = (lats <= cfg.tau).astype(jnp.float32)
            issued = mask_all

            if trace:
                ys = SimOutputs(
                    rewards=rewards, issued=issued, choices=choices,
                    latency=lats, proc_lat=procs, arrivals=arrivals,
                    queue=q_start, weights=w_now, true_mu=mu_true,
                    regret=reg, eps=strat["eps"](state), attempts=att_kc,
                    dropped=dropped_kc)
            else:
                acc = qm.update_accumulator(
                    acc, rewards=rewards, issued=issued, choices=choices,
                    procs=procs, arrivals=arrivals, regret=reg, mu=mu_true,
                    t_idx=t_idx, warmup_steps=warmup_steps, marks=marks,
                    ev_pre_steps=ev_pre_steps,
                    ev_bucket_steps=ev_bucket_steps, attempts=att_kc,
                    dropped=dropped_kc, brk_open=brk_open_step,
                    served=served_kc)
                issf = issued.astype(jnp.float32)
                ys = StepSeries(succ=(rewards * issf).sum(),
                                issued=issf.sum(), regret=reg.sum(),
                                attempts=att_kc.astype(jnp.float32).sum())
        if ctl_on:
            # step-end feedback: fold the fleet QoS/timeout totals into
            # the rolling EMAs the admission signal reads next step.
            # Under player sharding this (4,) observation must be
            # global or the replicated controller state would diverge
            # across shards — the control plane's ONE new in-loop
            # collective.
            with jax.named_scope(PH_CONTROL):
                issf_c = issued.astype(jnp.float32)
                attf_c = att_kc.astype(jnp.float32)
                compl_c = issf_c * (1.0 - dropped_kc.astype(jnp.float32))
                obs = jnp.stack([(rewards * issf_c).sum(), issf_c.sum(),
                                 (attf_c - compl_c).sum(), attf_c.sum()])
                if pshard is not None:
                    obs = jax.lax.psum(obs, pshard.axis)
                ctl = qc.control_observe(ccfg, ctl, obs, cfg.dt)
        if rec_on:
            # flight recorder: append this step's events from
            # quantities the step already computed — per-player lanes
            # are shard-local, fleet lanes (marks, control actions) are
            # recorded only by the shard holding global player 0, so
            # there is no new collective on the players axis.
            with jax.named_scope(PH_RECORDER):
                issf_r = issued.astype(jnp.float32)
                rec = obr.record_step(
                    rcfg, rec, t_idx=t_idx, pids=pids, marks=marks,
                    miss_k=((1.0 - rewards) * issf_r).sum(-1),
                    iss_k=issf_r.sum(-1),
                    retry_drop_k=retry_drop_k,
                    shed_k=(shed_kc.astype(jnp.float32).sum(-1)
                            if ctl_on and ccfg.admit else None),
                    open_now=(qb.breaker_is_open(brk, t) if brk_on
                              else None),
                    ctl_deltas=ctl_deltas if ctl_on else None)
        return (state, q, act, acc, groups, pids, brk, ctl, rec), ys

    return init_fn, step_fn


# PRNG salt separating tenant round-key folds from every other fold the
# engine makes off the round key (resilience uses 101 and 1000+a, but
# never composes with tenancy anyway). Folding per tenant makes tenant
# s's draw stream a pure function of (step key, round, tenant, global
# player id) — invariant to S and to how the player axis is sharded.
_TENANT_SALT = 7001


def _build_tenant_parts(
    strategy_name: str,
    cfg: SimConfig,
    K: int,
    M: int,
    fused: bool = True,
    trace: bool = True,
    warmup_steps: int = 0,
    pshard: PlayerSharding | None = None,
    **strategy_kw,
):
    """The multi-tenant engine: S services on one shared fleet.

    Same ``(init_fn, step_fn)`` contract and 9-slot carry layout as
    ``build_sim_parts`` — the strategy-state and accumulator slots hold
    S-tuples (one independent bandit fleet and one
    ``MetricAccumulator`` per tenant) and the queue is the shared
    (S, M) per-tenant backlog; the chunked/checkpointed drivers index
    the carry positionally and work unchanged.

    Queue model: a request's position in line is the TOTAL instance
    backlog ``q.sum(0)`` (tenants share single-worker queues), its
    service draw uses the tenant's effective row ``s_eff[s]``
    (``tenancy.TenancyConfig``: per-tenant demand scale + cross-service
    interference proportional to the backlog share OTHER tenants hold),
    and the per-round drain is work-conserving processor sharing: the
    round's ``dt/C`` seconds of capacity retire the same fraction of
    every tenant's backlog (``work = sum_s q[s]*s_eff[s]`` seconds
    outstanding; each instance completes ``min(1, (dt/C)/work)`` of
    it). At S=1 this reduces exactly to the single-service drain — but
    S=1 configs never trace this path (``build_sim_parts`` dispatch).

    Sharding: per-tenant bandit state is per-player and shards on the
    ``players`` axis like the single-service engine; the one in-loop
    collective stays one psum per round, now of the stacked (S, M)
    arrival matrix. Tenant draws fold ``_TENANT_SALT + s`` off the
    round key and then key per-player noise by global id, so sharded
    and unsharded multi-tenant runs match on counting statistics
    exactly. The resilience / control / recorder layers do not compose
    with tenancy yet (statically refused); the fused-round megakernel
    is single-service and falls back to the round scan.
    """
    tn = cfg.tenancy
    S = tn.S
    if trace:
        raise ValueError(
            "the multi-tenant engine is streaming-only: per-tenant "
            "trajectories are O(S*T*K*...) (set trace=False)")
    if cfg.resilience_on or cfg.max_retries or cfg.breaker_threshold:
        raise ValueError(
            "tenancy does not compose with the resilience layer yet: "
            "run multi-tenant configs with attempt_timeout=0, "
            "max_retries=0, breaker_threshold=0")
    if qc.control_enabled(cfg):
        raise ValueError(
            "tenancy does not compose with the control plane yet: "
            "run multi-tenant configs with control=None")
    if obr.recorder_enabled(cfg):
        raise ValueError(
            "tenancy does not compose with the flight recorder yet: "
            "run multi-tenant configs with recorder=None")
    if "params" in strategy_kw:
        raise ValueError(
            "explicit params= would share one tau across tenants; "
            "per-tenant params are derived from TenancyConfig.taus")
    if pshard is not None and pshard.shards == 1:
        pshard = None
    if pshard is not None and K % pshard.shards:
        raise ValueError(
            f"K={K} players must be a multiple of the "
            f"{pshard.shards}-way '{pshard.axis}' mesh axis")
    K_glob = K
    K = K if pshard is None else K // pshard.shards
    T, C = cfg.num_steps, cfg.max_clients
    taus = tuple(float(x) for x in tn.taus)
    scales = jnp.asarray(tn.scales, jnp.float32)
    xi = float(tn.interference)
    # one independent strategy instance per tenant, each built against
    # the tenant's own deadline (BanditParams/DecSarsaParams bake tau)
    strats = tuple(
        make_strategy(strategy_name,
                      dataclasses.replace(cfg, tau=taus[s]), K, M,
                      pshard=pshard, **strategy_kw)
        for s in range(S))
    batched_record = fused and strats[0].get("record_rings") is not None
    subset_maint = fused and strats[0].get("maintain_subset") is not None
    n_phases = max(cfg.maint_every, 1)
    n_blocks = -(-K_glob // n_phases)
    group_width = (n_blocks if pshard is None
                   else min(n_blocks, -(-K // n_phases) + 1))
    ev_pre_steps = max(1, int(round(cfg.ev_pre / cfg.dt)))
    ev_bucket_steps = max(1, int(round(cfg.ev_bucket / cfg.dt)))

    def eff_service(q, s_m):
        """(S, M) effective service row at the current (S, M) backlog:
        per-tenant demand scale, plus interference inflating a tenant's
        service time by xi per unit share of backlog held by OTHERS."""
        base = s_m[None, :] * scales[:, None]
        if xi == 0.0:
            return jnp.broadcast_to(base, (S, M))
        q_tot = q.sum(0)
        other = (q_tot[None, :] - q) / (1.0 + q_tot[None, :])
        return base * (1.0 + xi * other)

    def init_fn(rtt, active0, key, pids=None):
        if pids is None:
            if pshard is not None:
                raise ValueError(
                    "player-sharded init needs the shard's global "
                    "player ids (pids) as a sharded operand")
            pids = jnp.arange(K, dtype=jnp.int32)
        k_init, k_phase, k_scan = jax.random.split(key, 3)
        s0 = tuple(
            strats[s]["init"](rtt, active0,
                              jax.random.fold_in(k_init, s), pids)
            for s in range(S))
        q0 = jnp.zeros((S, M), jnp.float32)
        groups = _stagger_groups(k_phase, K_glob, n_phases, group_width,
                                 pids[0], K)
        accs = tuple(
            qm.init_accumulator(K, M, C, n_marks=qs.MAX_MARKS,
                                ev_buckets=cfg.ev_buckets)
            for _ in range(S))
        keys = jax.random.split(k_scan, T)
        return (s0, q0, active0, accs, groups, pids,
                None, None, None), keys

    def step_fn(rtt, marks, carry, xs):
        states, q, prev_active, accs, groups, pids, _b, _c, _r = carry
        t_idx, nc, act, rtt_scale, cut_k, cut_m, s_m, k_step, group = xs
        # nc is the (S, K) per-tenant client schedule for this step
        t = t_idx.astype(jnp.float32) * cfg.dt
        with jax.named_scope(PH_PLACEMENT):
            rtt_t = rtt * rtt_scale[None, :] + jnp.minimum(
                cut_k[:, None], cut_m[None, :])

            changed = jnp.any(act != prev_active)
            states = tuple(
                jax.lax.cond(
                    changed,
                    lambda st, _s=s: strats[_s]["on_activity"](st, act,
                                                               rtt_t, t),
                    lambda st: st, states[s])
                for s in range(S))
        with jax.named_scope(PH_MAINTENANCE):
            if subset_maint:
                states = tuple(
                    strats[s]["maintain_subset"](states[s], rtt_t, t, group)
                    for s in range(S))
            else:
                lb_mask = jnp.zeros((K,), bool).at[group].set(
                    True, mode="drop")
                states = tuple(
                    strats[s]["maintain"](states[s], rtt_t, t, lb_mask)
                    for s in range(S))

        # oracle + regret per tenant at step start, against the
        # step-start TOTAL backlog and the tenant's effective row
        with jax.named_scope(PH_TRUE_MU):
            q_tot0 = q.sum(0)
            s_eff0 = eff_service(q, s_m)
            mu_s = tuple(
                _true_mu_tau(rtt_t, q_tot0, taus[s], cfg.proc_sigma,
                             s_eff0[s])
                for s in range(S))
            reg_s = tuple(
                step_regret(strats[s]["weights"](states[s]), mu_s[s], act)
                for s in range(S))
        with jax.named_scope(PH_ROUNDS):
            mask_s = tuple(jnp.arange(C)[None, :] < nc[s][:, None]
                           for s in range(S))
            kidx = jnp.arange(K)

        def round_body(rc, r):
            states, q, arrivals = rc
            q_tot = q.sum(0)
            s_eff = eff_service(q, s_m)
            k_r = jax.random.fold_in(k_step, r)
            new_states, arr_rows, outs = [], [], []
            for s in range(S):
                k_t = jax.random.fold_in(k_r, _TENANT_SALT + s)
                k_sel, k_noise = jax.random.split(k_t)
                mask = r < nc[s]
                choice, st = strats[s]["select"](states[s], k_sel, t,
                                                 act, pids)
                z = jnp.exp(cfg.proc_sigma
                            * prand.player_normal(k_noise, pids))
                # position in line is the TOTAL backlog: the queue is
                # shared; only the service draw is tenant-specific
                proc = (q_tot[choice] + 1.0) * s_eff[s][choice] * z
                lat = rtt_t[kidx, choice] + proc
                if batched_record:
                    st = strats[s]["record_feedback"](st, choice, lat,
                                                      t, mask)
                else:
                    st = strats[s]["record"](st, choice, lat, t, mask)
                arr_rows.append(jax.ops.segment_sum(
                    mask.astype(jnp.float32), choice, num_segments=M))
                new_states.append(st)
                outs.append((choice, lat, proc))
            arr_sm = jnp.stack(arr_rows)               # (S, M) local
            # still ONE psum per round: the stacked per-tenant arrival
            # matrix crosses the players axis in a single collective
            arr_all = (arr_sm if pshard is None
                       else jax.lax.psum(arr_sm, pshard.axis))
            # work-conserving processor-sharing drain: this round's
            # dt/C seconds retire the same fraction f of every
            # tenant's backlog (work = seconds outstanding per
            # instance at the round-start effective rows)
            b = q + arr_all
            work = (b * s_eff).sum(0)
            f = jnp.minimum(1.0, (cfg.dt / C) / jnp.maximum(work, 1e-9))
            q = b * (1.0 - f[None, :])
            return (tuple(new_states), q, arrivals + arr_sm), \
                tuple(outs)

        with jax.named_scope(PH_ROUNDS):
            (states, q, arr_sm), ys_r = jax.lax.scan(
                round_body, (states, q, jnp.zeros((S, M), jnp.float32)),
                jnp.arange(C))

        new_states, new_accs = [], []
        succ_v, iss_v, reg_v = [], [], []
        for s in range(S):
            with jax.named_scope(PH_ROUNDS):
                ch_r, lat_r, proc_r = ys_r[s]
                choices, lats, procs = ch_r.T, lat_r.T, proc_r.T  # (K, C)
            st = states[s]
            if batched_record:
                with jax.named_scope(PH_RINGS):
                    st = strats[s]["record_rings"](st, choices, lats, t,
                                                   mask_s[s])
            with jax.named_scope(PH_ACCUMULATOR):
                rewards = (lats <= taus[s]).astype(jnp.float32)
                issued = mask_s[s]
                acc = qm.update_accumulator(
                    accs[s], rewards=rewards, issued=issued,
                    choices=choices, procs=procs, arrivals=arr_sm[s],
                    regret=reg_s[s], mu=mu_s[s], t_idx=t_idx,
                    warmup_steps=warmup_steps, marks=marks,
                    ev_pre_steps=ev_pre_steps,
                    ev_bucket_steps=ev_bucket_steps,
                    attempts=issued.astype(jnp.int32),
                    dropped=jnp.zeros_like(issued), brk_open=None,
                    served=None)
                issf = issued.astype(jnp.float32)
                succ_v.append((rewards * issf).sum())
                iss_v.append(issf.sum())
                reg_v.append(reg_s[s].sum())
            new_states.append(st)
            new_accs.append(acc)
        # per-step series carry one scalar PER TENANT: the streamed
        # StepSeries fields come out (T, S)
        with jax.named_scope(PH_ACCUMULATOR):
            ys = StepSeries(succ=jnp.stack(succ_v),
                            issued=jnp.stack(iss_v),
                            regret=jnp.stack(reg_v),
                            attempts=jnp.stack(iss_v))
        return (tuple(new_states), q, act, tuple(new_accs), groups,
                pids, None, None, None), ys

    return init_fn, step_fn


def build_sim_fn(
    strategy_name: str,
    cfg: SimConfig,
    K: int,
    M: int,
    fused: bool = True,
    trace: bool = True,
    warmup_steps: int = 0,
    pshard: PlayerSharding | None = None,
    **strategy_kw,
):
    """Build a traceable ``run(rtt, drivers, key)``.

    ``drivers`` is a compiled-scenario :class:`Drivers` pytree (see
    ``repro.continuum.scenarios``); ``scenarios.neutral_drivers``
    reproduces the pre-scenario-engine constant schedules bit-for-bit.

    Exposed separately from ``run_sim`` so harnesses can transform it:
    the evaluation suite vmaps the scenario axis into one program per
    strategy and shards its lanes across devices
    (``build_sim_grid_fn``; benchmarks/common.py::get_suite), and
    benchmarks/beyond.py vmaps a traced ``service_time`` to sweep the
    utilization axis (``service_time`` overrides ``drivers.s_m`` with a
    broadcast scalar, so it may be a traced vmap axis).

    ``trace=True`` returns full ``SimOutputs`` trajectories (O(T·K·M)
    memory — the debug/inspection mode); ``trace=False`` returns
    ``StreamOutputs`` (``MetricAccumulator`` + O(T) scalar series), the
    fleet-scale mode. ``warmup_steps`` gates the post-warmup
    accumulator fields and is ignored in trace mode.

    With ``pshard`` (see ``build_sim_parts``) the returned ``run`` is
    the per-shard program of a player-sharded streaming simulation and
    must be traced inside a ``shard_map`` over ``pshard.axis`` — its
    inputs/outputs carry local (K/shards,) player slices, and the
    fleet-level accumulator fields and the ``StepSeries`` scalars are
    ``psum``-reduced here, once, after the scan (the per-round arrival
    psum inside the scan is the only in-loop collective).
    ``build_sim_players_fn`` wraps this with the right specs.

    ``fused=False`` forces the pre-refactor step structure (per-round
    ring scatters + full-width maintenance gated only by ``lb_mask``)
    even for strategies that support the fused path — kept as the
    reference point for benchmarks/bandit_scale.py.
    """
    T = cfg.num_steps
    tn_S = qt.tenancy_size(cfg)
    init_fn, step_fn = build_sim_parts(
        strategy_name, cfg, K, M, fused=fused, trace=trace,
        warmup_steps=warmup_steps, pshard=pshard, **strategy_kw)

    def run(rtt, drivers, key, service_time=None, pids=None):
        if tn_S and (drivers.n_clients.ndim != 3
                     or drivers.n_clients.shape[-2] != tn_S):
            raise ValueError(
                f"multi-tenant run needs a (T, S={tn_S}, K) n_clients "
                f"schedule (got {drivers.n_clients.shape}): compile "
                "with scenarios.compile_tenant_scenario / "
                "tenant_neutral_drivers / broadcast_tenants")
        if service_time is not None:
            drivers = drivers._replace(s_m=jnp.broadcast_to(
                jnp.asarray(service_time, jnp.float32), drivers.s_m.shape))
        carry0, keys = init_fn(rtt, drivers.active[0], key, pids)
        t_idx = jnp.arange(T)
        # per-step maintenance rows, gathered from the stagger table
        # ONCE outside the loop and scanned in (see step_fn)
        grows = carry0[4][t_idx % max(cfg.maint_every, 1)]
        xs = (t_idx,
              *(getattr(drivers, f) for f in qs.STEP_FIELDS), keys, grows)
        carry, ys = jax.lax.scan(
            lambda c, x: step_fn(rtt, drivers.marks, c, x), carry0, xs)
        if trace:
            return ys
        acc = carry[3]
        if pshard is not None and pshard.shards > 1:
            # fleet-level fields accumulated shard-local partials all
            # scan long; reduce them once here. Counting fields are
            # integer-valued f32 sums, so the psum is exact; the regret
            # series is the one genuinely-float reduction (f32
            # reassociation tolerance). steps_measured is a pure
            # function of t_idx — already replicated, no reduction.
            def allsum(x):
                return jax.lax.psum(x, pshard.axis)

            def reduce_acc(a):
                return a._replace(arrivals_m=allsum(a.arrivals_m),
                                  proc_hist=allsum(a.proc_hist),
                                  ev_succ=allsum(a.ev_succ),
                                  ev_n=allsum(a.ev_n))

            # the tenant engine carries one accumulator per tenant;
            # each reduces its fleet-level partials independently
            acc = (tuple(reduce_acc(a) for a in acc) if tn_S
                   else reduce_acc(acc))
            ys = StepSeries(*(allsum(y) for y in ys))
        # control counters ride out with the stream: fleet-level fields
        # are replicated across player shards by construction (every
        # decision input is replicated), shed_k is per-player and
        # concatenates like the other (K,) accumulator fields
        ctl = carry[7]
        # the recorder ring is per-shard state and stays shard-local:
        # under player sharding the out-spec concatenates the rings
        # ((cap,) -> (D*cap,)) and pointers ((1,) -> (D,));
        # obs.recorder.recorder_events decodes either layout.
        return StreamOutputs(acc=acc, series=ys,
                             ctrl=ctl.counters if ctl is not None else None,
                             rec=carry[8])

    return run


def build_sim_chunks(
    strategy_name: str,
    cfg: SimConfig,
    K: int,
    M: int,
    fused: bool = True,
    warmup_steps: int = 0,
    **strategy_kw,
):
    """Chunked-horizon streaming: ``(init_fn, chunk_fn)``.

    ``chunk_fn(rtt, carry, t_idx, drivers, keys)`` scans the given time
    slice — ``drivers`` is a ``scenarios.slice_drivers`` slice whose
    per-step fields span the chunk (marks ride along whole, they are
    global step indices) — and returns ``(carry, StepSeries)``. Jit it
    with ``donate_argnums=(1,)`` (and the slice args) so the carry
    buffers are reused in place and peak device memory stays O(K·M) +
    one chunk of O(T) scalars regardless of the horizon.
    ``run_sim_stream`` is the reference driver.
    """
    init_fn, step_fn = build_sim_parts(
        strategy_name, cfg, K, M, fused=fused, trace=False,
        warmup_steps=warmup_steps, **strategy_kw)

    def chunk_fn(rtt, carry, t_idx, drivers, keys, service_time=None):
        if service_time is not None:
            drivers = drivers._replace(s_m=jnp.broadcast_to(
                jnp.asarray(service_time, jnp.float32), drivers.s_m.shape))
        grows = carry[4][t_idx % max(cfg.maint_every, 1)]
        xs = (t_idx, *(getattr(drivers, f) for f in qs.STEP_FIELDS), keys,
              grows)
        return jax.lax.scan(
            lambda c, x: step_fn(rtt, drivers.marks, c, x), carry, xs)

    return init_fn, chunk_fn


# The O(T) driver buffers are donated, but ONLY when this module
# constructed every leaf itself (caller passed neither drivers nor
# n_clients/active): donating a caller-supplied array would invalidate
# it under the caller's feet on backends that implement donation, and
# callers routinely reuse one Drivers batch across strategies. rtt and
# key are never donated (rtt is shared across strategies; key is
# 8 bytes). Donated buffers XLA cannot alias to an output draw a
# UserWarning per call; that is the expected case here (they are
# freed, not aliased), so the dispatch silences exactly that message.

@contextlib.contextmanager
def _quiet_donation():
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def _resolve_drivers(cfg, K, M, drivers, n_clients, active):
    """One Drivers pytree from whichever input style the caller used:
    a compiled scenario (``drivers``), legacy ``n_clients``/``active``
    schedules wrapped in neutral modulation, or the constant defaults.
    Donation (argnum 1 in every driver signature below) only when every
    leaf is module-created."""
    if drivers is not None:
        if n_clients is not None or active is not None:
            raise ValueError("pass either drivers= or n_clients=/active=, "
                             "not both")
        return drivers, ()
    fresh = n_clients is None and active is None
    drv = qs.neutral_drivers(cfg, K, M, n_clients=n_clients, active=active)
    if qt.tenancy_enabled(cfg):
        # module-built single-tenant schedules broadcast to every
        # tenant; explicitly-passed Drivers (above) must already carry
        # the (T, S, K) tenant axis — run() checks and says how
        drv = qs.broadcast_tenants(drv, cfg.tenancy.S)
    return drv, ((1,) if fresh else ())


def run_sim(
    strategy_name: str,
    rtt: jax.Array,              # (K, M) base LB->instance RTT [s]
    cfg: SimConfig,
    key: jax.Array,
    n_clients: jax.Array | None = None,   # (T, K) i32 active clients per LB
    active: jax.Array | None = None,      # (T, M) bool instance liveness
    drivers: Drivers | None = None,       # compiled scenario (wins over kwargs)
    **strategy_kw,
) -> SimOutputs:
    """Run one topology × strategy for the full horizon. jit-compiled.

    Full-trajectory (trace) mode. ``drivers`` takes a compiled
    scenario; the legacy ``n_clients``/``active`` kwargs wrap into
    neutral drivers. Default-constructed driver buffers are donated to
    the computation; caller-supplied arrays are left untouched.
    """
    K, M = rtt.shape
    drv, donate = _resolve_drivers(cfg, K, M, drivers, n_clients, active)
    run = build_sim_fn(strategy_name, cfg, K, M, **strategy_kw)
    with _quiet_donation():
        return jax.jit(run, donate_argnums=donate)(rtt, drv, key)


def run_sim_batch(
    strategy_name: str,
    rtts: jax.Array,             # (S, K, M) one base RTT matrix per lane
    cfg: SimConfig,
    keys: jax.Array,             # (S, 2) one PRNG key per scenario
    n_clients: jax.Array | None = None,   # (T, K), shared across scenarios
    active: jax.Array | None = None,      # (T, M), shared across scenarios
    drivers: Drivers | None = None,       # shared OR (S, ·) batched pytree
    **strategy_kw,
) -> SimOutputs:
    """Vmap the scenario axis: one compiled program for all S seeds.

    Returns SimOutputs with a leading (S,) axis on every field. The
    evaluation grid's per-strategy seeds share every static shape, so
    batching them removes S-1 compilations and lets XLA overlap the
    scenario lanes. A ``drivers`` batch from ``stack_drivers`` gives
    every lane its own compiled scenario; a plain ``Drivers`` (or the
    legacy kwargs) is shared across lanes. Defaulted driver buffers are
    donated. This is the trace-mode batch driver; the streaming,
    device-sharded grid is ``run_sim_grid``.
    """
    S, K, M = rtts.shape
    drv, donate = _resolve_drivers(cfg, K, M, drivers, n_clients, active)
    # lane-batched detection keys off `active` ((T, M) unbatched,
    # (S, T, M) batched): the tenant engine's UNBATCHED n_clients is
    # already (T, S_tenants, K) = ndim 3, so n_clients can't tell
    batched = drv.active.ndim == 3
    run = build_sim_fn(strategy_name, cfg, K, M, **strategy_kw)
    with _quiet_donation():
        return jax.jit(jax.vmap(run, in_axes=(0, 0 if batched else None, 0)),
                       donate_argnums=donate)(rtts, drv, keys)


def _mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _stream_specs(mesh, lead: tuple = (), ctrl_on: bool = False,
                  rec_on: bool = False, tn_S: int = 0):
    """``shard_map`` specs for a (possibly vmapped) streaming run.

    Resolved per field through the logical rule table
    (``repro.sharding.partitioning``): the player-dimension of ``rtt``,
    the (T, K) driver rows and the per-player accumulator fields carry
    the logical ``players`` axis; everything instance- or fleet-wide
    (queues-adjacent (M,) schedules, the reduced accumulator fields,
    the ``StepSeries`` scalars) is replicated across player shards.
    ``lead`` prepends logical axes for a leading batch dimension —
    ``("grid",)`` for the lane axis of the evaluation grid. Mesh axes
    absent from ``mesh`` drop out, so the same specs serve the 1-D grid
    mesh and the 2-D (``data``, ``players``) continuum mesh.

    ``tn_S`` > 0 is the multi-tenant layout: ``n_clients`` carries a
    replicated (S,) tenant axis between T and K, the accumulator slot
    is an S-tuple of per-tenant accumulator specs, and the series
    scalars gain a trailing replicated tenant axis. The tenant axis is
    NEVER sharded — tenants share the fleet, so every device simulates
    all S services for its players/lanes.
    """
    from repro.sharding import logical_to_spec

    def spec(*axes):
        return logical_to_spec(tuple(lead) + axes, mesh)

    in_specs = (
        spec("players", None),                    # rtt (K, M)
        Drivers(
            n_clients=(spec(None, None, "players") if tn_S
                       else spec(None, "players")),   # (T[, S], K)
            active=spec(None, None),              # (T, M) — replicated
            rtt_scale=spec(None, None),
            rtt_cut_k=spec(None, "players"),      # (T, K)
            rtt_cut_m=spec(None, None),
            s_m=spec(None, None),
            marks=spec(None)),
        spec(None),                               # key (2,)
    )
    acc_spec = qm.MetricAccumulator(
        succ_kc=spec("players", None),
        n_kc=spec("players", None),
        arrivals_m=spec(None),                    # psum-reduced
        choice_counts=spec("players", None),
        proc_hist=spec(None, None),               # psum-reduced
        regret_k=spec("players"),
        vb_k=spec("players"),
        prev_mu=spec("players", None),
        steps_measured=spec(),                    # replicated by design
        ev_succ=spec(None, None),                 # psum-reduced
        ev_n=spec(None, None),                    # psum-reduced
        att_k=spec("players"),
        timeout_k=spec("players"),
        drop_k=spec("players"),
        open_km=spec("players", None))
    series_spec = (
        StepSeries(succ=spec(None, None), issued=spec(None, None),
                   regret=spec(None, None), attempts=spec(None, None))
        if tn_S else
        StepSeries(succ=spec(None), issued=spec(None),
                   regret=spec(None), attempts=spec(None)))
    out_specs = StreamOutputs(
        acc=(tuple(acc_spec for _ in range(tn_S)) if tn_S
             else acc_spec),
        series=series_spec,
        ctrl=(None if not ctrl_on else qc.ControlCounters(
            shed_k=spec("players"),               # per-player, shard-local
            admit_frac_sum=spec(),                # replicated by design
            scale_up=spec(),
            scale_down=spec(),
            migrations=spec(),
            ctrl_up_m=spec(None),                 # fleet-level, replicated
            steps=spec())),
        rec=(None if not rec_on else obr.RecorderState(
            # each shard keeps its own ring; the out-spec concatenates
            # them along the players axis ((cap,) -> (D*cap,)) and the
            # (1,) pointers to (D,) — recorder_events splits them back
            step=spec("players"),
            kind=spec("players"),
            entity=spec("players"),
            value=spec("players"),
            ptr=spec("players"),
            prev_open=spec("players", None))))
    return in_specs, out_specs


def build_sim_grid_fn(
    strategy_name: str,
    cfg: SimConfig,
    K: int,
    M: int,
    mesh=None,
    warmup_steps: int = 0,
    fused: bool = True,
    **strategy_kw,
):
    """Traceable sharded evaluation grid: ``(run_grid, mesh)``.

    ``run_grid(rtts, drivers, keys)`` is the vmapped streaming run
    (``run_sim_batch`` shape, ``trace=False``) with the scenario/seed
    axis ``shard_map``-ed over the ``data`` axis of ``mesh`` — a 1-D
    mesh from ``launch.mesh.make_grid_mesh()`` by default. ``drivers``
    is an (S, ·)-batched ``Drivers`` pytree (``scenarios.stack_drivers``
    of compiled scenarios), so scenario *diversity* — surges, failures,
    drift, per-instance slowdowns — spreads across devices exactly
    like seeds do. Grid lanes are independent, so each device scans
    its own S/D scenarios with per-device ``MetricAccumulator``/
    ``StepSeries`` carries; outputs stay device-sharded along the
    scenario axis until the caller reads them. When the mesh has a
    single device the plain ``jax.vmap`` body is returned unwrapped —
    bit-for-bit the pre-sharding grid program.

    A 2-D (``data``, ``players``) mesh (``make_continuum_mesh``) adds
    the second scaling axis: lanes still spread over ``data``, and
    *inside* every lane the K players split over ``players``
    (``PlayerSharding`` program: per-round arrival psum, shard-local
    maintenance, reduced fleet metrics — see ``build_sim_parts``). K
    must then divide the ``players`` axis size; lane results are
    unchanged (counting stats exact, psum-reduced floats to f32
    tolerance, tests/test_sharded_players.py).

    S not divisible by the data-axis size is handled inside the traced
    function by padding with copies of the last scenario lane and
    slicing the pad back off — wasted lanes, never wrong results. On a
    2-D mesh the traced pad is refused (an XLA sharding-propagation
    bug mis-distributes a concat feeding the 2-axis ``shard_map``);
    ``run_sim_grid`` pads eagerly instead. Sharded and unsharded grids
    run the same per-lane program, so results match the single-device
    vmap exactly on every accumulator field
    (tests/test_sharded_grid.py, tests/test_sharded_players.py).

    Exposed AOT-style (like ``build_sim_fn``) so harnesses can
    ``jit(...).lower()`` it and measure compile time apart from run
    time (benchmarks/common.py::get_suite).
    """
    from repro.launch.mesh import make_grid_mesh

    mesh = make_grid_mesh() if mesh is None else mesh
    sizes = _mesh_axis_sizes(mesh)
    Dp = sizes.get("players", 1)
    Dd = int(mesh.devices.size) // Dp
    pshard = None
    if Dp > 1:
        if K % Dp:
            raise ValueError(
                f"K={K} players must be a multiple of the {Dp}-way "
                f"'players' axis of the grid mesh (pad K or reshape "
                f"the mesh)")
        pshard = PlayerSharding("players", Dp)
    run = build_sim_fn(strategy_name, cfg, K, M, fused=fused, trace=False,
                       warmup_steps=warmup_steps, pshard=pshard,
                       **strategy_kw)
    vrun = jax.vmap(run, in_axes=(0, 0, 0))
    if int(mesh.devices.size) == 1:
        return vrun, mesh

    in_specs, out_specs = _stream_specs(mesh, lead=("grid",),
                                        ctrl_on=qc.control_enabled(cfg),
                                        rec_on=obr.recorder_enabled(cfg),
                                        tn_S=qt.tenancy_size(cfg))
    if pshard is None:
        inner = jax.shard_map(vrun, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
    else:
        # the per-shard program needs its global player ids as a
        # SHARDED OPERAND (see build_sim_parts) — one arange(K) split
        # over the players axis, broadcast over lanes by the vmap
        from repro.sharding import logical_to_spec
        vrun_p = jax.vmap(
            lambda rtts_, drv_, keys_, pids_: run(rtts_, drv_, keys_,
                                                  pids=pids_),
            in_axes=(0, 0, 0, None))
        inner_p = jax.shard_map(
            vrun_p, mesh=mesh,
            in_specs=(*in_specs, logical_to_spec(("players",), mesh)),
            out_specs=out_specs, check_vma=False)

        def inner(rtts_, drv_, keys_):
            return inner_p(rtts_, drv_, keys_,
                           jnp.arange(K, dtype=jnp.int32))

    def _pad_lanes(x, pad):
        return jnp.concatenate([x, jnp.repeat(x[-1:], pad, 0)])

    def run_grid(rtts, drivers, keys):
        S = rtts.shape[0]
        pad = (-S) % Dd
        if pad and pshard is not None:
            # In-trace padding feeds a concat into the 2-axis
            # shard_map, and XLA's sharding propagation through it
            # mis-distributes the operands across (data, players) —
            # lanes then simulate with other lanes' data (observed on
            # jax 0.4.37 CPU; neither sharding constraints nor
            # optimization barriers stop it). Pad eagerly instead:
            # run_sim_grid does this automatically.
            raise ValueError(
                f"S={S} lanes must be a multiple of the {Dd}-way data "
                f"axis when the mesh also shards players; pre-pad the "
                f"lane axis (run_sim_grid does) or reshape the mesh")
        if pad:
            rtts = _pad_lanes(rtts, pad)
            keys = _pad_lanes(keys, pad)
            drivers = jax.tree.map(lambda x: _pad_lanes(x, pad), drivers)
        out = inner(rtts, drivers, keys)
        if pad:
            out = jax.tree.map(lambda x: x[:S], out)
        return out

    # drivers that must pre-pad eagerly (run_sim_grid on 2-D meshes)
    # read the lane-axis shard count from here instead of re-deriving
    # the mesh split — one source of truth for the S-divisibility rule
    run_grid.lane_shards = Dd if pshard is not None else 1
    return run_grid, mesh


def run_sim_grid(
    strategy_name: str,
    rtts: jax.Array,             # (S, K, M) one base RTT matrix per lane
    cfg: SimConfig,
    keys: jax.Array,             # (S, 2) one PRNG key per scenario
    n_clients: jax.Array | None = None,   # (T, K), shared across scenarios
    active: jax.Array | None = None,      # (T, M), shared across scenarios
    drivers: Drivers | None = None,       # shared OR (S, ·) batched pytree
    warmup_steps: int = 0,
    mesh=None,
    **strategy_kw,
) -> StreamOutputs:
    """Sharded evaluation grid driver: ``run_sim_batch`` semantics,
    streaming outputs, scenario lanes spread over every device.

    Returns ``StreamOutputs`` with a leading (S,) axis on every field.
    An un-batched ``drivers`` (or the legacy kwargs/defaults) is
    broadcast to every lane; a ``stack_drivers`` batch drives each lane
    with its own scenario. Single-device meshes degrade to the plain
    vmapped streaming grid. Defaulted driver buffers are donated.

    On a 2-D (``data``, ``players``) mesh, lanes not dividing the data
    axis are padded *eagerly* here (copies of the last lane, sliced
    back off the outputs) — the 1-D grid pads inside the traced
    program, but a traced pad feeding the 2-axis ``shard_map``
    trips an XLA sharding-propagation bug (see ``build_sim_grid_fn``).
    """
    S, K, M = rtts.shape
    drv, donate = _resolve_drivers(cfg, K, M, drivers, n_clients, active)
    run_grid, mesh = build_sim_grid_fn(
        strategy_name, cfg, K, M, mesh=mesh, warmup_steps=warmup_steps,
        **strategy_kw)
    pad = (-S) % getattr(run_grid, "lane_shards", 1)
    if pad:
        def _pad(x):
            return jnp.concatenate([x, jnp.repeat(x[-1:], pad, 0)])
        rtts = _pad(rtts)
        keys = _pad(keys)
        if drv.active.ndim == 3:        # lane-batched (see run_sim_batch)
            drv = jax.tree.map(_pad, drv)
    S_run = S + pad
    fn = run_grid
    if drv.active.ndim == 2:
        # shared schedule -> one lane per scenario; broadcast INSIDE
        # the traced program so the host never materializes S copies
        # of identical (T, ·) buffers
        def fn(rtts_, drv_, keys_):
            drv_b = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (S_run,) + x.shape),
                drv_)
            return run_grid(rtts_, drv_b, keys_)
    with _quiet_donation(), span("sim.grid_dispatch"):
        out = jax.jit(fn, donate_argnums=donate)(rtts, drv, keys)
    if pad:
        out = jax.tree.map(lambda x: x[:S], out)
    return out


def build_sim_players_fn(
    strategy_name: str,
    cfg: SimConfig,
    K: int,
    M: int,
    mesh=None,
    warmup_steps: int = 0,
    fused: bool = True,
    **strategy_kw,
):
    """Traceable player-sharded single simulation: ``(run, mesh)``.

    ``run(rtt, drivers, key)`` is ONE streaming simulation whose player
    axis K is ``shard_map``-ed over the ``players`` axis of ``mesh`` —
    by default ``launch.mesh.make_continuum_mesh()``, which puts every
    device on the player axis. Each device holds K/D players' bandit
    state (rings, weights, KDE stats — the O(K·M·R) memory), scans
    only its shard's selection/feedback/maintenance, and the round
    loop ``psum``s the (M,) per-round arrival vector before the shared
    queue drain (the only in-loop collective; the queues themselves
    stay replicated). Outputs are a full-K ``StreamOutputs``: the
    per-player accumulator fields concatenate across shards, the
    fleet-level fields are psum-reduced. Matches the unsharded engine
    — counting statistics exactly, the psum-reduced regret series to
    f32 reassociation tolerance (tests/test_sharded_players.py).

    The ``players`` axis size must divide K. A mesh whose ``players``
    axis is 1 (or absent) falls back to the plain streaming program —
    bit-for-bit what ``run_sim_stream`` runs.
    """
    from repro.launch.mesh import make_continuum_mesh

    mesh = make_continuum_mesh() if mesh is None else mesh
    Dp = _mesh_axis_sizes(mesh).get("players", 1)
    if Dp == 1:
        run = build_sim_fn(strategy_name, cfg, K, M, fused=fused,
                           trace=False, warmup_steps=warmup_steps,
                           **strategy_kw)
        return run, mesh
    if K % Dp:
        raise ValueError(
            f"K={K} players must be a multiple of the {Dp}-way "
            f"'players' mesh axis")
    from repro.sharding import logical_to_spec

    run = build_sim_fn(strategy_name, cfg, K, M, fused=fused, trace=False,
                       warmup_steps=warmup_steps,
                       pshard=PlayerSharding("players", Dp), **strategy_kw)
    in_specs, out_specs = _stream_specs(mesh,
                                        ctrl_on=qc.control_enabled(cfg),
                                        rec_on=obr.recorder_enabled(cfg),
                                        tn_S=qt.tenancy_size(cfg))
    # global player ids ride in as a sharded operand (see
    # build_sim_parts): the shard's identity arrives on the same data
    # path as its rtt rows
    inner = jax.shard_map(
        lambda rtt, drv, key, pids: run(rtt, drv, key, pids=pids),
        mesh=mesh, in_specs=(*in_specs, logical_to_spec(("players",), mesh)),
        out_specs=out_specs, check_vma=False)

    def sharded_run(rtt, drivers, key):
        return inner(rtt, drivers, key, jnp.arange(K, dtype=jnp.int32))

    return sharded_run, mesh


def run_sim_players(
    strategy_name: str,
    rtt: jax.Array,              # (K, M)
    cfg: SimConfig,
    key: jax.Array,
    n_clients: jax.Array | None = None,   # (T, K)
    active: jax.Array | None = None,      # (T, M)
    drivers: Drivers | None = None,       # compiled scenario
    warmup_steps: int = 0,
    mesh=None,
    **strategy_kw,
) -> StreamOutputs:
    """Player-sharded streaming driver: ``run_sim_stream`` semantics,
    the K load balancers of ONE simulation split across devices.

    This is the giant-fleet mode: the K=1000 × M=50 cell's ~37 MB of
    bandit state splits D ways, opening K ≫ 10⁴ fleets whose state
    would not fit (or not fit comfortably) on one device — see
    docs/SCALING.md for choosing between this and the grid axis, and
    ``make_continuum_mesh(players=...)`` for splitting devices between
    the two. Defaulted driver buffers are donated; a 1-way player mesh
    degrades to the plain streaming program.
    """
    K, M = rtt.shape
    drv, donate = _resolve_drivers(cfg, K, M, drivers, n_clients, active)
    run, mesh = build_sim_players_fn(
        strategy_name, cfg, K, M, mesh=mesh, warmup_steps=warmup_steps,
        **strategy_kw)
    with _quiet_donation():
        return jax.jit(run, donate_argnums=donate)(rtt, drv, key)


def run_sim_stream(
    strategy_name: str,
    rtt: jax.Array,              # (K, M)
    cfg: SimConfig,
    key: jax.Array,
    n_clients: jax.Array | None = None,   # (T, K)
    active: jax.Array | None = None,      # (T, M)
    drivers: Drivers | None = None,       # compiled scenario
    warmup_steps: int = 0,
    chunk_steps: int | None = None,
    mesh=None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 1,
    resume: bool = False,
    stop_at_step: int | None = None,
    **strategy_kw,
) -> StreamOutputs:
    """Streaming run: O(K·M) device memory, O(T) scalar series on host.

    ``chunk_steps`` bounds the compiled scan length: the horizon is
    driven in fixed-size chunks whose carry (strategy state + queue +
    accumulator) is donated back to the next chunk, so device memory is
    independent of ``cfg.horizon``. A trailing remainder chunk compiles
    one extra program; pick ``chunk_steps`` dividing ``num_steps`` to
    avoid it. Chunked and unchunked runs follow the identical per-step
    program on the identical PRNG stream.

    ``mesh`` with a >1 ``players`` axis routes to ``run_sim_players``
    (the player-sharded program); that path does not compose with
    ``chunk_steps`` yet — the sharded scan's memory is already O(K·M/D)
    + O(T) scalars, so chunking only matters for extreme horizons.

    ``checkpoint_dir`` makes the chunked loop fault-tolerant: every
    ``checkpoint_every`` chunks the donated carry plus the series
    drained so far are committed atomically via
    ``checkpoint.Checkpointer`` (snapshot on the caller thread, write
    async). ``resume=True`` restarts from the latest checkpoint in the
    directory — the per-step PRNG stream makes the resumed run equal
    the uninterrupted one *exactly*, for any ``chunk_steps``, and an
    empty directory degrades to a cold start. ``stop_at_step`` halts
    the loop at a chunk boundary >= that step and returns the partial
    result — the hook the kill/resume test (and any external
    orchestrator draining a budget) uses. All three require
    ``chunk_steps``.
    """
    K, M = rtt.shape
    T = cfg.num_steps
    if mesh is not None and _mesh_axis_sizes(mesh).get("players", 1) > 1:
        if chunk_steps is not None:
            raise ValueError(
                "player sharding and chunk_steps do not compose yet: "
                "the donated carry holds shard-local maintenance groups "
                "that cannot round-trip the shard_map boundary")
        return run_sim_players(
            strategy_name, rtt, cfg, key, n_clients=n_clients,
            active=active, drivers=drivers, warmup_steps=warmup_steps,
            mesh=mesh, **strategy_kw)
    drv, donate = _resolve_drivers(cfg, K, M, drivers, n_clients, active)
    if chunk_steps is None or chunk_steps >= T:
        if checkpoint_dir is not None or stop_at_step is not None:
            raise ValueError(
                "checkpoint_dir/resume/stop_at_step need the chunked "
                "loop: pass chunk_steps < num_steps")
        run = build_sim_fn(strategy_name, cfg, K, M, trace=False,
                           warmup_steps=warmup_steps, **strategy_kw)
        with _quiet_donation():
            return jax.jit(run, donate_argnums=donate)(rtt, drv, key)

    init_fn, chunk_fn = build_sim_chunks(
        strategy_name, cfg, K, M, warmup_steps=warmup_steps, **strategy_kw)
    with span("sim.init"):
        carry, keys = jax.jit(init_fn)(rtt, drv.active[0], key)

    ckpt = None
    start = 0
    parts: list = []          # on-device chunk outputs not yet drained
    done: StepSeries | None = None    # host-side series drained so far
    if checkpoint_dir is not None:
        from repro.checkpoint import Checkpointer
        from repro.obs import provenance as obs_provenance
        ckpt = Checkpointer(checkpoint_dir)
        ckpt_meta = {"config_hash": obs_provenance.config_hash(cfg),
                     "horizon_steps": int(T)}
        if resume and ckpt.latest_step() is not None:
            # the carry from init_fn is only a structure template here:
            # leaf shapes/dtypes come from the npz, so the restored
            # series keeps its true (start,) length
            template = {"carry": carry,
                        "series": StepSeries(*(np.zeros(0, np.float32)
                                               for _ in StepSeries._fields))}
            restored, start = ckpt.restore(template)
            carry = restored["carry"]
            done = jax.device_get(restored["series"])

    def drain() -> StepSeries | None:
        """Fold pending device chunks into the host-side series."""
        nonlocal parts, done
        if parts:
            with span("sim.drain"):
                host = jax.device_get(parts)
                prev = [done] if done is not None else []
                done = StepSeries(*(np.concatenate(
                    [np.asarray(getattr(p, f)) for p in prev + host])
                    for f in StepSeries._fields))
            parts = []
        return done

    # the carry aliases 1:1 to the chunk's output carry, so donation
    # reuses the state/accumulator buffers in place every chunk
    run_chunk = jax.jit(chunk_fn, donate_argnums=(1,))
    chunks_done = 0
    for lo in range(start, T, chunk_steps):
        if stop_at_step is not None and lo >= stop_at_step:
            break
        hi = min(lo + chunk_steps, T)
        with span("sim.chunk_dispatch"):
            carry, ys = run_chunk(
                rtt, carry, jnp.arange(lo, hi),
                qs.slice_drivers(drv, lo, hi), keys[lo:hi])
        parts.append(ys)    # on-device O(chunk) scalars; the loop only
        # depends on the donated carry, so dispatch runs ahead and the
        # single device_get below drains everything at once
        chunks_done += 1
        if ckpt is not None and hi < T and chunks_done % checkpoint_every == 0:
            # save() snapshots to numpy before returning, so the async
            # write never races the next chunk's donation; the manifest
            # meta identifies the run (restore ignores it)
            with span("sim.checkpoint_save"):
                ckpt.save(hi, {"carry": carry, "series": drain()},
                          blocking=False, meta=ckpt_meta)
    series = drain()
    if ckpt is not None:
        with span("sim.checkpoint_wait"):
            ckpt.wait()
    ctl = carry[7]
    # the recorder ring rides the chunked carry (and therefore the
    # checkpoint template above) like any other state — chunked,
    # checkpointed and resumed runs end with the bit-identical ring
    return StreamOutputs(acc=carry[3], series=series,
                         ctrl=ctl.counters if ctl is not None else None,
                         rec=carry[8])
