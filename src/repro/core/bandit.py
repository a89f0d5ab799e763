"""QEdgeProxy MP-MAB core (paper §IV–V, Algorithms 1–4).

Fully decentralized: the state factorizes over players (load balancers);
no cross-player terms exist anywhere in the update. We still *store* all
K players in one pytree of (K, M, ...) arrays so the whole fleet updates
in a single fused XLA program — the decentralization claim is preserved
because every reduction is over the trailing (per-player) axes only.

State layout (R = ring-buffer capacity per (player, arm)):
  lat_buf (K,M,R) f32   end-to-end latency samples
  ts_buf  (K,M,R) f32   sample timestamps (-inf = empty)
  ptr     (K,M)   i32   ring pointers
  mu_hat  (K,M)   f32   KDE success-probability estimates
  weights (K,M)   f32   routing weights (rows sum to 1 over the pool)
  cw      (K,M)   f32   SWRR current weights
  eps     (K,)    f32   exploration budget epsilon(t)
  err     (K,M)   i32   consecutive-error counters (Alg 2 line 5)
  cooldown_until (K,M) f32
  active  (M,)    bool  instance liveness (Alg 3/4)
  in_pool (K,M)   bool  QoS pool membership Q_k(t)
  explore (K,M)   bool  exploration-pool membership X_k(t)
  r_buf   (K,Rq)  f32   own-request reward ring (QoS_a degradation test)
  rts_buf (K,Rq)  f32   reward timestamps
  rptr    (K,)    i32
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import kde as kde_mod
from repro.core import prand
from repro.core.swrr import swrr_select
from repro.kernels import ops as kernel_ops


class BanditParams(NamedTuple):
    """QoS requirements + algorithm hyperparameters (paper Table I/II)."""

    tau: float = 0.080          # latency threshold [s]
    rho: float = 0.9            # required success ratio
    window: float = 10.0        # sliding window W [s]
    gamma: float = 0.01         # epsilon-decay factor
    eta: float = 0.01           # score smoothing floor
    err_thresh: int = 5         # E_t
    cooldown: float = 10.0      # Delta_cd [s]
    decay_mode: int = 0         # 0: eps*=(1-gamma)  1: eps*=gamma (literal)
    kde_mode: int = 0           # 0: KDE  1: empirical fraction (ablation)
    min_bandwidth: float = 1e-4
    reset_hysteresis: float = 0.0   # QoS_a drop needed to trigger reset
    ucb_coef: float = 0.0       # >0 enables beyond-paper UCB bonus
    unseen_mu: float = -1.0     # <0 => rho - 1e-6 (paper Alg 3 semantics)
    weight_ema: float = 0.0     # beyond-paper: damp weight jumps
    # w <- (1-ema)*w_new + ema*w_old. The paper's undamped update can
    # oscillate near capacity (herd -> overload -> flee); see EXPERIMENTS.md.


class BanditState(NamedTuple):
    lat_buf: jax.Array
    ts_buf: jax.Array
    ptr: jax.Array
    mu_hat: jax.Array
    weights: jax.Array
    cw: jax.Array
    eps: jax.Array
    err: jax.Array
    cooldown_until: jax.Array
    active: jax.Array
    in_pool: jax.Array
    explore: jax.Array
    r_buf: jax.Array
    rts_buf: jax.Array
    rptr: jax.Array

    @property
    def num_players(self) -> int:
        return self.lat_buf.shape[0]

    @property
    def num_arms(self) -> int:
        return self.lat_buf.shape[1]


NEG_INF = -1e30


def init_state(
    num_players: int,
    num_arms: int,
    params: BanditParams,
    ring: int = 64,
    reward_ring: int = 512,
    active: jax.Array | None = None,
    key: jax.Array | None = None,
    pids: jax.Array | None = None,
) -> BanditState:
    """Paper Alg 1 lines 1–5: uniform weights, eps = 1 - rho.

    ``key`` randomizes the SWRR phase. Real deployments are
    asynchronous (each LB ticks on its own clock); in a bulk-synchronous
    simulation identical weights + identical phase would make every
    player pick the *same* arm each round (herding the paper's testbed
    cannot exhibit). A random phase offset restores the async behaviour.

    ``pids`` (optional, (K,) i32 *global* player ids) switches the
    phase draw to player-indexed keying (``prand``), which is what lets
    a player-sharded simulation initialize its shard of the state
    bit-identically to the unsharded engine. The simulator always
    passes it; standalone callers may omit it and get one bulk draw.
    """
    K, M, R = num_players, num_arms, ring
    if active is None:
        active = jnp.ones((M,), dtype=bool)
    act = active.astype(jnp.float32)[None, :] * jnp.ones((K, 1), jnp.float32)
    n_act = jnp.maximum(act.sum(-1, keepdims=True), 1.0)
    if key is None:
        cw0 = jnp.zeros((K, M), jnp.float32)
    elif pids is not None:
        cw0 = prand.player_uniform_row(key, pids, M) / jnp.maximum(n_act, 1.0)
    else:
        cw0 = jax.random.uniform(key, (K, M)) / jnp.maximum(n_act, 1.0)
    return BanditState(
        lat_buf=jnp.zeros((K, M, R), jnp.float32),
        ts_buf=jnp.full((K, M, R), NEG_INF, jnp.float32),
        ptr=jnp.zeros((K, M), jnp.int32),
        mu_hat=jnp.zeros((K, M), jnp.float32),
        weights=act / n_act,
        cw=cw0,
        eps=jnp.full((K,), 1.0 - params.rho, jnp.float32),
        err=jnp.zeros((K, M), jnp.int32),
        cooldown_until=jnp.full((K, M), NEG_INF, jnp.float32),
        active=active,
        in_pool=active[None, :] * jnp.ones((K, M), bool),
        explore=active[None, :] * jnp.ones((K, M), bool),
        r_buf=jnp.zeros((K, reward_ring), jnp.float32),
        rts_buf=jnp.full((K, reward_ring), NEG_INF, jnp.float32),
        rptr=jnp.zeros((K,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Request path (Alg 2): select via SWRR, record feedback, cooldown.
# ---------------------------------------------------------------------------

def select(state: BanditState):
    """SWRR selection for every player. Returns (choice, state, valid)."""
    choice, cw, valid = swrr_select(state.weights, state.cw)
    return choice, state._replace(cw=cw), valid


def _record_control(
    state: BanditState,
    params: BanditParams,
    choice: jax.Array,      # (K,)
    reward: jax.Array,      # (K,) 1/0 QoS outcome
    t: jax.Array,
    mask: jax.Array,        # (K,)
) -> BanditState:
    """Error/cooldown/pool/weight part of one record round (Alg 2
    lines 5-9). Touches only (K, M) fields — the (K, M, R) ring writes
    live in ``record`` / ``record_batch``."""
    K, M, R = state.lat_buf.shape
    kidx = jnp.arange(K)
    old_err = state.err[kidx, choice]
    new_err = jnp.where(reward > 0, 0, old_err + 1).astype(jnp.int32)
    trip = mask & (new_err >= params.err_thresh)
    err = state.err.at[kidx, choice].set(
        jnp.where(mask, jnp.where(trip, 0, new_err), old_err))
    cd = state.cooldown_until.at[kidx, choice].set(
        jnp.where(trip, t + params.cooldown, state.cooldown_until[kidx, choice]))

    # remove tripped arms from the pool immediately and renormalize
    tripped_onehot = jax.nn.one_hot(choice, M, dtype=bool) & trip[:, None]
    in_pool = state.in_pool & ~tripped_onehot
    w = jnp.where(tripped_onehot, 0.0, state.weights)
    wsum = w.sum(-1, keepdims=True)
    # if the tripped arm carried all the weight, spread uniformly over
    # the arms still in the pool (or all active arms as a last resort)
    remaining = in_pool & state.active[None, :]
    rem_any = remaining.any(-1, keepdims=True)
    fallback = jnp.where(
        rem_any, remaining,
        state.active[None, :] & ~tripped_onehot).astype(jnp.float32)
    fallback = fallback / jnp.maximum(fallback.sum(-1, keepdims=True), 1.0)
    weights = jnp.where(wsum > 0, w / jnp.maximum(wsum, 1e-30), fallback)

    # a cooled-down arm must not keep winning on stale SWRR credit
    cw = jnp.where(tripped_onehot, 0.0, state.cw)

    return state._replace(
        err=err, cooldown_until=cd, in_pool=in_pool, weights=weights, cw=cw,
    )


def record(
    state: BanditState,
    params: BanditParams,
    choice: jax.Array,      # (K,) selected arm per player
    latency: jax.Array,     # (K,) end-to-end latency [s]
    t: jax.Array,           # scalar time [s]
    mask: jax.Array,        # (K,) bool: player actually issued a request
) -> BanditState:
    """Record one request per player (Alg 2 lines 4–9), vectorized.

    Masked players leave the state untouched. Repeated calls handle
    multiple requests per player per step; ``record_batch`` ingests all
    of them in one dense ring write instead.
    """
    K, M, R = state.lat_buf.shape
    kidx = jnp.arange(K)
    reward = (latency <= params.tau).astype(jnp.float32)

    # --- latency ring write at (k, choice[k], ptr) ---
    p = state.ptr[kidx, choice]
    lat_buf = state.lat_buf.at[kidx, choice, p].set(
        jnp.where(mask, latency, state.lat_buf[kidx, choice, p]))
    ts_buf = state.ts_buf.at[kidx, choice, p].set(
        jnp.where(mask, t, state.ts_buf[kidx, choice, p]))
    ptr = state.ptr.at[kidx, choice].set(
        jnp.where(mask, (p + 1) % R, p))

    # --- per-player reward ring (for the degradation test) ---
    rp = state.rptr
    r_buf = state.r_buf.at[kidx, rp].set(
        jnp.where(mask, reward, state.r_buf[kidx, rp]))
    rts_buf = state.rts_buf.at[kidx, rp].set(
        jnp.where(mask, t, state.rts_buf[kidx, rp]))
    rptr = jnp.where(mask, (rp + 1) % state.r_buf.shape[1], rp)

    state = state._replace(
        lat_buf=lat_buf, ts_buf=ts_buf, ptr=ptr,
        r_buf=r_buf, rts_buf=rts_buf, rptr=rptr)
    return _record_control(state, params, choice, reward, t, mask)


def record_feedback(
    state: BanditState,
    params: BanditParams,
    choice: jax.Array,      # (K,)
    latency: jax.Array,     # (K,)
    t: jax.Array,
    mask: jax.Array,        # (K,)
) -> BanditState:
    """Control half of one record round: err/cooldown/pool/weights but
    NO ring writes. Pair with ``record_rings_batch`` — the simulator
    interleaves this with selection (so in-step trips still steer the
    remaining rounds, exactly like sequential ``record``) and defers
    the expensive (K, M, R) ring writes to one dense write per step."""
    reward = (latency <= params.tau).astype(jnp.float32)
    return _record_control(state, params, choice, reward, t, mask)


def record_rings_batch(
    state: BanditState,
    params: BanditParams,
    choices: jax.Array,     # (K, C) selected arm per player per round
    latencies: jax.Array,   # (K, C) end-to-end latency [s]
    t: jax.Array,           # scalar time [s] (shared by the batch)
    mask: jax.Array,        # (K, C) bool: request actually issued
) -> BanditState:
    """Ring-buffer half of ``record_batch``: all C requests' latency /
    timestamp / reward samples land in one dense masked write.

    Ring slots are computed with per-(player, arm) offset arithmetic —
    the j-th masked write of the batch to arm m lands at
    ``(ptr + j) % R`` — so the C rounds of (K, M, R)/(K, Rq) writes
    collapse to one pass. Writes that a later same-slot write of the
    same batch would overwrite are dropped up front, so every ring slot
    has at most one writer. Final buffer contents are bit-for-bit what
    C sequential ``record`` calls leave behind; control flow
    (err/trips/weights) is NOT applied here.

    The write is a chain of C ``where`` selects over each buffer, and
    the index lookups are one-hot reductions, not a scatter and
    gathers: a TPU applies a scatter one index at a time (about 70 ns
    an index, masked columns included), so the K·C-index scatter cost
    far more than the O(M·R·C) elementwise pass per player that
    replaces it. ``where`` copies every value bit for bit, the censor
    sentinel and ``inf`` included.
    """
    M, R = state.lat_buf.shape[1:]
    Rq = state.r_buf.shape[1]
    t = jnp.asarray(t, jnp.float32)
    reward = (latencies <= params.tau).astype(jnp.float32)
    maski = mask.astype(jnp.int32)

    def pick(x, hot):           # x[k, c, choices[k, c]] without a gather
        return jnp.where(hot, x, 0).sum(-1)

    # --- latency rings: offset arithmetic over per-(k, arm) ranks ---
    chosen = choices[..., None] == jnp.arange(M)              # (K, C, M)
    onehot = (chosen & mask[..., None]).astype(jnp.int32)
    cnt = jnp.cumsum(onehot, axis=1)                          # inclusive
    total = cnt[:, -1, :]                                     # (K, M)
    rank = pick(cnt - onehot, chosen)                         # exclusive
    p0 = pick(state.ptr[:, None, :], chosen)                  # (K, C)
    tot_c = pick(total[:, None, :], chosen)
    keep = mask & (rank >= tot_c - R)       # drop within-batch overwrites
    # each write's flat (arm, slot) cell of its player's rings; -1: none
    dst = jnp.where(keep, choices * R + (p0 + rank) % R, -1)
    cell = jnp.arange(M * R).reshape(M, R)
    lat_buf, ts_buf = state.lat_buf, state.ts_buf
    for d, lat in zip(jnp.unstack(dst, axis=1),
                      jnp.unstack(latencies, axis=1)):
        hit = d[:, None, None] == cell
        lat_buf = jnp.where(hit, lat[:, None, None], lat_buf)
        ts_buf = jnp.where(hit, t, ts_buf)
    ptr = (state.ptr + total) % R

    # --- per-player reward ring ---
    crank = jnp.cumsum(maski, axis=1) - maski                 # (K, C)
    totk = maski.sum(1)                                       # (K,)
    keep_r = mask & (crank >= totk[:, None] - Rq)
    rslot = jnp.where(keep_r, (state.rptr[:, None] + crank) % Rq, -1)
    r_buf, rts_buf = state.r_buf, state.rts_buf
    for d, r in zip(jnp.unstack(rslot, axis=1),
                    jnp.unstack(reward, axis=1)):
        hit = d[:, None] == jnp.arange(Rq)
        r_buf = jnp.where(hit, r[:, None], r_buf)
        rts_buf = jnp.where(hit, t, rts_buf)
    rptr = (state.rptr + totk) % Rq

    return state._replace(
        lat_buf=lat_buf, ts_buf=ts_buf, ptr=ptr,
        r_buf=r_buf, rts_buf=rts_buf, rptr=rptr)


def record_batch(
    state: BanditState,
    params: BanditParams,
    choices: jax.Array,     # (K, C)
    latencies: jax.Array,   # (K, C)
    t: jax.Array,
    mask: jax.Array,        # (K, C)
) -> BanditState:
    """Ingest all C requests of a step: one dense ring write plus an
    in-order replay of the cheap (K, M) control flow. Bit-for-bit
    equal to C sequential ``record`` calls (tests/test_bandit_batch.py).

    The replay is a ``lax.scan`` over the C columns, so the control
    step is traced once instead of C times (same trick as the
    simulator's round loop — the compile-cost term in C goes away
    while execution order, and therefore every float, is unchanged).
    """
    state = record_rings_batch(state, params, choices, latencies, t, mask)

    def replay(st, x):
        c, l, m = x
        return record_feedback(st, params, c, l, t, m), None

    state, _ = jax.lax.scan(
        replay, state, (choices.T, latencies.T, mask.T))
    return state


# ---------------------------------------------------------------------------
# Request-lifecycle resilience: circuit breakers + censored observations.
#
# The breaker sits BETWEEN the balancer and the wire (Envoy-style outlier
# ejection): the bandit still owns selection, but an arm whose last
# `threshold` attempts all timed out is ejected for `cooldown` seconds
# and traffic re-routes over the remaining pool. After the cooldown the
# arm is half-open: one probe request is admitted, and a single further
# timeout re-trips the breaker while a success closes it fully. The
# state factorizes over players — (K, M) arrays, no cross-player terms —
# so it shards on the `players` mesh axis like the bandit state itself.
# ---------------------------------------------------------------------------


class BreakerState(NamedTuple):
    """Per-(player, arm) circuit breaker state.

    fails      (K, M) i32  consecutive timed-out attempts
    open_until (K, M) f32  ejected until this sim time (NEG_INF = closed)
    """

    fails: jax.Array
    open_until: jax.Array


def breaker_init(num_players: int, num_arms: int) -> BreakerState:
    return BreakerState(
        fails=jnp.zeros((num_players, num_arms), jnp.int32),
        open_until=jnp.full((num_players, num_arms), NEG_INF, jnp.float32))


def breaker_is_open(brk: BreakerState, t: jax.Array) -> jax.Array:
    """(K, M) bool: arm currently ejected for this player."""
    return t < brk.open_until


def breaker_update(
    brk: BreakerState,
    choice: jax.Array,      # (K,) arm each player attempted
    timed_out: jax.Array,   # (K,) bool: the attempt exceeded its timeout
    attempted: jax.Array,   # (K,) bool: player actually sent the attempt
    t: jax.Array,
    threshold: int,
    cooldown: float,
) -> BreakerState:
    """Advance the breaker after one attempt per player.

    A success fully closes the breaker (counter and ejection cleared); a
    timeout increments the consecutive-failure counter and, at
    `threshold`, opens the arm for `cooldown` seconds. The counter is
    left at `threshold - 1` while open so the post-cooldown half-open
    probe re-trips on a single failure.
    """
    K, M = brk.fails.shape
    kidx = jnp.arange(K)
    old_f = brk.fails[kidx, choice]
    new_f = jnp.where(timed_out, old_f + 1, 0).astype(jnp.int32)
    trip = attempted & (new_f >= threshold)
    new_f = jnp.where(trip, threshold - 1, new_f)
    old_ou = brk.open_until[kidx, choice]
    new_ou = jnp.where(trip, t + cooldown,
                       jnp.where(timed_out, old_ou, NEG_INF))
    return BreakerState(
        fails=brk.fails.at[kidx, choice].set(
            jnp.where(attempted, new_f, old_f)),
        open_until=brk.open_until.at[kidx, choice].set(
            jnp.where(attempted, new_ou, old_ou)))


def breaker_reset_arms(brk: BreakerState, changed: jax.Array) -> BreakerState:
    """Clear breaker columns for arms whose liveness changed (Alg 3/4
    placement events reset the bandit's per-arm data the same way)."""
    row = changed[None, :]
    return BreakerState(
        fails=jnp.where(row, 0, brk.fails),
        open_until=jnp.where(row, NEG_INF, brk.open_until))


def masked_pick(weights: jax.Array, ok: jax.Array,
                gumbel: jax.Array) -> jax.Array:
    """(K,) weighted sample over the arms allowed by `ok` via the Gumbel
    trick: argmax(log w + g) restricted to `ok`. Zero-weight allowed
    arms keep a tiny floor so a pool whose weight mass is entirely
    masked out still routes somewhere instead of an arbitrary arm 0."""
    score = jnp.log(weights + 1e-30) + gumbel
    return jnp.argmax(jnp.where(ok, score, NEG_INF), axis=-1)


def breaker_veto(
    choice: jax.Array,      # (K,) the bandit's pick
    brk: BreakerState,
    t: jax.Array,
    weights: jax.Array,     # (K, M) current routing weights
    active: jax.Array,      # (M,) instance liveness
    gumbel: jax.Array,      # (K, M) pre-drawn Gumbel noise
    mask: jax.Array,        # (K,) bool: player issues a request this round
) -> jax.Array:
    """Post-selection ejection mask: if the chosen arm is open, re-route
    to a weighted pick over closed active arms. Fails open — when every
    active arm is ejected the veto is waived entirely (shedding all
    traffic would be strictly worse than probing an ejected arm)."""
    K, M = weights.shape
    open_now = breaker_is_open(brk, t)
    ok = active[None, :] & ~open_now
    ok = jnp.where(ok.any(-1, keepdims=True), ok, active[None, :])
    alt = masked_pick(weights, ok, gumbel)
    blocked = mask & open_now[jnp.arange(K), choice]
    return jnp.where(blocked, alt, choice)


def retry_pick(
    weights: jax.Array,          # (K, M)
    active: jax.Array,           # (M,)
    avoid: jax.Array,            # (K,) the arm that just timed out
    open_now: jax.Array | None,  # (K, M) bool, or None when breakers off
    gumbel: jax.Array,           # (K, M)
) -> jax.Array:
    """Re-selection for a retry attempt: weighted pick over active,
    breaker-closed arms excluding the arm that just failed. Degrades
    gracefully rather than refusing to route: if nothing is closed the
    breaker constraint is dropped, and if the failed arm is the only
    active one it is retried."""
    K, M = weights.shape
    ok = active[None, :] & (jnp.arange(M)[None, :] != avoid[:, None])
    if open_now is not None:
        okb = ok & ~open_now
        ok = jnp.where(okb.any(-1, keepdims=True), okb, ok)
    ok = jnp.where(ok.any(-1, keepdims=True), ok, active[None, :])
    return masked_pick(weights, ok, gumbel)


def censored_latency(attempt_timeout: float, tau: float) -> float:
    """Imputed observation for a timed-out (right-censored) attempt.

    The client only learns `latency > attempt_timeout`; we record the
    lower bound pushed strictly past the QoS threshold so the attempt
    counts as a miss and the KDE sees a pessimistic point mass above
    tau. This biases mu_hat for slow arms DOWN — the safe direction for
    a load balancer (an arm that times out looks worse than it might
    be, never better). Static Python float: both knobs are config."""
    return max(float(attempt_timeout), float(tau)) + float(tau)


# ---------------------------------------------------------------------------
# Maintenance (Alg 1): pools, KDE estimates, scores, weights, eps schedule.
# ---------------------------------------------------------------------------

def _rolling_qos(state: BanditState, t, window):
    """(QoS over [t-W, t), QoS over [t-2W, t-W)) per player."""
    ts = state.rts_buf
    cur_m = (ts >= t - window) & (ts < t)
    prev_m = (ts >= t - 2 * window) & (ts < t - window)

    def mean(mask):
        n = mask.sum(-1)
        s = (state.r_buf * mask).sum(-1)
        return jnp.where(n > 0, s / jnp.maximum(n, 1), 1.0), n

    cur, ncur = mean(cur_m)
    prev, nprev = mean(prev_m)
    return cur, prev, ncur, nprev


def maintenance(
    state: BanditState,
    params: BanditParams,
    rtt: jax.Array,     # (K, M) current network RTT estimates [s]
    t: jax.Array,       # scalar time [s]
    lb_mask: jax.Array | None = None,   # (K,) bool: players updating now
) -> BanditState:
    """One decision step of Alg 1 (lines 6–30), vectorized over players.

    ``lb_mask`` restricts the update to a subset of players. Real
    deployments run each proxy's maintenance timer on its own clock;
    staggering the decision steps avoids the synchronized-rebalance
    oscillation a bulk-synchronous update would introduce.
    """
    K, M, R = state.lat_buf.shape

    # --- window mask over latency samples ---
    win = (state.ts_buf >= t - params.window) & (state.ts_buf < t) \
        & (state.ts_buf > NEG_INF / 2)

    # --- fused per-(player, arm) window stats: Silverman-bandwidth KDE
    # success probability (line 12) + rho-quantile of the processing
    # component (line 8). One VMEM pass on TPU (kernels/kde.py), the
    # bit-identical jnp composition elsewhere (kernels/ref.py). ---
    if params.kde_mode == 0:
        mu_flat, proc_q_flat = kernel_ops.bandit_maintenance_stats(
            state.lat_buf.reshape(K * M, R), win.reshape(K * M, R),
            rtt.reshape(K * M), params.tau, params.rho,
            min_bandwidth=params.min_bandwidth)
        mu = mu_flat.reshape(K, M)
        proc_q = proc_q_flat.reshape(K, M)
    else:
        proc = jnp.maximum(state.lat_buf - rtt[..., None], 0.0)
        proc_q = kde_mod.masked_quantile(proc, win, params.rho)   # (K, M)
        mu = kde_mod.empirical_success_prob(state.lat_buf, win, params.tau)

    # --- best expected processing latency l^{p*} (line 8 / Alg 3 line 1) ---
    big = jnp.finfo(jnp.float32).max
    any_obs = (win.sum((-1, -2)) > 0)                             # (K,)
    l_p_star = jnp.where(any_obs, jnp.min(proc_q, axis=-1), 0.0)  # optimistic 0 if no data
    l_p_star = jnp.where(l_p_star >= big, 0.0, l_p_star)

    # --- feasible set F_k(t) (line 9) ---
    not_cd = t >= state.cooldown_until
    feasible = (rtt + l_p_star[:, None] <= params.tau) & not_cd \
        & state.active[None, :]
    n_samples = win.sum(-1)
    unseen_mu = params.unseen_mu if params.unseen_mu >= 0 else params.rho - 1e-6
    mu = jnp.where(n_samples > 0, mu, unseen_mu)   # Alg 3: unseen => top explore score
    if params.ucb_coef > 0.0:                       # beyond-paper option
        total = jnp.maximum(n_samples.sum(-1, keepdims=True), 1.0)
        bonus = params.ucb_coef * jnp.sqrt(
            jnp.log(total) / jnp.maximum(n_samples, 1.0))
        mu = jnp.clip(mu + jnp.where(n_samples > 0, bonus, 0.0), 0.0, 1.0)

    # --- pools (lines 13-19) ---
    exploit = feasible & (mu >= params.rho)
    explore = feasible & (mu < params.rho)
    in_pool = exploit | explore

    # --- budgets & scores (lines 20-22) ---
    eps = state.eps
    s_e = jnp.where(exploit, (mu - params.rho) + params.eta, 0.0)
    s_x = jnp.where(explore, mu + params.eta, 0.0)
    sum_e = s_e.sum(-1, keepdims=True)
    sum_x = s_x.sum(-1, keepdims=True)
    has_e = sum_e[..., 0] > 0
    has_x = sum_x[..., 0] > 0
    # pool budgets; an empty pool donates its budget to the other
    w_e_budget = jnp.where(has_x, 1.0 - eps, 1.0) * has_e
    w_x_budget = jnp.where(has_e, eps, 1.0) * has_x
    w = s_e / jnp.maximum(sum_e, 1e-30) * w_e_budget[:, None] \
        + s_x / jnp.maximum(sum_x, 1e-30) * w_x_budget[:, None]
    # fallback: nothing feasible => uniform over active (keep traffic flowing)
    none = ~(has_e | has_x)
    uni = state.active.astype(jnp.float32)[None, :]
    uni = uni / jnp.maximum(uni.sum(-1, keepdims=True), 1.0)
    weights = jnp.where(none[:, None], uni, w)

    if params.weight_ema > 0.0:     # beyond-paper damping (see above)
        mixed = (1.0 - params.weight_ema) * weights \
            + params.weight_ema * state.weights
        # stay inside the new pool: zero out arms that left it
        mixed = jnp.where(in_pool | none[:, None], mixed, 0.0)
        msum = mixed.sum(-1, keepdims=True)
        weights = jnp.where(msum > 0, mixed / jnp.maximum(msum, 1e-30),
                            weights)

    # --- exploration schedule (lines 24-29) ---
    cur, prev, ncur, nprev = _rolling_qos(state, t, params.window)
    degraded = (ncur > 0) & (nprev > 0) \
        & (cur < prev - params.reset_hysteresis)
    if params.decay_mode == 0:
        eps_next = eps * (1.0 - params.gamma)
    else:
        eps_next = eps * params.gamma
    eps = jnp.where(degraded, 1.0 - params.rho, eps_next)

    # keep SWRR state bounded & consistent with the new pool
    cw = jnp.where(in_pool | none[:, None], state.cw, 0.0)

    if lb_mask is not None:
        keep = ~lb_mask
        mu = jnp.where(keep[:, None], state.mu_hat, mu)
        weights = jnp.where(keep[:, None], state.weights, weights)
        cw = jnp.where(keep[:, None], state.cw, cw)
        eps = jnp.where(keep, state.eps, eps)
        in_pool = jnp.where(keep[:, None], state.in_pool, in_pool)
        explore = jnp.where(keep[:, None], state.explore, explore)

    return state._replace(
        mu_hat=mu, weights=weights, cw=cw, eps=eps,
        in_pool=in_pool, explore=explore,
    )


def maintenance_subset(
    state: BanditState,
    params: BanditParams,
    rtt: jax.Array,         # (K, M)
    t: jax.Array,
    player_idx: jax.Array,  # (P,) i32 players due now; >= K entries = padding
) -> BanditState:
    """Alg 1 for a fixed-size subset of players; everyone else frozen.

    The state factorizes over players, so gather → maintenance →
    scatter commits exactly what ``maintenance(..., lb_mask)`` would for
    the same players, at ~P/K of the O(K·M·R) estimate+sort cost. The
    simulator's staggered decision clocks touch only ~K/H_d players per
    step, which is where the saving lands. ``player_idx`` entries must
    be unique (scatter rows would race otherwise); padding uses K.
    """
    K = state.lat_buf.shape[0]
    safe = jnp.minimum(player_idx, K - 1)

    sub = state._replace(
        lat_buf=state.lat_buf[safe], ts_buf=state.ts_buf[safe],
        ptr=state.ptr[safe], mu_hat=state.mu_hat[safe],
        weights=state.weights[safe], cw=state.cw[safe], eps=state.eps[safe],
        err=state.err[safe], cooldown_until=state.cooldown_until[safe],
        in_pool=state.in_pool[safe], explore=state.explore[safe],
        r_buf=state.r_buf[safe], rts_buf=state.rts_buf[safe],
        rptr=state.rptr[safe])                  # active is (M,): shared
    out = maintenance(sub, params, rtt[safe], t)

    tgt = jnp.where(player_idx < K, player_idx, K)      # drop padding rows
    return state._replace(
        mu_hat=state.mu_hat.at[tgt].set(out.mu_hat, mode="drop"),
        weights=state.weights.at[tgt].set(out.weights, mode="drop"),
        cw=state.cw.at[tgt].set(out.cw, mode="drop"),
        eps=state.eps.at[tgt].set(out.eps, mode="drop"),
        in_pool=state.in_pool.at[tgt].set(out.in_pool, mode="drop"),
        explore=state.explore.at[tgt].set(out.explore, mode="drop"),
    )


# ---------------------------------------------------------------------------
# Placement events (Alg 3 / Alg 4).
# ---------------------------------------------------------------------------

def instance_added(
    state: BanditState,
    params: BanditParams,
    m_new: jax.Array,          # scalar arm index
    rtt: jax.Array,            # (K, M)
    t: jax.Array,
) -> BanditState:
    """Alg 3: activate arm; join pools lazily with weight 0.

    Reachability (l^n + l^{p*} <= tau) is re-checked per player at the
    next maintenance step; here we clear stale feedback and mark active.
    """
    K, M, R = state.lat_buf.shape
    onehot = jax.nn.one_hot(m_new, M, dtype=bool)
    return state._replace(
        active=state.active | onehot,
        lat_buf=jnp.where(onehot[None, :, None], 0.0, state.lat_buf),
        ts_buf=jnp.where(onehot[None, :, None], NEG_INF, state.ts_buf),
        ptr=jnp.where(onehot[None, :], 0, state.ptr),
        err=jnp.where(onehot[None, :], 0, state.err),
        cooldown_until=jnp.where(onehot[None, :], NEG_INF, state.cooldown_until),
        # weight 0 until next maintenance (paper: w_{k,m_new} <- 0)
        weights=jnp.where(onehot[None, :], 0.0, state.weights),
        mu_hat=jnp.where(onehot[None, :], params.rho - 1e-6, state.mu_hat),
    )


def sync_active(
    state: BanditState,
    params: BanditParams,
    new_active: jax.Array,     # (M,) bool target liveness
) -> BanditState:
    """Vectorized Alg 3 + Alg 4 against a target liveness vector.

    Arms turning OFF are purged and weights renormalized (Alg 4); arms
    turning ON are reset with weight 0 and optimistic mu (Alg 3). Useful
    for elastic-scaling events where several replicas change at once.
    """
    added = new_active & ~state.active          # (M,)
    removed = state.active & ~new_active
    changed = (added | removed)[None, :]        # (K, M) broadcast
    w = jnp.where(removed[None, :], 0.0, state.weights)
    wsum = w.sum(-1, keepdims=True)
    unif = new_active.astype(jnp.float32)[None, :]
    unif = unif / jnp.maximum(unif.sum(-1, keepdims=True), 1.0)
    weights = jnp.where(wsum > 0, w / jnp.maximum(wsum, 1e-30), unif)
    weights = jnp.where(added[None, :], 0.0, weights)   # Alg 3: start at 0
    return state._replace(
        active=new_active,
        in_pool=state.in_pool & ~removed[None, :],
        explore=state.explore & ~removed[None, :],
        weights=weights,
        cw=jnp.where(changed, 0.0, state.cw),
        lat_buf=jnp.where(changed[..., None], 0.0, state.lat_buf),
        ts_buf=jnp.where(changed[..., None], NEG_INF, state.ts_buf),
        ptr=jnp.where(changed, 0, state.ptr),
        err=jnp.where(changed, 0, state.err),
        cooldown_until=jnp.where(changed, NEG_INF, state.cooldown_until),
        mu_hat=jnp.where(added[None, :], params.rho - 1e-6, state.mu_hat),
    )


def instance_removed(state: BanditState, m_rem: jax.Array) -> BanditState:
    """Alg 4: purge local data for the arm; renormalize weights."""
    K, M, R = state.lat_buf.shape
    onehot = jax.nn.one_hot(m_rem, M, dtype=bool)
    w = jnp.where(onehot[None, :], 0.0, state.weights)
    wsum = w.sum(-1, keepdims=True)
    uni = state.active & ~onehot
    unif = uni.astype(jnp.float32)[None, :]
    unif = unif / jnp.maximum(unif.sum(-1, keepdims=True), 1.0)
    weights = jnp.where(wsum > 0, w / jnp.maximum(wsum, 1e-30), unif)
    return state._replace(
        active=state.active & ~onehot,
        in_pool=state.in_pool & ~onehot[None, :],
        explore=state.explore & ~onehot[None, :],
        weights=weights,
        cw=jnp.where(onehot[None, :], 0.0, state.cw),
        lat_buf=jnp.where(onehot[None, :, None], 0.0, state.lat_buf),
        ts_buf=jnp.where(onehot[None, :, None], NEG_INF, state.ts_buf),
        ptr=jnp.where(onehot[None, :], 0, state.ptr),
        err=jnp.where(onehot[None, :], 0, state.err),
        cooldown_until=jnp.where(onehot[None, :], NEG_INF, state.cooldown_until),
    )
