"""Plain reference of the QEdgeProxy fleet simulation: the semantics of
one streaming job, written straight from the paper's algorithms and the
simulator's documented model, in plain ``jax.numpy``.

It imports nothing of the program and reads nothing the program made.
Its inputs are the base RTT matrix and the per-step driver rows that
``bench.traffic`` builds from the mix file, and the job's PRNG key.

What it computes, step by step (``dt`` = one client period):

* placement events (Alg 3/4): arms whose liveness flips are purged;
* staggered maintenance (Alg 1): the players whose decision clock
  fires this step recompute their KDE success estimates over the
  window, their feasible, exploit and explore pools, weights and
  exploration budget;
* the oracle: the closed-form success probability of every arm at the
  step-start queue, and each player's regret against its best arm;
* C request rounds, one client of every proxy per round, in order:
  SWRR selection, the instance's single-worker queue seen at the
  round's start, a lognormal processing time, the request's feedback
  (error counter, cooldown trip, pool and weight repair) and its ring
  write, written immediately as a proxy would; optional per-attempt
  timeouts, deadline-bounded retries and circuit breakers; then the
  queues drain by their per-round service;
* the job's statistics after the warm-up.

The processing noise and the initial SWRR phase are keyed by global
proxy id exactly as the workload defines them, so a job is one
deterministic function of its key. ``dtype`` sets the precision of all
state and arithmetic: float32 is the reference, bfloat16 the control.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
DRIVER_FIELDS = ("n_clients", "active", "rtt_scale", "rtt_cut_k",
                 "rtt_cut_m", "s_m")
# processing-latency histogram edges: 1e-4 s .. 10 s, 127 geometric edges
PROC_EDGES = np.geomspace(1e-4, 10.0, 127).astype(np.float32)
PROC_BINS = 128


class Params(NamedTuple):
    """Everything static about one job (hashable, for ``jit``)."""
    K: int
    M: int
    C: int              # client slots per proxy = request rounds per step
    R: int              # latency ring per (proxy, instance)
    Rq: int             # reward ring per proxy
    T: int              # steps per job
    warmup: int         # steps before the statistics start
    dt: float
    maint_every: int
    sigma: float        # lognormal processing noise
    tau: float
    rho: float
    window: float
    gamma: float
    eta: float
    err_thresh: int
    cooldown: float
    min_bw: float
    attempt_timeout: float = 0.0
    max_retries: int = 0
    retry_backoff: float = 0.0
    retry_deadline: bool = True
    breaker_threshold: int = 0
    breaker_cooldown: float = 0.0


class JobStats(NamedTuple):
    """One job's answer, post-warm-up unless noted."""
    succ: jax.Array          # () requests that met tau
    issued: jax.Array        # () requests issued
    arrivals: jax.Array      # (M,) arrivals per instance (retries included)
    choices: jax.Array       # (K, M) requests per (proxy, instance)
    proc_hist: jax.Array     # (M, PROC_BINS) processing-latency histogram
    regret: jax.Array        # (K,) oracle regret, whole horizon
    attempts: jax.Array      # () attempts, retries included
    timeouts: jax.Array      # () attempts that timed out
    drops: jax.Array         # () requests that never completed
    open_steps: jax.Array    # () (proxy, instance, step) breaker-open count
    regret_steps: jax.Array = None   # (T,) fleet regret of every step
    succ_steps: jax.Array = None     # (T,) requests that met tau, every step
    attempts_steps: jax.Array = None  # (T,) attempts of every step


# XLA's float32 erf (``ErfImpl32`` in xla/client/lib/math.cc): x * P(x^2)
# / Q(x^2) on x clamped to [-4, 4]. Written out so the reference rounds
# alike on every backend (XLA:CPU lowers ``lax.erf`` differently).
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08,
          -2.10102402082508e-06, -5.69250639462346e-05,
          -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


def _erf(x):
    f = x.dtype
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p = jnp.asarray(_ERF_P[0], f)
    for c in _ERF_P[1:]:
        p = p * x2 + jnp.asarray(c, f)
    q = jnp.asarray(_ERF_Q[0], f)
    for c in _ERF_Q[1:]:
        q = q * x2 + jnp.asarray(c, f)
    return jnp.clip(x * p / q, -1.0, 1.0)


def _phi(x):
    return 0.5 * (1.0 + _erf(x * 0.7071067811865476))


def _row_set(x, idx, v):
    """``x[idx] = v`` for one entry per proxy: the indices are unique
    and sorted by proxy, which lets the write run in parallel."""
    return x.at[idx].set(v, indices_are_sorted=True, unique_indices=True)


def _per_player(fn, key, K):
    return jax.vmap(lambda k: fn(jax.random.fold_in(key, k)))(jnp.arange(K))


def _kde_stats(lat, win, rtt, p: Params, f):
    """Per (proxy, arm) window: the Silverman-bandwidth KDE of
    P(latency <= tau), and the rho-quantile of max(latency - rtt, 0)."""
    m = win.astype(f)
    n = m.sum(-1)
    nc = jnp.maximum(n, 1.0)
    mean = (lat * m).sum(-1) / nc
    var = ((lat - mean[..., None]) ** 2 * m).sum(-1) / nc
    h = jnp.maximum(1.06 * jnp.sqrt(jnp.maximum(var, 0.0)) * nc ** -0.2,
                    p.min_bw)
    cdf = _phi((p.tau - lat) / h[..., None])
    mu = jnp.where(n > 0, (cdf * m).sum(-1) / nc, 0.0)
    big = jnp.finfo(f).max
    proc = jnp.where(win, jnp.maximum(lat - rtt[..., None], 0.0), big)
    ni = win.sum(-1)
    idx = jnp.clip((p.rho * (ni - 1)).astype(jnp.int32), 0, p.R - 1)
    val = jnp.take_along_axis(jnp.sort(proc, axis=-1), idx[..., None],
                              axis=-1)[..., 0]
    return mu.astype(f), jnp.where(ni > 0, val, big).astype(f), n


def _maintain(st, rows, rtt_t, t, p: Params, f):
    """Alg 1 for the proxies in ``rows`` (index K = no proxy)."""
    K = p.K
    safe = jnp.minimum(rows, K - 1)
    lat, ts = st["lat"][safe], st["ts"][safe]
    rtt = rtt_t[safe]
    win = (ts >= t - p.window) & (ts < t) & (ts > NEG / 2)
    mu, proc_q, n = _kde_stats(lat, win, rtt, p, f)
    big = jnp.finfo(f).max
    any_obs = win.sum((-1, -2)) > 0
    lps = jnp.where(any_obs, proc_q.min(-1), 0.0)
    lps = jnp.where(lps >= big, 0.0, lps)
    active = st["active"]
    feasible = ((rtt + lps[:, None] <= p.tau)
                & (t >= st["cd"][safe]) & active[None, :])
    mu = jnp.where(n > 0, mu, p.rho - 1e-6)
    exploit = feasible & (mu >= p.rho)
    explore = feasible & (mu < p.rho)
    pool = exploit | explore
    eps = st["eps"][safe]
    s_e = jnp.where(exploit, mu - p.rho + p.eta, 0.0)
    s_x = jnp.where(explore, mu + p.eta, 0.0)
    sum_e = s_e.sum(-1, keepdims=True)
    sum_x = s_x.sum(-1, keepdims=True)
    has_e, has_x = sum_e[:, 0] > 0, sum_x[:, 0] > 0
    b_e = jnp.where(has_x, 1.0 - eps, 1.0) * has_e
    b_x = jnp.where(has_e, eps, 1.0) * has_x
    w = (s_e / jnp.maximum(sum_e, 1e-30) * b_e[:, None]
         + s_x / jnp.maximum(sum_x, 1e-30) * b_x[:, None])
    none = ~(has_e | has_x)
    uni = active.astype(f)[None, :]
    uni = uni / jnp.maximum(uni.sum(-1, keepdims=True), 1.0)
    w = jnp.where(none[:, None], uni, w).astype(f)
    # exploration budget: reset when the own-request QoS of the last
    # window fell below the window before it, else decay
    rts, rb = st["rts"][safe], st["rb"][safe]
    cur_m = (rts >= t - p.window) & (rts < t)
    prev_m = (rts >= t - 2 * p.window) & (rts < t - p.window)

    def qos(mk):
        k = mk.sum(-1)
        return jnp.where(k > 0, (rb * mk).sum(-1) / jnp.maximum(k, 1), 1.0), k

    cur, ncur = qos(cur_m)
    prev, nprev = qos(prev_m)
    degraded = (ncur > 0) & (nprev > 0) & (cur < prev)
    eps = jnp.where(degraded, 1.0 - p.rho, eps * (1.0 - p.gamma)).astype(f)
    cw = jnp.where(pool | none[:, None], st["cw"][safe], 0.0)
    tgt = jnp.where(rows < K, rows, K)
    st = dict(st)
    st["w"] = st["w"].at[tgt].set(w, mode="drop")
    st["cw"] = st["cw"].at[tgt].set(cw, mode="drop")
    st["eps"] = st["eps"].at[tgt].set(eps, mode="drop")
    st["pool"] = st["pool"].at[tgt].set(pool, mode="drop")
    return st


def _placement(st, act, f):
    """Alg 3/4 against the new liveness row."""
    added = act & ~st["active"]
    removed = st["active"] & ~act
    changed = (added | removed)[None, :]
    w = jnp.where(removed[None, :], 0.0, st["w"])
    wsum = w.sum(-1, keepdims=True)
    uni = act.astype(f)[None, :]
    uni = uni / jnp.maximum(uni.sum(-1, keepdims=True), 1.0)
    w = jnp.where(wsum > 0, w / jnp.maximum(wsum, 1e-30), uni)
    w = jnp.where(added[None, :], 0.0, w)
    st = dict(st)
    st.update(
        active=act, pool=st["pool"] & ~removed[None, :], w=w.astype(f),
        cw=jnp.where(changed, 0.0, st["cw"]),
        lat=jnp.where(changed[..., None], 0.0, st["lat"]),
        ts=jnp.where(changed[..., None], NEG, st["ts"]),
        ptr=jnp.where(changed, 0, st["ptr"]),
        err=jnp.where(changed, 0, st["err"]),
        cd=jnp.where(changed, NEG, st["cd"]))
    if "bf" in st:
        st.update(bf=jnp.where(changed, 0, st["bf"]),
                  bo=jnp.where(changed, NEG, st["bo"]))
    return st


def _feedback(st, choice, obs, t, mask, p: Params, f):
    """One request's outcome at its proxy: ring writes, then the error
    counter, the cooldown trip and the weight repair (Alg 2)."""
    K, M = p.K, p.M
    k = jnp.arange(K)
    reward = (obs <= p.tau).astype(f)
    ptr = st["ptr"][k, choice]
    st = dict(st)
    st["lat"] = _row_set(st["lat"], (k, choice, ptr),
                         jnp.where(mask, obs, st["lat"][k, choice, ptr]))
    st["ts"] = _row_set(st["ts"], (k, choice, ptr),
                        jnp.where(mask, t, st["ts"][k, choice, ptr]))
    st["ptr"] = _row_set(st["ptr"], (k, choice),
                         jnp.where(mask, (ptr + 1) % p.R, ptr))
    rp = st["rp"]
    st["rb"] = _row_set(st["rb"], (k, rp),
                        jnp.where(mask, reward, st["rb"][k, rp]))
    st["rts"] = _row_set(st["rts"], (k, rp),
                         jnp.where(mask, t, st["rts"][k, rp]))
    st["rp"] = jnp.where(mask, (rp + 1) % p.Rq, rp)

    old = st["err"][k, choice]
    new = jnp.where(reward > 0, 0, old + 1)
    trip = mask & (new >= p.err_thresh)
    st["err"] = _row_set(st["err"], (k, choice),
                         jnp.where(mask, jnp.where(trip, 0, new), old))
    st["cd"] = _row_set(st["cd"], (k, choice),
                        jnp.where(trip, t + p.cooldown, st["cd"][k, choice]))
    hit = (jnp.arange(M)[None, :] == choice[:, None]) & trip[:, None]
    pool = st["pool"] & ~hit
    w = jnp.where(hit, 0.0, st["w"])
    wsum = w.sum(-1, keepdims=True)
    left = pool & st["active"][None, :]
    back = jnp.where(left.any(-1, keepdims=True), left,
                     st["active"][None, :] & ~hit).astype(f)
    back = back / jnp.maximum(back.sum(-1, keepdims=True), 1.0)
    st["w"] = jnp.where(wsum > 0, w / jnp.maximum(wsum, 1e-30), back
                        ).astype(f)
    st["pool"] = pool
    st["cw"] = jnp.where(hit, 0.0, st["cw"])
    return st


def _breaker(st, choice, timed_out, sent, t, p: Params):
    k = jnp.arange(p.K)
    old_f, old_o = st["bf"][k, choice], st["bo"][k, choice]
    new_f = jnp.where(timed_out, old_f + 1, 0)
    trip = sent & (new_f >= p.breaker_threshold)
    new_f = jnp.where(trip, p.breaker_threshold - 1, new_f)
    new_o = jnp.where(trip, t + p.breaker_cooldown,
                      jnp.where(timed_out, old_o, NEG))
    st = dict(st)
    st["bf"] = _row_set(st["bf"], (k, choice),
                        jnp.where(sent, new_f, old_f))
    st["bo"] = _row_set(st["bo"], (k, choice),
                        jnp.where(sent, new_o, old_o))
    return st


def _weighted_pick(w, ok, g):
    score = jnp.log(w.astype(jnp.float32) + 1e-30) + g
    return jnp.argmax(jnp.where(ok, score, NEG), axis=-1)


def _initial(rtt, act0, key, p: Params, f):
    K, M = p.K, p.M
    k_init, k_phase, k_scan = jax.random.split(key, 3)
    n_act = jnp.maximum(act0.sum(), 1).astype(jnp.float32)
    cw = _per_player(lambda kk: jax.random.uniform(kk, (M,)), k_init, K)
    st = dict(
        lat=jnp.zeros((K, M, p.R), f), ts=jnp.full((K, M, p.R), NEG, f),
        ptr=jnp.zeros((K, M), jnp.int32),
        w=jnp.broadcast_to(act0.astype(f) / n_act.astype(f), (K, M)),
        cw=(cw / n_act).astype(f),
        eps=jnp.full((K,), 1.0 - p.rho, f),
        err=jnp.zeros((K, M), jnp.int32), cd=jnp.full((K, M), NEG, f),
        active=act0, pool=jnp.broadcast_to(act0, (K, M)),
        rb=jnp.zeros((K, p.Rq), f), rts=jnp.full((K, p.Rq), NEG, f),
        rp=jnp.zeros((K,), jnp.int32), q=jnp.zeros((M,), f))
    if p.breaker_threshold > 0:
        st.update(bf=jnp.zeros((K, M), jnp.int32),
                  bo=jnp.full((K, M), NEG, f))
    # decision clocks: proxies tile into blocks of maint_every, and each
    # block's members take the phases of a random permutation
    n = max(p.maint_every, 1)
    n_blocks = -(-K // n)
    perms = jax.vmap(lambda b: jax.random.permutation(
        jax.random.fold_in(k_phase, b), n))(jnp.arange(n_blocks))
    # due[phase, block] = the proxy of that block whose clock fires
    due = (jnp.arange(n_blocks)[None, :] * n
           + jnp.argsort(perms, axis=1).T).astype(jnp.int32)
    due = jnp.where(due < K, due, K)
    return st, due, jax.random.split(k_scan, p.T)


def _stats0(p: Params, f32=jnp.float32):
    z = jnp.zeros((), f32)
    return JobStats(succ=z, issued=z, arrivals=jnp.zeros((p.M,), f32),
                    choices=jnp.zeros((p.K, p.M), f32),
                    proc_hist=jnp.zeros((p.M, PROC_BINS), f32),
                    regret=jnp.zeros((p.K,), f32),
                    attempts=z, timeouts=z, drops=z, open_steps=z)


@functools.partial(jax.jit, static_argnames=("p", "dtype", "steps"))
def run_job(p: Params, rtt, drivers, key, dtype=jnp.float32, steps=None):
    """One job: ``drivers`` is a dict of the per-step rows ``n_clients``
    (T, K), ``active`` (T, M), ``rtt_scale`` (T, M), ``rtt_cut_k``
    (T, K), ``rtt_cut_m`` (T, M) and ``s_m`` (T, M). ``steps`` runs
    only the job's first steps, on the same keys. Returns ``JobStats``
    in float32."""
    f = jnp.dtype(dtype)
    K, M, C = p.K, p.M, p.C
    kidx = jnp.arange(K)
    res_on = p.attempt_timeout > 0
    brk_on = res_on and p.breaker_threshold > 0
    A = 1 + (p.max_retries if res_on else 0)
    censor = max(p.attempt_timeout, p.tau) + p.tau
    rtt = rtt.astype(f)
    st0, due, keys = _initial(rtt, drivers["active"][0], key, p, f)
    edges = jnp.asarray(PROC_EDGES)
    n_ph = max(p.maint_every, 1)

    def step(carry, xs):
        st, acc = carry
        t_idx, nc, act, scale, cut_k, cut_m, s_m, k_step = xs
        t = t_idx.astype(f) * jnp.asarray(p.dt, f)
        s_m = s_m.astype(f)
        rtt_t = (rtt * scale.astype(f)[None, :]
                 + jnp.minimum(cut_k.astype(f)[:, None],
                               cut_m.astype(f)[None, :]))
        st = jax.lax.cond(jnp.any(act != st["active"]),
                          lambda s: _placement(s, act, f), lambda s: s, st)
        st = _maintain(st, due[t_idx % n_ph], rtt_t, t, p, f)

        q = st["q"]
        margin = (p.tau - rtt_t) / ((q[None, :] + 1.0) * s_m[None, :])
        mu = jnp.where(margin > 0, _phi(jnp.log(jnp.maximum(margin, 1e-9))
                                        / p.sigma), 0.0)
        best = jnp.where(act[None, :], mu, -jnp.inf).max(-1)
        got = (st["w"] * jnp.where(act[None, :], mu, 0.0)).sum(-1)
        regret = jnp.maximum(best - got, 0.0)
        open0 = (t < st["bo"]) if brk_on else None
        served = p.dt / (C * s_m)

        def request_round(rc, r):
            st, q, succ, issued, att, tos, drops, arrivals, choice_cnt, hist = rc
            k_r = jax.random.fold_in(k_step, r)
            _, k_noise = jax.random.split(k_r)
            mask = r < nc
            # SWRR: every proxy advances its credits; the winner pays
            w = st["w"]
            cw = st["cw"] + w
            choice = jnp.argmax(cw, axis=-1)
            cw = cw - jnp.where(jnp.arange(M)[None, :] == choice[:, None],
                                w.sum(-1, keepdims=True), 0.0)
            st = dict(st, cw=cw)
            if brk_on:
                g = _per_player(lambda kk: jax.random.gumbel(kk, (M,)),
                                jax.random.fold_in(k_r, 101), K)
                is_open = t < st["bo"]
                ok = act[None, :] & ~is_open
                ok = jnp.where(ok.any(-1, keepdims=True), ok, act[None, :])
                alt = _weighted_pick(st["w"], ok, g)
                choice = jnp.where(mask & is_open[kidx, choice], alt, choice)
            z = jnp.exp(p.sigma * _per_player(
                lambda kk: jax.random.normal(kk), k_noise, K)).astype(f)
            proc = (q[choice] + 1.0) * s_m[choice] * z
            lat = rtt_t[kidx, choice] + proc
            arr = jnp.zeros((M,), jnp.float32).at[choice].add(
                mask.astype(jnp.float32))
            n_att = mask.astype(jnp.float32)
            if not res_on:
                st = _feedback(st, choice, lat, t, mask, p, f)
                lat_out, proc_out, choice_out = lat, proc, choice
                dropped = jnp.zeros((K,), bool)
            else:
                to = mask & (lat > p.attempt_timeout)
                st = (_breaker(st, choice, to, mask, t, p) if brk_on else st)
                st = _feedback(st, choice, jnp.where(to, censor, lat), t,
                               mask, p, f)
                elapsed = jnp.where(mask, jnp.minimum(lat, p.attempt_timeout),
                                    0.0)
                proc_out = jnp.where(to, jnp.minimum(proc, p.attempt_timeout),
                                     proc)
                done = mask & ~to
                choice_out, pending = choice, to
                for a in range(1, A):
                    backoff = p.retry_backoff * 2.0 ** (a - 1)
                    go = pending
                    if p.retry_deadline:
                        go = go & (elapsed + backoff < p.tau)
                    k_pick, k_z = jax.random.split(
                        jax.random.fold_in(k_r, 1000 + a))
                    g = _per_player(lambda kk: jax.random.gumbel(kk, (M,)),
                                    k_pick, K)
                    ok = act[None, :] & (jnp.arange(M)[None, :]
                                         != choice_out[:, None])
                    if brk_on:
                        okb = ok & ~(t < st["bo"])
                        ok = jnp.where(okb.any(-1, keepdims=True), okb, ok)
                    ok = jnp.where(ok.any(-1, keepdims=True), ok,
                                   act[None, :])
                    c_a = jnp.where(go, _weighted_pick(st["w"], ok, g),
                                    choice_out)
                    z_a = jnp.exp(p.sigma * _per_player(
                        lambda kk: jax.random.normal(kk), k_z, K)).astype(f)
                    proc_a = (q[c_a] + 1.0) * s_m[c_a] * z_a
                    lat_a = rtt_t[kidx, c_a] + proc_a
                    to_a = go & (lat_a > p.attempt_timeout)
                    elapsed = jnp.where(
                        go, elapsed + backoff
                        + jnp.minimum(lat_a, p.attempt_timeout), elapsed)
                    st = (_breaker(st, c_a, to_a, go, t, p) if brk_on
                          else st)
                    st = _feedback(st, c_a, jnp.where(to_a, censor, lat_a),
                                   t, go, p, f)
                    arr = arr.at[c_a].add(go.astype(jnp.float32))
                    n_att = n_att + go.astype(jnp.float32)
                    choice_out = jnp.where(go, c_a, choice_out)
                    proc_out = jnp.where(
                        to_a, jnp.minimum(proc_a, p.attempt_timeout),
                        jnp.where(go, proc_a, proc_out))
                    done = done | (go & ~to_a)
                    pending = to_a
                dropped = mask & ~done
                lat_out = jnp.where(done, elapsed, censor)
            q = jnp.maximum(q + arr.astype(f) - served, 0.0)
            mf = mask.astype(jnp.float32)
            ok_req = (lat_out <= p.tau).astype(jnp.float32) * mf
            succ, issued = succ + ok_req.sum(), issued + mf.sum()
            att = att + n_att.sum()
            drops = drops + (dropped.astype(jnp.float32) * mf).sum()
            tos = tos + (n_att - mf * (1.0 - dropped.astype(jnp.float32))
                         ).sum()
            arrivals = arrivals + arr
            choice_cnt = choice_cnt.at[kidx, choice_out].add(
                mf, indices_are_sorted=True, unique_indices=True)
            pbin = jnp.clip(jnp.searchsorted(
                edges, proc_out.astype(jnp.float32)), 0, PROC_BINS - 1)
            hist = hist.at[choice_out, pbin].add(mf)
            return (st, q, succ, issued, att, tos, drops, arrivals,
                    choice_cnt, hist), None

        z0 = jnp.zeros((), jnp.float32)
        (st, q, succ, issued, att, tos, drops, arrivals, choice_cnt,
         hist), _ = jax.lax.scan(
            request_round,
            (st, q, z0, z0, z0, z0, z0, jnp.zeros((M,), jnp.float32),
             jnp.zeros((K, M), jnp.float32),
             jnp.zeros((M, PROC_BINS), jnp.float32)),
            jnp.arange(C))
        st["q"] = q
        meas = (t_idx >= p.warmup).astype(jnp.float32)
        acc = JobStats(
            succ=acc.succ + meas * succ, issued=acc.issued + meas * issued,
            arrivals=acc.arrivals + meas * arrivals,
            choices=acc.choices + meas * choice_cnt,
            proc_hist=acc.proc_hist + meas * hist,
            regret=acc.regret + regret.astype(jnp.float32),
            attempts=acc.attempts + meas * att,
            timeouts=acc.timeouts + meas * tos,
            drops=acc.drops + meas * drops,
            open_steps=acc.open_steps + (
                meas * open0.astype(jnp.float32).sum() if brk_on else 0.0))
        return (st, acc), (regret.astype(jnp.float32).sum(), succ, att)

    n = p.T if steps is None else steps
    xs = (jnp.arange(n), drivers["n_clients"][:n], drivers["active"][:n],
          drivers["rtt_scale"][:n], drivers["rtt_cut_k"][:n],
          drivers["rtt_cut_m"][:n], drivers["s_m"][:n], keys[:n])
    (_, acc), (regret_steps, succ_steps, att_steps) = jax.lax.scan(
        step, (st0, _stats0(p)), xs)
    return acc._replace(regret_steps=regret_steps, succ_steps=succ_steps,
                        attempts_steps=att_steps)
