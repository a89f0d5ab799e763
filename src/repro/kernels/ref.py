"""Pure-jnp oracles for every Pallas kernel in this package.

These are the numerical ground truth: each kernel's test sweeps shapes
and dtypes and asserts allclose against the function here. They are
also the XLA fallback path used on non-TPU backends (and for the
CPU-hosted dry-run, where Mosaic cannot lower).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_NEG = -1e30


# ---------------------------------------------------------------------------
# Attention (prefill): causal GQA with optional sliding window.
# ---------------------------------------------------------------------------

def attention(
    q: jax.Array,            # (B, Hq, S, D)
    k: jax.Array,            # (B, Hkv, S, D)
    v: jax.Array,            # (B, Hkv, S, D)
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qg = qf.reshape(B, Hkv, G, S, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kf)
    idx = jnp.arange(S)
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= idx[:, None] >= idx[None, :]
    if window is not None:
        mask &= idx[:, None] - idx[None, :] < window
    logits = jnp.where(mask[None, None, None], logits, _NEG)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
    return out.reshape(B, Hq, S, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention (decode): one query token against a KV cache.
# ---------------------------------------------------------------------------

def decode_attention(
    q: jax.Array,            # (B, Hq, D)
    k: jax.Array,            # (B, Hkv, S, D) cache
    v: jax.Array,            # (B, Hkv, S, D)
    length: jax.Array,       # (B,) valid cache entries
    scale: float | None = None,
) -> jax.Array:
    B, Hq, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Hkv, G, D)
    logits = jnp.einsum("bhgd,bhkd->bhgk", qf, k.astype(jnp.float32))
    valid = jnp.arange(S)[None, :] < length[:, None]          # (B, S)
    logits = jnp.where(valid[:, None, None, :], logits, _NEG)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba-2 SSD: exact sequential recurrence (the semantic definition).
# ---------------------------------------------------------------------------

def ssd(
    x: jax.Array,            # (B, S, H, P) inputs per head
    dt: jax.Array,           # (B, S, H) softplus'd step sizes (>0)
    A: jax.Array,            # (H,) negative state decay rates
    Bm: jax.Array,           # (B, S, N) input projections (ngroups=1)
    Cm: jax.Array,           # (B, S, N) output projections
) -> jax.Array:
    """y_t = C_t^T h_t;  h_t = exp(A dt_t) h_{t-1} + dt_t B_t x_t^T.

    State h has shape (H, N, P) per batch element. Returns (B, S, H, P).
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = Bm.astype(jnp.float32)
    Cf = Cm.astype(jnp.float32)

    def scan_one(b):
        def step(h, inp):
            xt, dtt, Bt, Ct = inp                  # (H,P) (H,) (N,) (N,)
            decay = jnp.exp(Af * dtt)              # (H,)
            h = h * decay[:, None, None] + (
                dtt[:, None, None] * Bt[None, :, None] * xt[:, None, :])
            y = jnp.einsum("n,hnp->hp", Ct, h)
            return h, y

        h0 = jnp.zeros((H, N, P), jnp.float32)
        _, ys = jax.lax.scan(step, h0, (xf[b], dtf[b], Bf[b], Cf[b]))
        return ys                                   # (S, H, P)

    out = jax.vmap(scan_one)(jnp.arange(Bsz))
    return out.astype(x.dtype)


def ssd_decode_step(
    h: jax.Array,            # (B, H, N, P) carried state
    x: jax.Array,            # (B, H, P) current token input
    dt: jax.Array,           # (B, H)
    A: jax.Array,            # (H,)
    Bm: jax.Array,           # (B, N)
    Cm: jax.Array,           # (B, N)
):
    """Single-token SSD update (serving decode). Returns (h', y)."""
    decay = jnp.exp(A[None, :] * dt)                          # (B, H)
    h = h * decay[..., None, None] + (
        dt[..., None, None] * Bm[:, None, :, None] * x[:, :, None, :])
    y = jnp.einsum("bn,bhnp->bhp", Cm, h)
    return h, y.astype(x.dtype)


# ---------------------------------------------------------------------------
# KDE success probability (the bandit's per-decision hot spot).
# ---------------------------------------------------------------------------

# erf(x) ~= x * P(x^2) / Q(x^2) on [-4, 4], the f32 rational
# approximation XLA itself lowers erf to (max abs error ~1e-7).
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08,
          -2.10102402082508e-06, -5.69250639462346e-05,
          -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


def erf(x: jax.Array) -> jax.Array:
    """f32 erf from clamp, multiply, add and divide only.

    Mosaic has no lowering for ``lax.erf``; the kernels and every jnp
    oracle call this one definition, so the two paths stay bit-identical
    in interpret mode."""
    x = jnp.clip(x.astype(jnp.float32), -4.0, 4.0)
    x2 = x * x
    p = jnp.float32(_ERF_P[0])
    for c in _ERF_P[1:]:
        p = p * x2 + jnp.float32(c)
    q = jnp.float32(_ERF_Q[0])
    for c in _ERF_Q[1:]:
        q = q * x2 + jnp.float32(c)
    return jnp.clip(x * p / q, -1.0, 1.0)


def normal_cdf(x: jax.Array) -> jax.Array:
    """Standard normal CDF, Phi(x) = (1 + erf(x / sqrt 2)) / 2."""
    return 0.5 * (1.0 + erf(x * 0.7071067811865476))


def kde_success_prob(
    lat: jax.Array,          # (rows, R) latency windows
    mask: jax.Array,         # (rows, R) validity
    tau: float,
    bandwidth: jax.Array,    # (rows,)
) -> jax.Array:
    m = mask.astype(jnp.float32)
    n = m.sum(-1)
    z = (tau - lat.astype(jnp.float32)) / bandwidth[:, None]
    cdf = normal_cdf(z)
    s = (cdf * m).sum(-1)
    return jnp.where(n > 0, s / jnp.maximum(n, 1.0), 0.0)


def _bitonic_sort_rows(x: jax.Array) -> jax.Array:
    """Ascending per-row sort of a (rows, R) array, R a power of two,
    as a branchless bitonic network (21 min/max stages at R=64).

    XLA:CPU lowers ``jnp.sort`` to a scalar comparator loop — ~35 ms
    for the (5000, 64) maintenance batch at K=1000×M=50, which made
    the rho-quantile the single hottest op of the whole simulator. The
    network is pure reshape+minimum/maximum, so it vectorizes.

    Bit-exactness: for finite values with no -0.0 (the processing
    quantile input is ``max(lat - rtt, 0)`` / finfo.max fill), the
    ascending multiset of a row is unique, so the output is
    bit-identical to ``jnp.sort``.
    """
    rows, R = x.shape
    assert R & (R - 1) == 0, R
    k = 2
    while k <= R:
        j = k // 2
        while j >= 1:
            x4 = x.reshape(rows, R // (2 * j), 2, j)
            lo, hi = x4[:, :, 0, :], x4[:, :, 1, :]
            mn, mx = jnp.minimum(lo, hi), jnp.maximum(lo, hi)
            # ascending iff bit k of the element's global index is 0
            blk = jnp.arange(R // (2 * j)) * (2 * j)
            asc = ((blk & k) == 0)[None, :, None]
            x = jnp.stack(
                (jnp.where(asc, mn, mx), jnp.where(asc, mx, mn)),
                axis=2).reshape(rows, R)
            j //= 2
        k *= 2
    return x


def bandit_maintenance_stats(
    lat: jax.Array,          # (rows, R) latency windows
    mask: jax.Array,         # (rows, R) validity (bool)
    rtt: jax.Array,          # (rows,) network RTT per row
    tau: float,
    rho: float,
    min_bandwidth: float = 1e-4,
):
    """Fused Alg-1 window stats per (player, arm) row: Silverman
    bandwidth -> Gaussian-CDF success probability at tau, plus the
    masked rho-quantile of the processing component max(lat - rtt, 0).

    Oracle for ``kernels/kde.py::fused_maintenance``. Mirrors the
    repro/core/kde.py composition op-for-op (bit-identical on CPU);
    kept self-contained because importing repro.core here would close a
    core -> kernels -> core cycle. Returns ``(mu (rows,), q (rows,))``.
    """
    latf = lat.astype(jnp.float32)
    m = mask.astype(jnp.float32)

    # Silverman bandwidth h = 1.06 * sigma * n^(-1/5) (core silverman_bandwidth)
    nc = jnp.maximum(m.sum(-1), 1.0)
    mean = (latf * m).sum(-1) / nc
    var = ((latf - mean[..., None]) ** 2 * m).sum(-1) / nc
    sigma = jnp.sqrt(jnp.maximum(var, 0.0))
    h = jnp.maximum(1.06 * sigma * nc ** (-0.2), min_bandwidth)

    # Gaussian-kernel CDF estimate of P(lat <= tau) (core kde_success_prob)
    n = m.sum(-1)
    z = (tau - latf) / h[..., None]
    cdf = normal_cdf(z)
    contrib = (cdf * m).sum(-1)
    mu = jnp.where(n > 0, contrib / jnp.maximum(n, 1.0), 0.0)

    # masked rho-quantile of processing latency (core masked_quantile)
    proc = jnp.maximum(latf - rtt[..., None], 0.0)
    big = jnp.asarray(jnp.finfo(jnp.float32).max, jnp.float32)
    filled = jnp.where(mask, proc, big)
    R = lat.shape[-1]
    if R & (R - 1) == 0:
        xs = _bitonic_sort_rows(filled)      # bit-identical, ~10x faster
    else:
        xs = jnp.sort(filled, axis=-1)
    ni = mask.sum(-1)
    idx = jnp.clip((rho * (ni - 1)).astype(jnp.int32), 0, lat.shape[-1] - 1)
    val = jnp.take_along_axis(xs, idx[..., None], axis=-1)[..., 0]
    q = jnp.where(ni > 0, val, big)
    return mu, q


# ---------------------------------------------------------------------------
# Fused simulator round (the per-step hot path).
#
# One call covers ALL C request rounds of one simulator step: SWRR
# selection, the shared (M,)-queue recursion, the per-round feedback
# control (error counters / cooldown trips / weight renormalization)
# and the deferred ring scatter. Mirrors, op for op:
#   repro.core.swrr.swrr_select
#   repro.core.bandit._record_control      (via record_feedback)
#   the round scan in repro.continuum.simulator.build_sim_parts
# and, in result only, repro.core.bandit.record_rings_batch: that writes
# the rings with dense masked selects, ``_ring_scatter`` with a scatter
# to the same slots, and both leave the same rings and pointers.
# Kept self-contained (no repro.core imports) for the same reason as
# ``bandit_maintenance_stats``: core -> kernels -> core would cycle.
#
# Bit-exactness contract (tests/test_round_fused.py): every output is
# bit-identical to the unfused round scan. The two deliberate
# reassociations are provably exact — ``arrivals`` sums integer-valued
# f32 counts (< 2**24), and the batch ring scatter is the proven
# equivalent of C sequential ring writes (tests/test_bandit_batch.py).
# The per-round processing-noise draws arrive PREcomputed as ``z``
# (C, K): each element is the same threefry stream the sequential loop
# draws, just batched (a pure function of (step key, round, player id)).
# ---------------------------------------------------------------------------


class RoundStepOut(NamedTuple):
    """Everything one fused round produces: the updated bandit tensors,
    the shared queue, and the per-request outputs the metric
    accumulator consumes."""
    weights: jax.Array          # (K, M)
    cw: jax.Array               # (K, M)
    err: jax.Array              # (K, M) i32
    cooldown_until: jax.Array   # (K, M)
    in_pool: jax.Array          # (K, M) bool
    lat_buf: jax.Array          # (K, M, R)
    ts_buf: jax.Array           # (K, M, R)
    ptr: jax.Array              # (K, M) i32
    r_buf: jax.Array            # (K, Rq)
    rts_buf: jax.Array          # (K, Rq)
    rptr: jax.Array             # (K,) i32
    q: jax.Array                # (M,) queue after all C rounds
    arrivals: jax.Array         # (M,) requests per instance this step
    choices: jax.Array          # (K, C) i32
    lats: jax.Array             # (K, C)
    procs: jax.Array            # (K, C)


def _ring_scatter(lat_buf, ts_buf, ptr, r_buf, rts_buf, rptr,
                  choices, lats, t, mask, tau):
    """`core.bandit.record_rings_batch`'s rings and pointers, bit for
    bit, written with a scatter where that function selects densely."""
    K, M, R = lat_buf.shape
    C = choices.shape[1]
    Rq = r_buf.shape[1]
    kk = jnp.broadcast_to(jnp.arange(K)[:, None], (K, C))
    t_arr = jnp.broadcast_to(jnp.asarray(t, jnp.float32), (K, C))
    reward = (lats <= tau).astype(jnp.float32)
    maski = mask.astype(jnp.int32)

    onehot = (choices[..., None] == jnp.arange(M)) & mask[..., None]
    cnt = jnp.cumsum(onehot.astype(jnp.int32), axis=1)
    total = cnt[:, -1, :]
    rank = jnp.take_along_axis(
        cnt - onehot.astype(jnp.int32), choices[..., None], axis=2)[..., 0]
    p0 = jnp.take_along_axis(ptr, choices, axis=1)
    slot = (p0 + rank) % R
    tot_c = jnp.take_along_axis(total, choices, axis=1)
    keep = mask & (rank >= tot_c - R)
    slot = jnp.where(keep, slot, R)
    lat_buf = lat_buf.at[kk, choices, slot].set(lats, mode="drop")
    ts_buf = ts_buf.at[kk, choices, slot].set(t_arr, mode="drop")
    ptr = (ptr + total) % R

    crank = jnp.cumsum(maski, axis=1) - maski
    totk = maski.sum(1)
    rslot = (rptr[:, None] + crank) % Rq
    keep_r = mask & (crank >= totk[:, None] - Rq)
    rslot = jnp.where(keep_r, rslot, Rq)
    r_buf = r_buf.at[kk, rslot].set(reward, mode="drop")
    rts_buf = rts_buf.at[kk, rslot].set(t_arr, mode="drop")
    rptr = (rptr + totk) % Rq
    return lat_buf, ts_buf, ptr, r_buf, rts_buf, rptr


def round_step_swrr(
    weights: jax.Array,         # (K, M)
    cw: jax.Array,              # (K, M) SWRR current weights
    err: jax.Array,             # (K, M) i32 consecutive-error counters
    cooldown_until: jax.Array,  # (K, M)
    in_pool: jax.Array,         # (K, M) bool
    active: jax.Array,          # (M,) bool instance liveness
    lat_buf: jax.Array,         # (K, M, R)
    ts_buf: jax.Array,          # (K, M, R)
    ptr: jax.Array,             # (K, M) i32
    r_buf: jax.Array,           # (K, Rq)
    rts_buf: jax.Array,         # (K, Rq)
    rptr: jax.Array,            # (K,) i32
    q: jax.Array,               # (M,) queue at step start
    nc: jax.Array,              # (K,) i32 admitted client slots
    z: jax.Array,               # (C, K) processing-noise factors e^{sigma N}
    rtt_t: jax.Array,           # (K, M) effective RTT this step
    s_m: jax.Array,             # (M,) service-time row
    served_per_round: jax.Array,  # (M,) dt / (C * s_m)
    t: jax.Array,               # scalar sim time [s]
    tau: float,
    err_thresh: int,
    cooldown: float,
    unroll: bool = False,
) -> RoundStepOut:
    """All C SWRR rounds of one step, fused (jnp oracle).

    The round loop stays a scan (rounds are genuinely sequential: each
    sees the queue its predecessors filled) with the C per-round PRNG
    dispatches gone — ``z`` arrives batched. ``unroll`` trades compile
    time and L2 pressure for cross-round fusion; on XLA:CPU the rolled
    loop measured faster at K=1000×M=50 (the unrolled body spills its
    8x (K, M) intermediates), so it is off by default.
    """
    K, M, R = lat_buf.shape
    C = z.shape[0]
    kidx = jnp.arange(K)

    def body(carry, xs):
        w, cw_c, err_c, cd, pool, qc = carry
        r, z_r = xs
        mask = r < nc
        # --- core.swrr.swrr_select ---
        total = w.sum(-1, keepdims=True)
        cw_c = cw_c + w
        choice = jnp.argmax(cw_c, axis=-1)
        onehot_f = jax.nn.one_hot(choice, M, dtype=cw_c.dtype)
        cw_c = cw_c - onehot_f * total
        # --- latency (simulator round_body) ---
        q_seen = qc[choice]
        proc = (q_seen + 1.0) * s_m[choice] * z_r
        lat = rtt_t[kidx, choice] + proc
        # --- core.bandit._record_control ---
        reward = (lat <= tau).astype(jnp.float32)
        old_err = err_c[kidx, choice]
        new_err = jnp.where(reward > 0, 0, old_err + 1).astype(jnp.int32)
        trip = mask & (new_err >= err_thresh)
        err_c = err_c.at[kidx, choice].set(
            jnp.where(mask, jnp.where(trip, 0, new_err), old_err))
        cd = cd.at[kidx, choice].set(
            jnp.where(trip, t + cooldown, cd[kidx, choice]))
        tripped = jax.nn.one_hot(choice, M, dtype=bool) & trip[:, None]
        pool = pool & ~tripped
        w2 = jnp.where(tripped, 0.0, w)
        wsum = w2.sum(-1, keepdims=True)
        remaining = pool & active[None, :]
        rem_any = remaining.any(-1, keepdims=True)
        fallback = jnp.where(
            rem_any, remaining,
            active[None, :] & ~tripped).astype(jnp.float32)
        fallback = fallback / jnp.maximum(
            fallback.sum(-1, keepdims=True), 1.0)
        w = jnp.where(wsum > 0, w2 / jnp.maximum(wsum, 1e-30), fallback)
        cw_c = jnp.where(tripped, 0.0, cw_c)
        # --- shared-queue recursion ---
        arr_r = jax.ops.segment_sum(
            mask.astype(jnp.float32), choice, num_segments=M)
        qc = jnp.maximum(qc + arr_r - served_per_round, 0.0)
        return (w, cw_c, err_c, cd, pool, qc), (choice, lat, proc, arr_r)

    carry, (ch_r, lat_r, proc_r, arr_cr) = jax.lax.scan(
        body, (weights, cw, err, cooldown_until, in_pool, q),
        (jnp.arange(C), z), unroll=C if unroll else 1)
    weights, cw, err, cooldown_until, in_pool, q = carry
    choices, lats, procs = ch_r.T, lat_r.T, proc_r.T
    arrivals = arr_cr.sum(0)                 # integer-valued: order-free
    mask_kc = jnp.arange(C)[None, :] < nc[:, None]
    lat_buf, ts_buf, ptr, r_buf, rts_buf, rptr = _ring_scatter(
        lat_buf, ts_buf, ptr, r_buf, rts_buf, rptr,
        choices, lats, t, mask_kc, tau)
    return RoundStepOut(weights, cw, err, cooldown_until, in_pool,
                        lat_buf, ts_buf, ptr, r_buf, rts_buf, rptr,
                        q, arrivals, choices, lats, procs)


def round_step_gumbel(
    weights: jax.Array,         # (K, M) static routing weights
    q: jax.Array,               # (M,)
    nc: jax.Array,              # (K,) i32
    z: jax.Array,               # (C, K)
    gum: jax.Array,             # (C, K, M) selection Gumbel rows
    rtt_t: jax.Array,           # (K, M)
    s_m: jax.Array,             # (M,)
    served_per_round: jax.Array,  # (M,)
):
    """All C Gumbel-categorical rounds of one step, fully vectorized.

    Stateless strategies (proxy-mity) pick arms from FIXED weights, so
    selection is queue-independent: every round's argmax happens at
    once and only the tiny (M,)-wide queue recursion stays sequential.
    Returns ``(q, arrivals, choices (K, C), lats, procs)``.
    """
    C, K, M = gum.shape
    logits = jnp.log(weights + 1e-30)
    choices_cr = jnp.argmax(logits[None] + gum, axis=-1)       # (C, K)
    mask_cr = jnp.arange(C)[:, None] < nc[None, :]             # (C, K)
    arr_cr = jax.vmap(
        lambda m, c: jax.ops.segment_sum(
            m.astype(jnp.float32), c, num_segments=M))(mask_cr, choices_cr)

    def qbody(qc, xs):
        c_r, a_r = xs
        q_seen = qc[c_r]
        return jnp.maximum(qc + a_r - served_per_round, 0.0), q_seen

    q, qseen_cr = jax.lax.scan(qbody, q, (choices_cr, arr_cr), unroll=C)
    procs_cr = (qseen_cr + 1.0) * s_m[choices_cr] * z
    lats_cr = rtt_t[jnp.arange(K)[None, :], choices_cr] + procs_cr
    arrivals = arr_cr.sum(0)                 # integer-valued: order-free
    return q, arrivals, choices_cr.T, lats_cr.T, procs_cr.T
