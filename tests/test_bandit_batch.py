"""Fused request/maintenance paths vs the sequential references.

Acceptance for the fleet-scale throughput work: ``record_batch`` must
be bit-for-bit a loop of C ``record`` calls, the fused maintenance
kernel (interpret mode) must match the pure-jnp Silverman/KDE/quantile
composition, and the subset/batched drivers must commit exactly what
the full-width versions do.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BanditParams, init_state, maintenance,
                        maintenance_subset, record, record_batch)
from repro.core import kde as core_kde
from repro.core.bandit import censored_latency, record_rings_batch
from repro.kernels import ref
from repro.kernels.kde import fused_maintenance

P = BanditParams()


def _random_trace(rng, K, M, C, full_mask=False, p_mask=0.7, special=None):
    """``special``: a latency planted in about a quarter of the columns,
    which the rings must hold bit for bit."""
    choices = jnp.asarray(rng.integers(0, M, (K, C)), jnp.int32)
    lats = rng.uniform(0.005, 0.3, (K, C))
    if special is not None:
        lats = np.where(rng.random((K, C)) < 0.25, special, lats)
    lats = jnp.asarray(lats, jnp.float32)
    if full_mask:
        mask = jnp.ones((K, C), bool)
    else:
        mask = jnp.asarray(rng.random((K, C)) < p_mask)
    return choices, lats, mask


def _assert_states_equal(a, b):
    for name, xa, xb in zip(a._fields, a, b):
        np.testing.assert_array_equal(
            np.asarray(xa), np.asarray(xb), err_msg=f"field {name}")


@pytest.mark.parametrize("K,M,C,ring,reward_ring,p_mask,special,steps", [
    (5, 4, 6, 8, 16, 0.7, None, 12),        # ring wraps across steps
    (3, 2, 8, 64, 16, 0.7, None, 4),        # several writes per (k, arm)
    (7, 5, 4, 16, 16, 0.7, None, 6),
    # the testbed's retry-storm step: 8 rounds x 3 attempts, about half
    # the columns issued; every reward ring and 50 of the 300 latency
    # rings wrap within the steps
    (30, 10, 24, 64, 512, 0.5, None, 48),
    # the timeout censor sentinel and inf must be copied bit for bit
    (5, 4, 6, 8, 16, 0.7, censored_latency(0.09, 0.08), 12),
    (5, 4, 6, 8, 16, 0.7, np.inf, 12),
])
def test_record_batch_matches_sequential(K, M, C, ring, reward_ring, p_mask,
                                         special, steps):
    rng = np.random.default_rng(0)
    st_a = init_state(K, M, P, ring=ring, reward_ring=reward_ring,
                      key=jax.random.PRNGKey(0))
    st_b = st_a
    for i in range(steps):
        choices, lats, mask = _random_trace(rng, K, M, C, p_mask=p_mask,
                                            special=special)
        t = jnp.float32(i * 0.1)
        st_a = record_batch(st_a, P, choices, lats, t, mask)
        for c in range(C):
            st_b = record(st_b, P, choices[:, c], lats[:, c], t, mask[:, c])
        _assert_states_equal(st_a, st_b)


def test_record_rings_batch_lowers_without_scatter_or_gather():
    """The ring write stays dense at the testbed's shape (K=30, M=10,
    8 rounds x 3 attempts, rings of 64 and 512): a TPU applies a
    scatter or gather one index at a time, and the four K*C-index ring
    scatters once took over a quarter of the simulator step."""
    K, M, C, R, Rq = 30, 10, 24, 64, 512
    st = init_state(K, M, P, ring=R, reward_ring=Rq)
    low = jax.jit(record_rings_batch, static_argnums=1).lower(
        st, P, jnp.zeros((K, C), jnp.int32), jnp.zeros((K, C), jnp.float32),
        jnp.float32(0.0), jnp.zeros((K, C), bool))
    for text in (low.as_text(), low.compile().as_text()):
        assert not re.findall(r'(?:gather|scatter)"?\(', text)


def test_record_batch_overflow_within_batch():
    """C > R on a single arm: the batch overwrites its own oldest
    writes exactly like the sequential ring does."""
    K, M, C, ring = 2, 3, 6, 2
    rng = np.random.default_rng(1)
    st_a = init_state(K, M, P, ring=ring, reward_ring=4)
    st_b = st_a
    choices = jnp.zeros((K, C), jnp.int32)          # everyone hammers arm 0
    lats = jnp.asarray(rng.uniform(0.005, 0.3, (K, C)), jnp.float32)
    mask = jnp.ones((K, C), bool)
    t = jnp.float32(0.5)
    st_a = record_batch(st_a, P, choices, lats, t, mask)
    for c in range(C):
        st_b = record(st_b, P, choices[:, c], lats[:, c], t, mask[:, c])
    _assert_states_equal(st_a, st_b)


def test_record_batch_trips_cooldown_like_sequential():
    params = BanditParams(err_thresh=3, cooldown=5.0)
    K, M, C = 2, 2, 5
    st_a = init_state(K, M, params, ring=8, reward_ring=8)
    st_a = st_a._replace(weights=jnp.asarray([[1.0, 0.0], [1.0, 0.0]]))
    st_b = st_a
    choices = jnp.zeros((K, C), jnp.int32)
    lats = jnp.full((K, C), 1.0, jnp.float32)       # always violates tau
    mask = jnp.ones((K, C), bool)
    t = jnp.float32(0.2)
    st_a = record_batch(st_a, params, choices, lats, t, mask)
    for c in range(C):
        st_b = record(st_b, params, choices[:, c], lats[:, c], t, mask[:, c])
    _assert_states_equal(st_a, st_b)
    assert float(st_a.cooldown_until[0, 0]) > 0.2   # tripped mid-batch


def _driven_state(rng, K, M, ring=32, steps=60):
    st = init_state(K, M, P, ring=ring, reward_ring=64,
                    key=jax.random.PRNGKey(3))
    for i in range(steps):
        choices, lats, mask = _random_trace(rng, K, M, 4)
        st = record_batch(st, P, choices, lats, jnp.float32(i * 0.1), mask)
    return st


def test_maintenance_subset_matches_lb_mask():
    K, M = 6, 4
    rng = np.random.default_rng(2)
    st = _driven_state(rng, K, M)
    rtt = jnp.asarray(rng.uniform(0.002, 0.02, (K, M)), jnp.float32)
    t = jnp.float32(7.0)
    idx = jnp.asarray([4, 1, K, K], jnp.int32)      # padded group
    got = maintenance_subset(st, P, rtt, t, idx)
    lb_mask = jnp.asarray([False, True, False, False, True, False])
    want = maintenance(st, P, rtt, t, lb_mask=lb_mask)
    _assert_states_equal(got, want)


def test_maintenance_fused_stats_path_matches_composition():
    """maintenance() routes KDE+quantile through kernels.ops; on CPU the
    ref path must reproduce the core/kde composition bit for bit."""
    K, M, R = 5, 3, 16
    rng = np.random.default_rng(4)
    st = _driven_state(rng, K, M, ring=R)
    rtt = jnp.asarray(rng.uniform(0.002, 0.02, (K, M)), jnp.float32)
    t = jnp.float32(9.0)
    win = (st.ts_buf >= t - P.window) & (st.ts_buf < t) \
        & (st.ts_buf > -1e30 / 2)
    mu_ref, q_ref = ref.bandit_maintenance_stats(
        st.lat_buf.reshape(K * M, R), win.reshape(K * M, R),
        rtt.reshape(K * M), P.tau, P.rho, P.min_bandwidth)
    bw = core_kde.silverman_bandwidth(st.lat_buf, win, P.min_bandwidth)
    mu_core = core_kde.kde_success_prob(st.lat_buf, win, P.tau, bandwidth=bw)
    proc = jnp.maximum(st.lat_buf - rtt[..., None], 0.0)
    q_core = core_kde.masked_quantile(proc, win, P.rho)
    np.testing.assert_array_equal(np.asarray(mu_ref).reshape(K, M),
                                  np.asarray(mu_core))
    np.testing.assert_array_equal(np.asarray(q_ref).reshape(K, M),
                                  np.asarray(q_core))


@pytest.mark.parametrize("rows,R", [(8, 16), (300, 64), (130, 128)])
def test_fused_maintenance_kernel_matches_ref(rows, R):
    rng = np.random.default_rng(5)
    lat = jnp.asarray(rng.exponential(0.03, (rows, R)), jnp.float32)
    mask = jnp.asarray(rng.random((rows, R)) < 0.7)
    rtt = jnp.asarray(rng.uniform(0.001, 0.02, rows), jnp.float32)
    mu_k, q_k = fused_maintenance(lat, mask, rtt, 0.08, 0.9,
                                  interpret=True)
    mu_r, q_r = ref.bandit_maintenance_stats(lat, mask, rtt, 0.08, 0.9)
    np.testing.assert_allclose(mu_k, mu_r, rtol=2e-5, atol=2e-6)
    # quantile is pure value selection: exact, including empty rows
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))


def test_fused_maintenance_kernel_ties_and_empty_rows():
    lat = jnp.asarray([
        [0.05, 0.05, 0.05, 0.05],       # all ties
        [0.01, 0.02, 0.03, 0.04],
        [0.10, 0.10, 0.20, 0.20],       # duplicate pairs
        [0.00, 0.00, 0.00, 0.00],
    ], jnp.float32)
    mask = jnp.asarray([
        [True, True, True, True],
        [True, False, True, False],
        [True, True, True, True],
        [False, False, False, False],   # empty window
    ])
    rtt = jnp.asarray([0.0, 0.005, 0.02, 0.01], jnp.float32)
    mu_k, q_k = fused_maintenance(lat, mask, rtt, 0.08, 0.9,
                                  interpret=True)
    mu_r, q_r = ref.bandit_maintenance_stats(lat, mask, rtt, 0.08, 0.9)
    np.testing.assert_allclose(mu_k, mu_r, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    assert float(q_k[3]) == np.finfo(np.float32).max


def test_sim_fused_matches_sequential_step_structure():
    """The fused step (deferred ring scatter + interleaved control)
    must be bit-for-bit the sequential fallback, including in the
    overloaded regime where in-step cooldown trips steer later
    rounds."""
    from repro.continuum import (SimConfig, build_sim_fn, make_topology,
                                 neutral_drivers)
    cfg = SimConfig(horizon=12.0, service_time=0.009)   # overloaded
    topo = make_topology(jax.random.PRNGKey(3), 8, 3)
    rtt = topo.lb_instance_rtt()
    T = cfg.num_steps
    drv = neutral_drivers(cfg, 8, 3,
                          n_clients=jnp.full((T, 8), 6, jnp.int32))
    key = jax.random.PRNGKey(42)
    outs_f = jax.jit(build_sim_fn("qedgeproxy", cfg, 8, 3, fused=True))(
        rtt, drv, key)
    outs_s = jax.jit(build_sim_fn("qedgeproxy", cfg, 8, 3, fused=False))(
        rtt, drv, key)
    for name, xf, xs in zip(outs_f._fields, outs_f, outs_s):
        np.testing.assert_array_equal(
            np.asarray(xf), np.asarray(xs), err_msg=f"field {name}")
    # overload must actually have tripped arms, or this test is vacuous
    assert float(np.asarray(outs_f.rewards).mean()) < 0.9


def test_run_sim_batch_matches_per_seed():
    from repro.continuum import SimConfig, run_sim, run_sim_batch
    from repro.continuum import make_topology
    cfg = SimConfig(horizon=6.0)
    rtts, keys = [], []
    for seed in (1, 2):
        topo = make_topology(jax.random.PRNGKey(seed), 8, 4)
        rtts.append(topo.lb_instance_rtt())
        keys.append(jax.random.PRNGKey(100 + seed))
    batched = run_sim_batch("qedgeproxy", jnp.stack(rtts), cfg,
                            jnp.stack(keys))
    for i, seed in enumerate((1, 2)):
        single = run_sim("qedgeproxy", rtts[i], cfg, keys[i])
        for name, xb, xs in zip(single._fields, batched, single):
            np.testing.assert_allclose(
                np.asarray(xb[i]), np.asarray(xs), atol=1e-6,
                err_msg=f"field {name} seed {seed}")
