"""The benchmark's own RTT generator: a frozen copy of the program's
synthetic European topology (paper §VII-A1) and greedy k-center
placement (§VII-A3), so that a later change to the program cannot move
the yardstick.

``dense_lb_instance_rtt`` is the program's generator, copied verbatim:
cities cluster around Zipf-popular metro centres in a 2400x1800 km box,
RTT = 3 ms + 0.014 ms/km + symmetric pairwise jitter, and the M
instances sit on the nodes that greedy k-center picks from the (N, N)
RTT matrix. It forms that (N, N) matrix, which is fine up to a few
thousand nodes.

``factored_lb_instance_rtt`` is for fleets whose (N, N) matrix would
not fit (K=10^5 is 40 GB): the same node coordinates, placement by
greedy k-center on the Euclidean distances of a node subsample, and the
(K, M) RTT built from coordinates with the jitter drawn per (node,
instance) pair instead of per node pair. Its RTTs have the same range
and distance model; they are not the dense generator's numbers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BASE_MS, MS_PER_KM, JITTER_MS = 3.0, 0.014, 2.0
BOX_KM = (2400.0, 1800.0)
N_CLUSTERS, CLUSTER_SIGMA_KM = 6, 140.0


def _positions(key, n_nodes: int):
    """Node coordinates [km] and the jitter key, as the generator draws
    them."""
    kp, kj, kc, ka = jax.random.split(key, 4)
    centers = jax.random.uniform(kc, (N_CLUSTERS, 2)) * jnp.asarray(BOX_KM)
    pop = 1.0 / (1.0 + jnp.arange(N_CLUSTERS))
    assign = jax.random.categorical(
        ka, jnp.log(pop)[None, :].repeat(n_nodes, 0))
    pos = centers[assign] + CLUSTER_SIGMA_KM * jax.random.normal(
        kp, (n_nodes, 2))
    return pos, kj


def european_rtt_matrix(key, n_nodes: int = 30) -> jax.Array:
    """(N, N) RTT [s], symmetric with a zero diagonal."""
    pos, kj = _positions(key, n_nodes)
    d = jnp.linalg.norm(pos[:, None, :] - pos[None, :, :], axis=-1)
    jit = jax.random.uniform(kj, (n_nodes, n_nodes)) * JITTER_MS
    jit = (jit + jit.T) / 2.0
    rtt_ms = BASE_MS + MS_PER_KM * d + jit
    rtt_ms = rtt_ms * (1.0 - jnp.eye(n_nodes))
    return rtt_ms / 1e3


def k_center_placement(rtt: np.ndarray, n_instances: int) -> np.ndarray:
    """Greedy k-center: start at the medoid, then repeatedly add the node
    farthest from the chosen centres."""
    rtt = np.asarray(rtt)
    centers = [int(np.argmin(rtt.sum(1)))]
    while len(centers) < n_instances:
        d = rtt[:, centers].min(axis=1)
        d[centers] = -1.0
        centers.append(int(np.argmax(d)))
    return np.asarray(sorted(centers), dtype=np.int32)


def dense_lb_instance_rtt(key, n_nodes: int, n_instances: int) -> jax.Array:
    """(K, M) RTT from every proxy (one per node) to every instance."""
    rtt = european_rtt_matrix(key, n_nodes)
    placement = k_center_placement(np.asarray(rtt), n_instances)
    return rtt[:, jnp.asarray(placement)]


def factored_lb_instance_rtt(key, n_nodes: int, n_instances: int,
                             sample: int = 4096) -> jax.Array:
    """(K, M) RTT from coordinates; never forms the (N, N) matrix."""
    pos, kj = _positions(key, n_nodes)
    step = max(1, n_nodes // sample)
    sub = np.asarray(pos[::step])
    dist = np.linalg.norm(sub[:, None, :] - sub[None, :, :], axis=-1)
    centers = k_center_placement(dist, n_instances) * step
    d = jnp.linalg.norm(pos[:, None, :] - pos[jnp.asarray(centers)][None],
                        axis=-1)
    jit = jax.random.uniform(kj, (n_nodes, n_instances)) * JITTER_MS
    rtt_ms = BASE_MS + MS_PER_KM * d + jit
    is_self = jnp.arange(n_nodes)[:, None] == jnp.asarray(centers)[None, :]
    return jnp.where(is_self, 0.0, rtt_ms) / 1e3


def lb_instance_rtt(key, n_nodes: int, n_instances: int,
                    kind: str = "dense") -> jax.Array:
    if kind == "dense":
        return dense_lb_instance_rtt(key, n_nodes, n_instances)
    if kind == "factored":
        return factored_lb_instance_rtt(key, n_nodes, n_instances)
    raise ValueError(f"unknown topology kind {kind!r}")
