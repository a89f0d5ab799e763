"""The benchmark's copy of the RTT generator: verbatim where the
program's fits, and the factored form for fleets whose (N, N) matrix
would not."""
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

from bench import topology  # noqa: E402


def test_dense_copy_equals_program_generator():
    from repro.continuum import make_topology
    for K, M, s in ((30, 10, 1), (1000, 50, 3)):
        key = jax.random.PRNGKey(s)
        want = np.asarray(make_topology(key, K, M).lb_instance_rtt())
        got = np.asarray(topology.dense_lb_instance_rtt(key, K, M))
        np.testing.assert_array_equal(got, want)


def test_factored_k100k_matches_dense_ranges():
    """Same distance model: the K=10^5 factored RTTs span the range of
    the dense generator at K=1000, quantile by quantile."""
    key = jax.random.PRNGKey(7)
    dense = np.asarray(topology.dense_lb_instance_rtt(key, 1000, 50))
    fact = np.asarray(topology.factored_lb_instance_rtt(key, 100_000, 50))
    assert fact.shape == (100_000, 50)
    assert np.isfinite(fact).all() and fact.min() >= 0.0
    q = [5, 25, 50, 75, 95]
    np.testing.assert_allclose(np.percentile(fact[fact > 0], q),
                               np.percentile(dense[dense > 0], q),
                               rtol=0.15)
    assert 0.001 < fact[fact > 0].min() and fact.max() < 0.06
    # the instance hosts see themselves at zero RTT, one per instance
    assert (fact == 0).sum() == 50
