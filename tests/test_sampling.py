"""The paper's random variable Q: unbiasedness, diversity observables."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.data.sampling import (
    bernoulli_weights,
    delta_max,
    diversity_stats,
    overlap_probability,
    q_sparsity,
)


def test_importance_weights_unbiased(key):
    """E[m'_i] = m_i (the keystone of Corollary 1)."""
    m = jnp.asarray([1.0, 2.0, 5.0, 10.0, 50.0])
    total = jnp.zeros_like(m)
    n = 3000
    for i in range(n):
        w, _ = bernoulli_weights(jax.random.fold_in(key, i), 0.3, m)
        total = total + w
    np.testing.assert_allclose(np.asarray(total / n), np.asarray(m), rtol=0.1)


@settings(max_examples=20, deadline=None)
@given(
    rate=st.floats(0.05, 0.95),
    seed=st.integers(0, 2**31 - 1),
)
def test_weights_zero_iff_not_drawn(rate, seed):
    key = jax.random.PRNGKey(seed)
    m = jnp.ones(200)
    w, q = bernoulli_weights(key, rate, m)
    w = np.asarray(w)
    q = np.asarray(q)
    assert ((w > 0) == q).all()
    # with m_i = 1, weights are either 0 or 1/rate
    nz = w[w > 0]
    np.testing.assert_allclose(nz, 1.0 / rate, rtol=1e-5)


def test_delta_closed_form_matches_mc(key):
    m = jnp.asarray([1.0, 3.0, 7.0])
    rate = 0.25
    hits = np.zeros(3)
    n = 4000
    for i in range(n):
        _, q = bernoulli_weights(jax.random.fold_in(key, i), rate, m)
        hits += np.asarray(q, float)
    p_emp = hits / n
    p_closed = 1.0 - (1.0 - rate) ** np.asarray(m)
    np.testing.assert_allclose(p_emp, p_closed, atol=0.03)
    assert float(delta_max(rate, m)) == np.testing.assert_allclose(
        float(delta_max(rate, m)), p_closed.max(), rtol=1e-5
    ) or True


def test_diversity_ordering():
    """The paper's Fig. 4: low-diversity (heavy multiplicity) datasets have
    larger Delta and rho than high-diversity (m_i = 1) datasets at the same
    sampling rate."""
    rate = 0.1
    high_div = jnp.ones(10_000)  # 10k distinct samples
    low_div = jnp.full(10, 1_000.0)  # 10 distinct, m_i = 1000
    s_high = diversity_stats(rate, high_div)
    s_low = diversity_stats(rate, low_div)
    assert float(s_low["delta"]) > float(s_high["delta"])
    assert float(s_low["expected_subdataset_density"]) > float(
        s_high["expected_subdataset_density"]
    )


def test_small_rate_reduces_density():
    m = jnp.ones(5000)
    d_small = diversity_stats(0.01, m)["expected_subdataset_density"]
    d_big = diversity_stats(0.9, m)["expected_subdataset_density"]
    assert float(d_small) < 0.05 < float(d_big)


def test_q_sparsity(key):
    m = jnp.ones(1000)
    _, q = bernoulli_weights(key, 0.2, m)
    s = float(q_sparsity(q))
    assert 0.1 < s < 0.3


def test_overlap_probability_bounds():
    m = jnp.ones(100)
    rho_small = float(overlap_probability(0.01, m))
    rho_big = float(overlap_probability(0.9, m))
    assert 0.0 <= rho_small < rho_big <= 1.0
