"""The paper's random variable Q: unbiasedness, diversity observables."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import sampling
from repro.data.sampling import (
    DrawPlan,
    bernoulli_weights,
    draw_plan,
    draws_from_plan,
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


# ------------------------------------------- the planned draw, bit for bit
# ``bernoulli_weights`` with a ``DrawPlan`` mirrors jax 0.9's
# ``_binomial`` (``_binomial_inversion`` and ``_btrs``) and reads the
# partitionable threefry counters row by row. These cases pin it to
# ``jax.random.binomial`` as the benchmark's reference calls it: a jax
# upgrade that changes either fails here.
ROWS = 200_000


def _profile(name: str) -> np.ndarray:
    from bench.data import zipf_multiplicity

    rng = np.random.default_rng(11)
    if name == "ones":
        return np.ones(ROWS, np.float32)
    if name == "zipf":
        return zipf_multiplicity(ROWS, ROWS)
    if name == "zipf_shuffled":
        return rng.permutation(zipf_multiplicity(ROWS, ROWS))
    if name == "zeros_mixed":
        return rng.integers(0, 4, ROWS).astype(np.float32)
    assert name == "one_huge"
    m = np.ones(ROWS, np.float32)
    m[ROWS // 3] = 1e6
    return m


def _reference(key, rate, m):
    """``bench/reference.draws``' expression: Binomial(m, R) / R."""
    rate = jnp.broadcast_to(jnp.float32(rate), m.shape)
    counts = jax.random.binomial(key, m, rate)
    return (counts / rate).astype(jnp.float32), counts > 0


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("planned", [True, False], ids=["plan", "no_plan"])
@pytest.mark.parametrize("rate", [0.8, 0.3, 0.5, 1.0])
@pytest.mark.parametrize(
    "profile", ["ones", "zipf", "zipf_shuffled", "zeros_mixed", "one_huge"])
def test_draw_matches_jax_binomial(profile, rate, planned):
    m = jnp.asarray(_profile(profile))
    drawn = draw_plan(m, rate) if planned else m
    if planned:
        assert isinstance(drawn, DrawPlan)
    draw = jax.jit(lambda k, d: bernoulli_weights(k, rate, d))
    reference = jax.jit(lambda k, m: _reference(k, rate, m))
    for i in range(5):
        k = jax.random.fold_in(jax.random.PRNGKey(19), i)
        for got, want in zip(draw(k, drawn), reference(k, m)):
            np.testing.assert_array_equal(_bits(got), _bits(want))


def test_planned_draw_tiers_match_jax_binomial(monkeypatch):
    """Smaller blocks and more tiers than the defaults, so that every tier
    of the stand-ins' loop runs at this size."""
    monkeypatch.setattr(sampling, "_BLOCK", 32)
    monkeypatch.setattr(sampling, "_TIERS", (1024, 64, 8))
    m = jnp.asarray(_profile("zipf_shuffled"))
    plan = draw_plan(m, 0.8)
    draw = jax.jit(lambda k, p: bernoulli_weights(k, 0.8, p))
    reference = jax.jit(lambda k, m: _reference(k, 0.8, m))
    for i in range(5):
        k = jax.random.fold_in(jax.random.PRNGKey(23), i)
        for got, want in zip(draw(k, plan), reference(k, m)):
            np.testing.assert_array_equal(_bits(got), _bits(want))


def test_plan_falls_back_to_jax_binomial_for_other_keys():
    """An ``rbg`` key has no per-row counters: the plan is not used."""
    m = jnp.asarray(_profile("zipf"))
    plan = draw_plan(m, 0.8)
    k = jax.random.key(5, impl="rbg")
    assert not draws_from_plan(k, 0.8, plan)
    assert draws_from_plan(jax.random.PRNGKey(5), 0.8, plan)
    for got, want in zip(bernoulli_weights(k, 0.8, plan), _reference(k, 0.8, m)):
        np.testing.assert_array_equal(_bits(got), _bits(want))


def test_plan_lists_the_rows_that_loop():
    m = jnp.asarray([0.0, 1.0, 2.0, 12.0, 13.0, 50.0, 1.0])
    plan = draw_plan(m, 0.8)  # q = 0.2: BTRS where m * q > 10
    assert plan.inversion_rows.tolist() == [2, 3, 4, 5]
    assert plan.btrs_rows.tolist() == []
    plan = draw_plan(m, 0.3)  # BTRS where m > 33.3
    assert plan.inversion_rows.tolist() == [2, 3, 4]
    assert plan.btrs_rows.tolist() == [5]
    two_d = jnp.ones((4, 2))
    assert draw_plan(two_d, 0.8) is two_d
    assert draw_plan(m, np.full(7, 0.8)) is m
    with pytest.raises(ValueError, match="rate"):
        bernoulli_weights(jax.random.PRNGKey(0), 0.5, plan)


def test_async_runtime_draws_from_plan_bit_identically(monkeypatch):
    """One worker (a fixed schedule): the trees and F after a few folds
    equal those of the same run through ``jax.random.binomial``."""
    from repro import obs
    from repro.core.sgbdt import SGBDTConfig
    from repro.data.synthetic import make_dense_low_diversity
    from repro.ps import runtime
    from repro.trees.learner import LearnerConfig

    data = make_dense_low_diversity(3000, 6, 60_000, seed=2)
    cfg = SGBDTConfig(n_trees=5, step_length=0.3, sampling_rate=0.8,
                      learner=LearnerConfig(depth=3, n_bins=64))
    planned_rt = runtime.AsyncRuntime(cfg, data, n_workers=1)
    plan = planned_rt.plan
    assert plan.inversion_rows.shape[0] > 0 and plan.btrs_rows.shape[0] > 0
    obs.enable()
    try:
        obs.drain()
        planned, _ = planned_rt.run(seed=4)
        counts = obs.drain().counts
    finally:
        obs.disable()
    assert counts.get("sample.plan", 0) == cfg.n_trees + 1  # the run's own warm-up too
    assert "sample.full" not in counts
    monkeypatch.setattr(runtime, "draw_plan", lambda multiplicity, rate: multiplicity)
    full, _ = runtime.AsyncRuntime(cfg, data, n_workers=1).run(seed=4)
    for a, b in ((planned.forest.feature, full.forest.feature),
                 (planned.forest.threshold, full.forest.threshold),
                 (planned.forest.leaf_value, full.forest.leaf_value),
                 (planned.f, full.f)):
        np.testing.assert_array_equal(_bits(a), _bits(b))
