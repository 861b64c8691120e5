"""Optimizers + the paper's DelayedGradient staleness mechanism."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

import repro.optim as O


TARGET = jnp.asarray([3.0, -1.0, 0.5])


def _grad(w):
    return w - TARGET


def _run(opt, steps=300, w0=None):
    w = jnp.zeros_like(TARGET) if w0 is None else w0
    st_ = opt.init(w)
    for _ in range(steps):
        u, st_ = opt.update(_grad(w), st_, w)
        w = O.apply_updates(w, u)
    return w


def test_sgd_converges():
    assert np.allclose(_run(O.sgd(0.3)), TARGET, atol=1e-3)


def test_sgd_momentum_converges():
    assert np.allclose(_run(O.sgd(0.05, momentum=0.9)), TARGET, atol=1e-2)


def test_adam_converges():
    assert np.allclose(_run(O.adam(0.1), 400), TARGET, atol=1e-2)


def test_adamw_full_recipe():
    opt = O.adamw(0.1, weight_decay=1e-4, max_grad_norm=1.0)
    assert np.allclose(_run(opt, 500), TARGET, atol=5e-2)


def test_clip_by_global_norm():
    opt = O.clip_by_global_norm(1.0)
    st_ = opt.init(TARGET)
    g = jnp.asarray([30.0, 40.0, 0.0])  # norm 50
    u, _ = opt.update(g, st_, TARGET)
    np.testing.assert_allclose(float(jnp.linalg.norm(u)), 1.0, rtol=1e-5)
    u2, _ = opt.update(g / 100, st_, TARGET)  # below max: untouched
    np.testing.assert_allclose(np.asarray(u2), np.asarray(g / 100), rtol=1e-5)


def test_cosine_schedule_shape():
    lr = O.cosine_schedule(1.0, warmup_steps=10, total_steps=100)
    assert float(lr(jnp.asarray(1))) < 0.2
    np.testing.assert_allclose(float(lr(jnp.asarray(10))), 1.0, rtol=1e-5)
    assert float(lr(jnp.asarray(100))) < 0.2


# --------------------------------------------------------------- delayed SGD
def test_delay_zero_is_identity():
    inner = O.sgd(0.3)
    assert O.delayed_gradient(inner, 0) is inner


def test_delayed_warmup_applies_nothing():
    opt = O.delayed_gradient(O.sgd(0.5), delay=3)
    w = jnp.zeros_like(TARGET)
    st_ = opt.init(w)
    for _ in range(3):
        u, st_ = opt.update(_grad(w), st_, w)
        assert np.allclose(np.asarray(u), 0.0)


def test_delayed_applies_stale_gradient_exactly():
    """After warm-up, step t must apply the gradient pushed at t - delay."""
    delay = 2
    opt = O.delayed_gradient(O.sgd(1.0), delay=delay)
    w = jnp.zeros(1)
    st_ = opt.init(w)
    grads = [jnp.asarray([float(i + 1)]) for i in range(5)]
    applied = []
    for g in grads:
        u, st_ = opt.update(g, st_, w)
        applied.append(float(-u[0]))  # sgd(1.0): update = -grad
    assert applied == [0.0, 0.0, 1.0, 2.0, 3.0]


@settings(max_examples=10, deadline=None)
@given(delay=st.integers(1, 6), seed=st.integers(0, 1000))
def test_delayed_converges_with_prop1_scaling(delay, seed):
    """Paper conclusion 2: with the step length deflated per Prop. 1,
    delayed SGD converges for any bounded staleness."""
    lr = 0.4 * O.staleness_step_scale(delay, rho=0.5)
    opt = O.delayed_gradient(O.sgd(lr), delay=delay)
    w = _run(opt, steps=800)
    assert np.allclose(w, TARGET, atol=0.1), f"delay={delay}: {w}"


def test_staleness_scale_monotone():
    scales = [O.staleness_step_scale(t, 0.3) for t in range(6)]
    assert all(a > b for a, b in zip(scales, scales[1:]))
    assert scales[0] == 1.0


def test_delayed_adam_pytree():
    """Delayed wrapper must handle arbitrary pytrees (dict of arrays)."""
    params = {"a": jnp.zeros(3), "b": {"c": jnp.ones(2)}}
    tgt = {"a": TARGET, "b": {"c": jnp.asarray([2.0, -2.0])}}
    # paper conclusion 2: stale gradients need a smaller step (adam with
    # lr 0.05 limit-cycles at ~0.14 error under delay=2; 0.01 converges)
    opt = O.delayed_gradient(O.adam(0.01), delay=2)
    st_ = opt.init(params)
    w = params
    for _ in range(1500):
        g = jax.tree.map(lambda x, t: x - t, w, tgt)
        u, st_ = opt.update(g, st_, w)
        w = O.apply_updates(w, u)
    flat_err = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(tgt))
    )
    assert flat_err < 0.1
