"""Bernoulli importance sampling — the paper's random variable Q.

Each of the m_i copies of distinct sample i carries an independent Bernoulli
Q_ij with P(Q_ij = 1) = R_ij; the sampled objective weights sample i by
m'_i = sum_j Q_ij / R_ij, an unbiased estimator of m_i (E[m'_i] = m_i, the
keystone of Corollary 1). With uniform rates this is Binomial(m_i, R) / R.

The draw is ``jax.random.binomial``'s. Its loops run until the slowest row
is done, so a ``DrawPlan``, made once from the counts, lists the rows that
need more than one step, and the planned draw loops over those alone and
returns the same bits.

Also here: the observable the scalability theory reads — the sparsity of the
Q' vector (Q'_i = any copy drawn), and closed forms for Delta and rho.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend.random import threefry2x32_p

from repro import obs


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DrawPlan:
    """What a multiplicity profile fixes about ``jax.random.binomial``'s work.

    jax's sampler (``jax._src.random._binomial``) runs two ``while`` loops
    over every row, each until its slowest row is done: inversion
    (``_binomial_inversion``: one uniform a step until the geometric sum
    passes the count) and BTRS (``_btrs``: two uniforms a step until every
    row, those that use inversion included, has accepted). Which rows need
    more than one inversion step and which take BTRS depends on the counts
    and the rate alone, so a plan lists them once, from concrete data, and
    the draw loops only over those rows (``_planned_binomial``).

    ``multiplicity``: the (N,) counts the plan was made from; ``inversion_rows``
    the rows whose inversion needs a second step (count 2 or more);
    ``btrs_rows`` the rows that take BTRS; ``rate`` the rate it was made for.
    Make one with ``draw_plan``.
    """

    multiplicity: jax.Array
    inversion_rows: jax.Array
    btrs_rows: jax.Array
    rate: float = dataclasses.field(metadata=dict(static=True))


def _branches(count: jax.Array, prob: jax.Array):
    """``_binomial``'s preamble: (q, use_inversion, count_nan_or_neg,
    count_inf, q_is_nan, q_l_0, p_lt_half), with the count not yet floored."""
    p_lt_half = prob < 0.5
    q = lax.select(p_lt_half, prob, 1.0 - prob)
    count_nan_or_neg = jnp.isnan(count) | (count < 0.0)
    count_inf = jnp.isinf(count)
    q_is_nan = jnp.isnan(q)
    q_l_0 = q < 0.0
    q = lax.select(q_is_nan | q_l_0, lax.full_like(q, 0.01), q)
    use_inversion = count_nan_or_neg | (count * q <= 10.0)
    return q, use_inversion, count_nan_or_neg, count_inf, q_is_nan, q_l_0, p_lt_half


def _log1minusprob(count: jax.Array, rate: float) -> jax.Array:
    """Inversion's ``log1p(-q)`` at a constant rate, shaped like ``count``.
    Built from the rate's broadcast as jax builds it, so that the compiler
    folds it and turns the division by it into the same multiplication by
    its reciprocal as in ``jax.random.binomial`` at that rate."""
    return jnp.log1p(-_branches(count, jnp.broadcast_to(jnp.float32(rate), count.shape))[0])


@jax.jit
def _plan_masks(multiplicity, prob):
    _, use_inversion = _branches(multiplicity, prob)[:2]
    return use_inversion & (jnp.floor(multiplicity) >= 2.0), ~use_inversion


def draw_plan(multiplicity, rate) -> DrawPlan | jax.Array:
    """What ``bernoulli_weights`` should draw from: the draw plan of a
    concrete (N,) multiplicity at a scalar rate, made on the device with the
    sampler's own arithmetic, or the multiplicity itself where no plan
    applies (traced, not 1-D, empty, a rate per row, or keys that are not
    partitionable threefry)."""
    if (isinstance(multiplicity, jax.core.Tracer) or np.ndim(multiplicity) != 1
            or np.shape(multiplicity)[0] == 0 or np.ndim(rate) != 0
            or not _partitionable_threefry(None)):
        return multiplicity
    multiplicity = jnp.asarray(multiplicity, jnp.float32)
    rate = float(rate)
    prob = jnp.broadcast_to(jnp.float32(rate), multiplicity.shape)
    inversion, btrs = (np.asarray(m) for m in _plan_masks(multiplicity, prob))
    return DrawPlan(
        multiplicity=multiplicity,
        inversion_rows=jnp.asarray(np.flatnonzero(inversion).astype(np.int32)),
        btrs_rows=jnp.asarray(np.flatnonzero(btrs).astype(np.int32)),
        rate=rate,
    )


def _partitionable_threefry(rng) -> bool:
    """Whether a key's uniforms are threefry2x32 at counter (0, index), so
    bits can be drawn for chosen rows alone. ``None``: the default key."""
    if not jax.config.jax_threefry_partitionable:
        return False
    if rng is not None and jax.dtypes.issubdtype(rng.dtype, jax.dtypes.prng_key):
        return str(jax.random.key_impl(rng)) == "threefry2x32"
    return jax.config.jax_default_prng_impl == "threefry2x32"


def draws_from_plan(rng, rate, multiplicity) -> bool:
    """Whether ``bernoulli_weights`` takes the planned path for these
    arguments (known before the call, from their types)."""
    if not isinstance(multiplicity, DrawPlan):
        return False
    if not isinstance(rate, (int, float, np.floating)):
        return False
    if np.float32(rate) != np.float32(multiplicity.rate):
        raise ValueError(
            f"draw plan made for rate {multiplicity.rate}, drawn at {rate}"
        )
    return _partitionable_threefry(rng)


def count_draw(rng, rate, multiplicity, n: int = 1) -> None:
    """Count ``n`` draws under ``sample.plan`` or ``sample.full``."""
    plan = draws_from_plan(rng, rate, multiplicity)
    obs.count("sample.plan" if plan else "sample.full", n)


def bernoulli_weights(
    rng: jax.Array,
    rate: jax.Array | float,
    multiplicity: jax.Array | DrawPlan,
) -> tuple[jax.Array, jax.Array]:
    """Draw one sampling round.

    Returns (m_prime, q_any): the importance weights m'_i (N,) f32 and the
    Q'_i indicator (N,) bool. E[m_prime] = multiplicity. ``multiplicity``
    is the (N,) counts, or a ``DrawPlan`` made from them: the draw is then
    the same bits as ``jax.random.binomial``'s, with its loops over the
    rows that need them.
    """
    plan = multiplicity if draws_from_plan(rng, rate, multiplicity) else None
    if isinstance(multiplicity, DrawPlan):
        multiplicity = multiplicity.multiplicity
    rate = jnp.broadcast_to(jnp.asarray(rate, jnp.float32), multiplicity.shape)
    if plan is None:
        counts = jax.random.binomial(rng, multiplicity, rate)
    else:
        counts = _planned_binomial(rng, plan, rate)
    m_prime = counts / rate
    return m_prime.astype(jnp.float32), counts > 0


# ------------------------------------------------------ the planned draw
# Every expression below is jax 0.9's (``jax._src.random``), in its order,
# so that each row sees the same float operations on the same values;
# ``tests/test_sampling.py`` pins the result to ``jax.random.binomial``.

def _uniform_at(key, rows):
    """``jax.random.uniform(key, (N,))[rows]`` without the other rows: a
    partitionable threefry uniform's bits at index i are the xor of the
    two words of threefry2x32(key, (0, i))."""
    k = jax.random.key_data(key)
    hi = jnp.zeros(rows.shape, jnp.uint32)
    bits1, bits2 = threefry2x32_p.bind(k[0], k[1], hi, rows.astype(jnp.uint32))
    bits = bits1 ^ bits2
    one = np.array(1.0, np.float32).view(np.uint32)
    float_bits = lax.bitwise_or(lax.shift_right_logical(bits, np.uint32(32 - 23)), one)
    floats = lax.bitcast_convert_type(float_bits, jnp.float32) - np.float32(1.0)
    return lax.max(np.float32(0.0), floats)


def _stirling_approx_tail(k):
    stirling_tail_vals = jnp.array(
        [
            0.0810614667953272,
            0.0413406959554092,
            0.0276779256849983,
            0.02079067210376509,
            0.0166446911898211,
            0.0138761288230707,
            0.0118967099458917,
            0.0104112652619720,
            0.00925546218271273,
            0.00833056343336287,
        ],
        dtype=k.dtype,
    )
    use_tail_values = k <= 9
    k = lax.clamp(np.array(0.0, k.dtype), k, np.array(9.0, k.dtype))
    kp1sq = (k + 1) * (k + 1)
    approx = (1.0 / 12 - (1.0 / 360 - 1.0 / 1260 / kp1sq) / kp1sq) / (k + 1)
    k = jnp.floor(k)
    return lax.select(
        use_tail_values, stirling_tail_vals[jnp.asarray(k, dtype="int32")], approx)


def _btrs_params(count, prob):
    stddev = jnp.sqrt(count * prob * (1 - prob))
    b = 1.15 + 2.53 * stddev
    a = -0.0873 + 0.0248 * b + 0.01 * prob
    c = count * prob + 0.5
    v_r = 0.92 - 4.2 / b
    r = prob / (1 - prob)
    alpha = (2.83 + 5.1 / b) * stddev
    m = jnp.floor((count + 1) * prob)
    return count, a, b, c, v_r, r, alpha, m


def _btrs_try(params, u, v):
    """One BTRS proposal from uniforms (u, v): (k, accept)."""
    count, a, b, c, v_r, r, alpha, m = params
    u = u - 0.5
    us = 0.5 - jnp.abs(u)
    accept1 = (us >= 0.07) & (v <= v_r)
    k = jnp.floor((2 * a / us + b) * u + c)
    reject = (k < 0) | (k > count)
    v = jnp.log(v * alpha / (a / (us * us) + b))
    ub = (
        (m + 0.5) * jnp.log((m + 1) / (r * (count - m + 1)))
        + (count + 1) * jnp.log((count - m + 1) / (count - k + 1))
        + (k + 0.5) * jnp.log(r * (count - k + 1) / (k + 1))
        + _stirling_approx_tail(m)
        + _stirling_approx_tail(count - m)
        - _stirling_approx_tail(k)
        - _stirling_approx_tail(count - k)
    )
    accept2 = v <= ub
    return k, accept1 | (~reject & accept2)


def _inversion(key, count_inv, rows, rate):
    """``_binomial_inversion``: step 1 over every row, which settles each
    row with a count of 0 or 1; the loop over ``rows``, the others."""
    log1minusprob = _log1minusprob(count_inv, rate)
    with jax.named_scope("sample.pass1"):
        subkey, _ = jax.random.split(key)
        u = jax.random.uniform(subkey, count_inv.shape, jnp.float32)
        geom = jnp.ceil(jnp.log(u) / log1minusprob)
        k = (geom <= count_inv).astype(jnp.float32)
    if rows.shape[0] == 0:
        return k
    with jax.named_scope("sample.inversion"):
        count = count_inv[rows]
        log1minusprob = _log1minusprob(count, rate)

        def body_fn(carry):
            i, num_geom, geom_sum, key = carry
            subkey, key = jax.random.split(key)
            num_geom_out = lax.select(geom_sum <= count, num_geom + 1, num_geom)
            u = _uniform_at(subkey, rows)
            geom = jnp.ceil(jnp.log(u) / log1minusprob)
            geom_sum = geom_sum + geom
            return i + 1, num_geom_out, geom_sum, key

        def cond_fn(carry):
            return (carry[2] <= count).any()

        zeros = jnp.zeros(rows.shape, jnp.float32)
        num_geom = lax.while_loop(cond_fn, body_fn, (0, zeros, zeros, key))[1]
        return k.at[rows].set(num_geom - 1, indices_are_sorted=True, unique_indices=True)


# The stand-ins' loop runs in tiers. Tier 0 is every row; each later tier
# holds at most that many blocks of ``_BLOCK`` rows, taken as soon as no
# more of the blocks hold a row still drawing.
_BLOCK = 128
_TIERS = (4096, 256)


def _first_open(open_blocks: jax.Array, cap: int) -> jax.Array:
    """The positions of the first ``cap`` open blocks, ``len(open_blocks)``
    past the last: a prefix sum and a binary search, no scatter."""
    csum = jnp.cumsum(open_blocks.astype(jnp.int32))
    return jnp.searchsorted(csum, jnp.arange(1, cap + 1, dtype=jnp.int32))


def _btrs_rounds(key, count_btrs, q_btrs, use_inversion):
    """How many steps ``_btrs``'s loop takes over the rows that use
    inversion (count 1e4, p 0.5 there): the first step by which each has
    accepted. The rows that take BTRS start accepted."""
    n = count_btrs.shape[0]
    n_blocks = -(-n // _BLOCK)
    pad = n_blocks * _BLOCK - n
    caps = [cap for cap in _TIERS if cap < n_blocks]
    params = _btrs_params(count_btrs, q_btrs)

    def blocks(x, fill):
        return jnp.pad(x, (0, pad), constant_values=fill).reshape(n_blocks, _BLOCK)

    def body_fn(carry):
        i, accepted, key = carry
        key, subkey_0, subkey_1 = jax.random.split(key, 3)
        u = jax.random.uniform(subkey_0, count_btrs.shape, jnp.float32)
        v = jax.random.uniform(subkey_1, count_btrs.shape, jnp.float32)
        _, accept = _btrs_try(params, u, v)
        return i + 1, accepted | accept, key

    def cond_fn(carry):
        open_blocks = ~blocks(carry[1], True).all(axis=1)
        return open_blocks.any() & (not caps or open_blocks.sum() > caps[0])

    i, accepted, key = lax.while_loop(cond_fn, body_fn, (0, ~use_inversion, key))
    rows = jnp.arange(n_blocks * _BLOCK, dtype=jnp.int32).reshape(n_blocks, _BLOCK)
    accepted = blocks(accepted, True)
    count, prob = blocks(count_btrs, 1e4), blocks(q_btrs, 0.5)
    for t, cap in enumerate(caps):
        take = _first_open(~accepted.all(axis=1), cap)
        rows, accepted, count, prob = (
            x.at[take].get(mode="fill", fill_value=fill)
            for x, fill in ((rows, 0), (accepted, True), (count, 1e4), (prob, 0.5)))
        params = _btrs_params(count.reshape(-1), prob.reshape(-1))
        flat_rows = rows.reshape(-1)
        next_cap = caps[t + 1] if t + 1 < len(caps) else None

        def tier_body(carry, params=params, flat_rows=flat_rows):
            i, accepted, key = carry
            key, subkey_0, subkey_1 = jax.random.split(key, 3)
            _, accept = _btrs_try(params, _uniform_at(subkey_0, flat_rows),
                                  _uniform_at(subkey_1, flat_rows))
            return i + 1, accepted | accept.reshape(accepted.shape), key

        def tier_cond(carry, next_cap=next_cap):
            open_blocks = ~carry[1].all(axis=1)
            return open_blocks.any() & (next_cap is None or open_blocks.sum() > next_cap)

        i, accepted, key = lax.while_loop(tier_cond, tier_body, (i, accepted, key))
    return i


def _btrs(key, count_btrs, q_btrs, use_inversion, rows):
    """``_btrs`` at ``rows``. Each row keeps the proposal of the last step
    at which it accepted, and jax's loop runs until every row has accepted,
    the inversion rows' stand-ins too: so the loop here runs until its own
    rows have accepted and at least as many steps as those stand-ins take."""
    with jax.named_scope("sample.btrs_count"):
        rounds = _btrs_rounds(key, count_btrs, q_btrs, use_inversion)
    with jax.named_scope("sample.btrs_rows"):
        params = _btrs_params(count_btrs[rows], q_btrs[rows])

        def body_fn(carry):
            i, k_out, accepted, key = carry
            key, subkey_0, subkey_1 = jax.random.split(key, 3)
            k, accept = _btrs_try(params, _uniform_at(subkey_0, rows),
                                  _uniform_at(subkey_1, rows))
            k_out = lax.select(accept, k, k_out)
            return i + 1, k_out, accepted | accept, key

        def cond_fn(carry):
            i, accepted = carry[0], carry[2]
            return (~accepted).any() | (i < rounds)

        k_init = jnp.full(rows.shape, -1.0, jnp.float32)
        accepted = jnp.zeros(rows.shape, bool)
        return lax.while_loop(cond_fn, body_fn, (0, k_init, accepted, key))[1]


def _planned_binomial(rng, plan: DrawPlan, prob: jax.Array) -> jax.Array:
    """``jax.random.binomial(rng, plan.multiplicity, prob)``, bit for bit,
    with each loop over the rows the plan lists (``prob``: the plan's rate,
    broadcast)."""
    if not jax.dtypes.issubdtype(rng.dtype, jax.dtypes.prng_key):
        rng = jax.random.wrap_key_data(rng)
    count = plan.multiplicity
    (q, use_inversion, count_nan_or_neg, count_inf, q_is_nan, q_l_0,
     p_lt_half) = _branches(count, prob)
    count = jnp.floor(count)
    count_inv = lax.select(use_inversion, count, lax.full_like(count, 0.0))
    samples = _inversion(rng, count_inv, plan.inversion_rows, plan.rate)
    if plan.btrs_rows.shape[0]:
        count_btrs = lax.select(use_inversion, lax.full_like(count, 1e4), count)
        q_btrs = lax.select(use_inversion, lax.full_like(q, 0.5), q)
        rows = plan.btrs_rows
        k = _btrs(rng, count_btrs, q_btrs, use_inversion, rows)
        samples = samples.at[rows].set(k, indices_are_sorted=True, unique_indices=True)
    invalid = q_l_0 | q_is_nan | count_nan_or_neg
    samples = lax.select(invalid, jnp.full_like(samples, np.nan), samples)
    samples = lax.select(
        count_inf & (~invalid), jnp.full_like(samples, np.inf), samples)
    return lax.select(
        p_lt_half | count_nan_or_neg | q_is_nan | count_inf,
        samples,
        count - samples,
    )


def q_sparsity(q_any: jax.Array) -> jax.Array:
    """Fraction of distinct samples present in the subdataset (density of Q')."""
    return jnp.mean(q_any.astype(jnp.float32))


def delta_max(rate, multiplicity: jax.Array) -> jax.Array:
    """Delta = max_i P(Q'_i = 1) = max_i 1 - (1 - R)^{m_i} (closed form)."""
    rate = jnp.asarray(rate, jnp.float32)
    return jnp.max(1.0 - (1.0 - rate) ** multiplicity)


def overlap_probability(rate, multiplicity: jax.Array) -> jax.Array:
    """rho = P(two independent subdatasets intersect).

    P(i in both) = p_i^2 with p_i = 1 - (1-R)^{m_i};
    rho = 1 - prod_i (1 - p_i^2). High diversity (m_i = 1, small R) => small
    per-sample p_i but the product over many i can still be large — exactly
    the tension the paper's requirements describe.
    """
    rate = jnp.asarray(rate, jnp.float32)
    p = 1.0 - (1.0 - rate) ** multiplicity
    return 1.0 - jnp.exp(jnp.sum(jnp.log1p(-jnp.minimum(p * p, 1.0 - 1e-7))))


def diversity_stats(rate, multiplicity: jax.Array) -> dict[str, jax.Array]:
    """The asynch-SGBDT-requirement observables for a (dataset, rate) pair."""
    return {
        "delta": delta_max(rate, multiplicity),
        "rho": overlap_probability(rate, multiplicity),
        "expected_subdataset_density": jnp.mean(
            1.0 - (1.0 - jnp.asarray(rate, jnp.float32)) ** multiplicity
        ),
    }
