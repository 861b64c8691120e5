"""Per-kernel correctness: Pallas (interpret mode) vs the pure-jnp oracle,
swept over shapes/dtypes, plus hypothesis property tests on the semantics.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.histogram import histogram_pallas


def _rand_case(key, n, f, n_bins, n_nodes, grad_dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    bins = jax.random.randint(k1, (n, f), 0, n_bins, dtype=jnp.int32)
    node = jax.random.randint(k2, (n,), -1, n_nodes, dtype=jnp.int32)
    grad = jax.random.normal(k3, (n,), grad_dtype)
    hess = jax.random.uniform(k4, (n,), grad_dtype)
    return bins, node, grad, hess


# ---------------------------------------------------------------- histogram
SHAPE_SWEEP = [
    # (N, F, n_bins, n_nodes)
    (64, 4, 8, 1),
    (300, 10, 16, 4),
    (512, 8, 32, 8),
    (1000, 17, 64, 16),  # non-multiple N and F -> exercises padding
    (2048, 32, 64, 32),
]


@pytest.mark.parametrize("n,f,n_bins,n_nodes", SHAPE_SWEEP)
def test_histogram_pallas_matches_ref(key, n, f, n_bins, n_nodes):
    bins, node, grad, hess = _rand_case(key, n, f, n_bins, n_nodes)
    out_ref = ref.histogram_ref(bins, node, grad, hess, n_nodes, n_bins)
    out_pal = ops.build_histogram(
        bins, node, grad, hess, n_nodes, n_bins, backend="pallas"
    )
    np.testing.assert_allclose(out_ref, out_pal, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("sample_block,feature_block", [(128, 4), (256, 8), (512, 16)])
def test_histogram_pallas_block_shapes(key, sample_block, feature_block):
    """Kernel result must be invariant to BlockSpec tiling choices."""
    bins, node, grad, hess = _rand_case(key, 1024, 16, 16, 4)
    base = ref.histogram_ref(bins, node, grad, hess, 4, 16)
    out = histogram_pallas(
        bins, node, grad, hess, 4, 16,
        sample_block=sample_block, feature_block=feature_block, interpret=True,
    )
    np.testing.assert_allclose(base, out, rtol=1e-5, atol=1e-4)


def test_histogram_inactive_samples_ignored(key):
    bins, node, grad, hess = _rand_case(key, 256, 6, 8, 4)
    node_off = jnp.where(jnp.arange(256) % 2 == 0, node, -1)
    out = ref.histogram_ref(bins, node_off, grad, hess, 4, 8)
    # recompute with only active samples
    act = np.asarray(node_off) >= 0
    out2 = ref.histogram_ref(
        jnp.asarray(np.asarray(bins)[act]),
        jnp.asarray(np.asarray(node_off)[act]),
        jnp.asarray(np.asarray(grad)[act]),
        jnp.asarray(np.asarray(hess)[act]),
        4, 8,
    )
    np.testing.assert_allclose(out, out2, rtol=1e-5, atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(8, 200),
    f=st.integers(1, 12),
    n_bins=st.sampled_from([4, 8, 16]),
    n_nodes=st.sampled_from([1, 2, 4]),
    seed=st.integers(0, 2**31 - 1),
)
def test_histogram_mass_conservation(n, f, n_bins, n_nodes, seed):
    """Property: summing a histogram over (node, bin) recovers the total
    grad/hess mass of active samples, for every feature."""
    key = jax.random.PRNGKey(seed)
    bins, node, grad, hess = _rand_case(key, n, f, n_bins, n_nodes)
    out = ref.histogram_ref(bins, node, grad, hess, n_nodes, n_bins)
    active = np.asarray(node) >= 0
    tg = float(np.sum(np.asarray(grad)[active]))
    th = float(np.sum(np.asarray(hess)[active]))
    per_feature_g = np.asarray(out[0].sum(axis=(0, 2)))
    per_feature_h = np.asarray(out[1].sum(axis=(0, 2)))
    np.testing.assert_allclose(per_feature_g, tg, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(per_feature_h, th, rtol=1e-3, atol=1e-3)


def test_split_bf16_is_exact(key):
    """hi + mid + lo rebuilds every f32 value bit for bit: random grads over
    a wide range of magnitudes, hessians, 0, negatives, 1e-30 and the
    smallest normal. The one limit is |x| near f32's largest value, where
    ``hi`` rounds to inf."""
    from repro.kernels.histogram import split_bf16

    k1, k2, k3 = jax.random.split(key, 3)
    n = 1 << 16
    scale = jnp.exp2(jax.random.randint(k2, (n,), -60, 60).astype(jnp.float32))
    tiny = np.finfo(np.float32).tiny
    x = jnp.concatenate([
        jax.random.normal(k1, (n,)) * scale,
        jax.random.uniform(k3, (n,)) * 0.25,
        jnp.asarray([0.0, 1e-30, -1e-30, tiny, -tiny, 1.0, -1.0, 3e38, -3e38],
                    jnp.float32),
    ])
    parts = split_bf16(x)
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    hi, mid, lo = (np.asarray(p, np.float32) for p in parts)
    xs = np.asarray(x)
    np.testing.assert_array_equal((hi + mid) + lo, xs)
    assert np.any(hi != xs)  # the parts below hi carry bits
    assert np.isinf(np.asarray(split_bf16(jnp.float32(3.4e38))[0], np.float32))


def _histogram_f64(bins, node, grad, hess, active, n_bins):
    """(2, R, F, B) float64 sums and absolute masses of the rows of
    ``active`` nodes."""
    bins, node = np.asarray(bins), np.asarray(node)
    r_of = {int(a): r for r, a in enumerate(np.asarray(active))}
    val = np.zeros((2, len(r_of), bins.shape[1], n_bins))
    mass = np.zeros_like(val)
    for s in np.flatnonzero(np.isin(node, list(r_of))):
        r = r_of[int(node[s])]
        for i, w in enumerate((float(grad[s]), float(hess[s]))):
            val[i, r, np.arange(bins.shape[1]), bins[s]] += w
            mass[i, r, np.arange(bins.shape[1]), bins[s]] += abs(w)
    return val, mass


@pytest.mark.parametrize("parts", ["hi_mid_lo", "hi_only"])
@pytest.mark.parametrize("subset", [False, True], ids=["full_level", "node_subset"])
def test_histogram_error_vs_float64(key, monkeypatch, subset, parts):
    """Against float64 sums the kernel's error stays under 1e-6 of each
    cell's absolute mass, full level and node subset alike. A planted
    variant that keeps only the hi part of grad/hess (their bf16 rounding)
    fails the same bound: the test sees bf16 rounding."""
    import repro.kernels.histogram as H

    n, f, n_bins, n_nodes = 2048, 6, 16, 4
    bins, node, grad, hess = _rand_case(key, n, f, n_bins, n_nodes)
    grad = grad * jnp.exp2(jax.random.randint(key, (n,), -8, 8).astype(jnp.float32))
    active = jnp.asarray([3, 0], jnp.int32) if subset else jnp.arange(n_nodes)
    if parts == "hi_only":
        def hi_only(x):
            hi = x.astype(jnp.bfloat16)
            return hi, jnp.zeros_like(hi), jnp.zeros_like(hi)

        monkeypatch.setattr(H, "split_bf16", hi_only)
    jax.clear_caches()  # trace the kernel anew, with or without the plant
    try:
        out = histogram_pallas(
            bins, node, grad, hess, n_nodes, n_bins, sample_block=512,
            interpret=True, active_nodes=active if subset else None,
        )
    finally:
        jax.clear_caches()
    val, mass = _histogram_f64(bins, node, grad, hess, active, n_bins)
    err = np.abs(np.asarray(out, np.float64) - val)
    rel = np.max(err[mass > 0] / mass[mass > 0])
    if parts == "hi_mid_lo":
        assert rel <= 1e-6, rel
    else:
        assert rel > 1e-6, rel


# --------------------------------------------------------------- split gain
@pytest.mark.parametrize("l,f,b", [(1, 4, 8), (4, 8, 16), (8, 16, 64), (16, 7, 32)])
def test_split_gain_pallas_matches_ref(key, l, f, b):
    hist = jax.random.uniform(key, (2, l, f, b), jnp.float32)
    g_ref = ops.split_gain(hist, 1.0, 1e-3, backend="ref")
    g_pal = ops.split_gain(hist, 1.0, 1e-3, backend="pallas")
    ref_m = np.where(np.isfinite(g_ref), np.asarray(g_ref), -1e30)
    pal_m = np.where(np.isfinite(g_pal), np.asarray(g_pal), -1e30)
    np.testing.assert_allclose(ref_m, pal_m, rtol=1e-4, atol=1e-4)


def _signed_hist(key, l, f, b):
    """(2, L, F, B) grad/hess histograms: signed grad, nonnegative hess,
    with some empty bins so invalid split points appear."""
    kg, kh, kz = jax.random.split(key, 3)
    g = jax.random.normal(kg, (l, f, b), jnp.float32)
    h = jax.random.uniform(kh, (l, f, b), jnp.float32)
    empty = jax.random.uniform(kz, (l, f, b)) < 0.3
    return jnp.stack([jnp.where(empty, 0.0, g), jnp.where(empty, 0.0, h)])


@pytest.mark.parametrize(
    "l,node_block",
    [(1, 1), (8, 8), (64, 8), (64, 16), (64, 32), (64, 64)],
)
def test_split_gain_pallas_node_tiles_bitwise(key, l, node_block):
    """Tiling the node axis changes no value: every node block's gains are
    the oracle's, bit for bit (-inf at the same split points)."""
    from repro.kernels.split_scan import split_gain_pallas

    f, b = 6, 64  # 6 features: three 2-feature blocks of 128 lanes
    hist = _signed_hist(key, l, f, b)
    want = ref.split_gain_surface_ref(hist, jnp.float32(1.0), jnp.float32(1e-3))
    got = split_gain_pallas(hist, 1.0, 1e-3, node_block=node_block, feature_block=2,
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("l", [1, 3, 8, 24, 64, 200])
def test_split_gain_dispatch_pads_nodes_bitwise(key, monkeypatch, l):
    """``ops.split_gain`` pads the level to whole node blocks and drops
    the pad: with the budget cut so that a level needs several node
    blocks, the surface is still the oracle's, bit for bit."""
    from repro.kernels import autotune

    f, b = 5, 16
    monkeypatch.setattr(autotune, "SPLIT_VMEM_BUDGET",
                        autotune.split_vmem_bytes(16, 8, b))
    hist = _signed_hist(key, l, f, b)
    want = ops.split_gain(hist, 1.0, 1e-3, backend="ref")
    got = ops.split_gain(hist, 1.0, 1e-3, backend="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("f", [28, 300, 20958, 150360])
@pytest.mark.parametrize("b", [16, 64, 128])
def test_split_tiling_stays_in_budget(f, b):
    """For every level width from 1 to 256 the chosen split blocks are ones
    Mosaic lowers (a node block of a multiple of 8 or the whole level,
    whole 128-lane groups of bins) and price within the VMEM budget."""
    from repro.kernels import autotune
    from repro.kernels.split_scan import scan_width

    for l in range(1, 257):
        l_pad, nb, f_pad, fb = autotune.split_tiling(l, f, b)
        assert l_pad >= l and l_pad % nb == 0 and l_pad - l < max(nb, 8)
        assert nb == l or nb % 8 == 0
        assert f_pad >= f and f_pad % fb == 0 and (fb * b) % scan_width(b) == 0
        assert f_pad - f < fb or fb == f_pad
        assert autotune.split_vmem_bytes(nb, fb, b) <= autotune.SPLIT_VMEM_BUDGET


@pytest.mark.parametrize("f", [300, 20958])
def test_split_tiling_refuses_a_tile_over_budget(f):
    """256 bins over 128-feature blocks price an 8-node tile over the
    budget: the chooser raises rather than hand Mosaic a block it would
    refuse. The same bins over 28 features still fit."""
    from repro.kernels import autotune

    with pytest.raises(ValueError, match="over the"):
        autotune.split_tiling(8, f, 256)
    assert autotune.split_tiling(8, 28, 256) == (8, 8, 28, 28)


@pytest.mark.parametrize("l", [1, 2, 4, 8, 16])
def test_split_tiling_keeps_one_block_at_higgs(l):
    """HIGGS's levels (L <= 16 nodes, 28 features, 64 bins) stay one block
    in both axes: the whole level in one tile, as before node tiling."""
    from repro.kernels import autotune

    assert autotune.split_tiling(l, 28, 64) == (l, l, 28, 28)


def test_split_tiling_realsim_depth7():
    """real-sim's deepest split level (64 nodes x 20,958 features) splits
    into node blocks of 16 over the 128-feature blocks."""
    from repro.kernels import autotune

    assert autotune.split_tiling(64, 20958, 64) == (64, 16, 20992, 128)


@pytest.mark.parametrize("b", [8, 64, 256])
def test_bin_prefix_sum_matches_cumsum(key, b):
    """The oracle's log-step scan fixes the kernels' summation order; it
    must still be a prefix sum. Each output is at most ceil(log2 B)
    roundings from the exact sum (float64 cumsum); jnp.cumsum's own order
    adds up to B - 1 more, relative to the running sum of |x|."""
    kg, kh = jax.random.split(key)
    x = jnp.stack([
        jax.random.normal(kg, (3, 7, b), jnp.float32),
        jax.random.uniform(kh, (3, 7, b), jnp.float32),
    ])
    got = np.asarray(ref.bin_prefix_sum(x), np.float64)
    xs = np.asarray(x, np.float64)
    scale = np.cumsum(np.abs(xs), axis=-1)
    eps = float(np.finfo(np.float32).eps)
    depth = int(np.ceil(np.log2(b)))
    assert np.all(np.abs(got - np.cumsum(xs, axis=-1)) <= depth * eps * scale)
    want = np.asarray(jnp.cumsum(x, axis=-1), np.float64)
    assert np.all(np.abs(got - want) <= (b + depth) * eps * scale)


def test_split_gain_last_bin_invalid(key):
    hist = jax.random.uniform(key, (2, 2, 3, 8), jnp.float32)
    gain = ops.split_gain(hist, 1.0, 0.0, backend="ref")
    assert bool(np.all(~np.isfinite(np.asarray(gain)[..., -1])))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), b=st.sampled_from([8, 16, 32]))
def test_split_gain_nonnegative_at_optimum(seed, b):
    """Property: gain of the argmax split is >= 0 whenever any split is
    valid (splitting cannot hurt the regularized objective)."""
    key = jax.random.PRNGKey(seed)
    g = jax.random.normal(key, (2, 1, 4, b), jnp.float32)
    hist = g.at[1].set(jnp.abs(g[1]) + 0.1)
    best, feat, thr = ref.split_scan_ref(
        hist, jnp.float32(1.0), jnp.float32(1e-6)
    )
    valid = np.isfinite(float(best[0]))
    if valid:
        assert float(best[0]) >= -1e-4


def test_best_split_agrees_with_bruteforce(key):
    hist = jax.random.uniform(key, (2, 3, 5, 16), jnp.float32)
    lam, minh = 0.5, 1e-3
    best, feat, thr = ref.split_scan_ref(hist, jnp.float32(lam), jnp.float32(minh))
    g, h = np.asarray(hist[0]), np.asarray(hist[1])
    for node in range(3):
        best_gain = -np.inf
        for fi in range(5):
            gl = hl = 0.0
            gt, ht = g[node, fi].sum(), h[node, fi].sum()
            for bi in range(15):  # last bin invalid
                gl += g[node, fi, bi]
                hl += h[node, fi, bi]
                gr, hr = gt - gl, ht - hl
                if hl < minh or hr < minh:
                    continue
                gain = gl**2 / (hl + lam) + gr**2 / (hr + lam) - gt**2 / (ht + lam)
                best_gain = max(best_gain, gain)
        np.testing.assert_allclose(float(best[node]), best_gain, rtol=1e-4)


# ----------------------------------------------------------- flash attention
FLASH_SWEEP = [
    # (b, sq, sk, h, kv, hd, causal)
    (2, 128, 128, 4, 4, 64, True),
    (2, 128, 128, 4, 4, 64, False),
    (1, 256, 256, 8, 2, 64, True),  # GQA group 4
    (2, 100, 100, 4, 2, 32, True),  # padding path
    (1, 96, 96, 2, 2, 128, False),  # non-causal + padding (kv mask)
    (2, 64, 192, 4, 4, 64, False),  # cross-shaped (Sq != Sk)
]


@pytest.mark.parametrize("b,sq,sk,h,kv,hd,causal", FLASH_SWEEP)
def test_flash_attention_matches_ref(key, b, sq, sk, h, kv, hd, causal):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, sq, h, hd))
    kk = jax.random.normal(k2, (b, sk, kv, hd))
    v = jax.random.normal(k3, (b, sk, kv, hd))
    o_ref = ops.flash_attention(q, kk, v, causal=causal, backend="ref")
    o_pal = ops.flash_attention(
        q, kk, v, causal=causal, backend="pallas", block_q=64, block_k=64
    )
    np.testing.assert_allclose(o_ref, o_pal, rtol=1e-4, atol=1e-5)


def test_flash_attention_bf16(key):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (2, 128, 4, 64), jnp.bfloat16)
    kk = jax.random.normal(k2, (2, 128, 4, 64), jnp.bfloat16)
    v = jax.random.normal(k3, (2, 128, 4, 64), jnp.bfloat16)
    o_ref = ops.flash_attention(q, kk, v, backend="ref").astype(jnp.float32)
    o_pal = ops.flash_attention(q, kk, v, backend="pallas").astype(jnp.float32)
    np.testing.assert_allclose(o_ref, o_pal, rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_block_invariance(key, bq, bk):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (1, 256, 4, 64))
    kk = jax.random.normal(k2, (1, 256, 4, 64))
    v = jax.random.normal(k3, (1, 256, 4, 64))
    base = ops.flash_attention(q, kk, v, backend="ref")
    out = ops.flash_attention(q, kk, v, backend="pallas", block_q=bq, block_k=bk)
    np.testing.assert_allclose(base, out, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("b,sq,sk,h,kv,hd,causal", FLASH_SWEEP)
def test_flash_attention_backward_matches_ref(key, b, sq, sk, h, kv, hd, causal):
    """The fused Pallas dq/dk/dv kernels vs grads through the oracle."""
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, sq, h, hd))
    kk = jax.random.normal(k2, (b, sk, kv, hd))
    v = jax.random.normal(k3, (b, sk, kv, hd))

    def loss(backend):
        def f(q_, k_, v_):
            out = ops.flash_attention(
                q_, k_, v_, causal=causal, backend=backend,
                block_q=64, block_k=64,
            )
            return jnp.sum(jnp.sin(out))
        return f

    gp = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, kk, v)
    gr = jax.grad(loss("ref"), argnums=(0, 1, 2))(q, kk, v)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


# --------------------------------------------------------- forest traversal
def _rand_forest_case(key, n, f, n_bins, n_trees, depth):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    n_int, n_leaf = (1 << depth) - 1, 1 << depth
    bins = jax.random.randint(k1, (n, f), 0, n_bins, dtype=jnp.int32)
    feat = jax.random.randint(k2, (n_trees, n_int), 0, f, dtype=jnp.int32)
    thr = jax.random.randint(k3, (n_trees, n_int), 0, n_bins, dtype=jnp.int32)
    leaf = jax.random.normal(k4, (n_trees, n_leaf), jnp.float32)
    return bins, feat, thr, leaf


FOREST_SWEEP = [
    # (N, F, n_bins, T, depth, live)
    (64, 4, 8, 1, 2, 1),
    (200, 6, 16, 3, 3, 3),
    (300, 10, 32, 17, 4, 9),  # non-multiple N -> exercises sample padding
    (1000, 17, 64, 40, 6, 25),  # partially filled
    (512, 8, 64, 64, 5, 0),  # nothing live -> exact zeros
]


@pytest.mark.parametrize("n,f,n_bins,n_trees,depth,live", FOREST_SWEEP)
def test_forest_traverse_pallas_matches_ref(key, n, f, n_bins, n_trees, depth, live):
    """Interpret-mode kernel is bitwise-exact vs the oracle (single tree
    block — the serving default for any capacity <= 512)."""
    bins, feat, thr, leaf = _rand_forest_case(key, n, f, n_bins, n_trees, depth)
    nt = jnp.asarray(live, jnp.int32)
    out_ref = ref.forest_traverse_ref(bins, feat, thr, leaf, nt, depth)
    out_pal = ops.forest_traverse(bins, feat, thr, leaf, nt, depth, backend="pallas")
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_pal))


def test_forest_traverse_pallas_block_shapes(key):
    """Result must be invariant to tiling; cross-tree-block accumulation is
    float-rounded, so multi-block tilings match to f32 tolerance."""
    from repro.kernels.forest_traversal import forest_traverse_pallas

    bins, feat, thr, leaf = _rand_forest_case(key, 512, 8, 32, 64, 4)
    nt = jnp.asarray(50, jnp.int32)
    base = ref.forest_traverse_ref(bins, feat, thr, leaf, nt, 4)
    for sample_block, tree_block in [(128, 16), (256, 64), (512, 32)]:
        out = forest_traverse_pallas(
            bins, feat, thr, leaf, nt, 4,
            sample_block=sample_block, tree_block=tree_block, interpret=True,
        )
        np.testing.assert_allclose(base, out, rtol=1e-5, atol=1e-5)


def test_forest_traverse_masks_stale_slots(key):
    """Slots >= n_trees must contribute 0 even when they hold garbage —
    the partially-filled / hot-swap serving contract."""
    bins, feat, thr, leaf = _rand_forest_case(key, 256, 6, 16, 12, 3)
    live = 7
    nt = jnp.asarray(live, jnp.int32)
    clean = ref.forest_traverse_ref(
        bins, feat[:live], thr[:live], leaf[:live], nt, 3
    )
    for backend in ("ref", "pallas"):
        out = ops.forest_traverse(bins, feat, thr, leaf, nt, 3, backend=backend)
        np.testing.assert_allclose(clean, out, rtol=1e-6, atol=1e-6)


def test_forest_traverse_ref_matches_apply_forest(key):
    """On zero-padded (training-produced) forests the masked serving sum
    equals the unmasked train-time scan."""
    bins, feat, thr, leaf = _rand_forest_case(key, 400, 8, 16, 10, 4)
    live = 6
    feat = feat.at[live:].set(0)
    thr = thr.at[live:].set(2**30)
    leaf = leaf.at[live:].set(0.0)
    masked = ref.forest_traverse_ref(bins, feat, thr, leaf, live, 4)
    unmasked = ref.apply_forest_ref(bins, feat, thr, leaf, 4)
    np.testing.assert_allclose(masked, unmasked, rtol=1e-6, atol=1e-6)


MULTI_OUT_SWEEP = [
    # (N, F, n_bins, T, depth, live, K) — T and live are slot counts
    (128, 5, 16, 6, 3, 6, 3),
    (300, 8, 32, 20, 4, 12, 4),  # partially-filled, live % K == 0
    (64, 4, 8, 10, 2, 7, 2),  # live mid-round (odd slot count)
    (200, 6, 16, 15, 3, 0, 5),  # nothing live -> exact zeros
]


@pytest.mark.parametrize("n,f,n_bins,n_trees,depth,live,k", MULTI_OUT_SWEEP)
def test_forest_traverse_multi_output_pallas_matches_ref(
    key, n, f, n_bins, n_trees, depth, live, k
):
    """K-output traversal: slot t reduces into column t % K. The kernel's
    per-output masked sums reassociate the reduction vs the oracle's
    segment_sum, so parity is f32-tolerance (bitwise stays a K=1-only
    property of the single-tree-block kernel)."""
    bins, feat, thr, leaf = _rand_forest_case(key, n, f, n_bins, n_trees, depth)
    nt = jnp.asarray(live, jnp.int32)
    out_ref = ref.forest_traverse_ref(bins, feat, thr, leaf, nt, depth, n_outputs=k)
    assert out_ref.shape == (n, k)
    out_pal = ops.forest_traverse(
        bins, feat, thr, leaf, nt, depth, backend="pallas", n_outputs=k
    )
    np.testing.assert_allclose(
        np.asarray(out_ref), np.asarray(out_pal), rtol=1e-6, atol=1e-6
    )
    out_scan = ops.forest_traverse(
        bins, feat, thr, leaf, nt, depth, backend="ref", n_outputs=k
    )
    np.testing.assert_allclose(out_ref, out_scan, rtol=1e-6, atol=1e-6)


def test_forest_traverse_multi_output_columns_are_per_output_sums(key):
    """Column k of the K-output traversal equals a single-output traversal
    over only that output's live slots."""
    k_out, rounds, depth = 3, 4, 3
    bins, feat, thr, leaf = _rand_forest_case(key, 100, 5, 16, k_out * rounds, depth)
    live = k_out * rounds
    out = ref.forest_traverse_ref(
        bins, feat, thr, leaf, jnp.asarray(live), depth, n_outputs=k_out
    )
    for k in range(k_out):
        sel = np.arange(live) % k_out == k
        col = ref.forest_traverse_ref(
            bins, feat[sel], thr[sel], leaf[sel],
            jnp.asarray(int(sel.sum())), depth,
        )
        np.testing.assert_allclose(np.asarray(out[:, k]), np.asarray(col),
                                   rtol=1e-6, atol=1e-6)


# -------------------------------------------------------------- apply_forest
def test_apply_forest_matches_tree_sum(key):
    from repro.trees import LearnerConfig, build_tree, empty_forest, forest_push
    from repro.trees.tree import apply_tree

    bins = jax.random.randint(key, (200, 6), 0, 16, dtype=jnp.int32)
    forest = empty_forest(3, depth=3)
    total = jnp.zeros(200)
    for i in range(3):
        k = jax.random.fold_in(key, i)
        g = jax.random.normal(k, (200,))
        tree = build_tree(
            LearnerConfig(depth=3, n_bins=16, feature_fraction=1.0),
            bins, g, jnp.ones(200), k,
        )
        forest = forest_push(forest, tree, jnp.float32(0.5))
        total = total + 0.5 * apply_tree(tree, bins)
    from repro.trees import forest_predict
    np.testing.assert_allclose(
        np.asarray(forest_predict(forest, bins)),
        np.asarray(forest.base_score + total),
        rtol=1e-5, atol=1e-5,
    )


def test_kernel_interpret_default_autodetects(key):
    """Regression: raw kernel entry points default interpret=None, resolved
    from the backend (interpret off TPU, Mosaic on it) — a direct caller no
    longer silently runs the interpreter on real hardware. On this CPU the
    auto mode must equal an explicit interpret=True run."""
    import inspect

    from repro.kernels.flash_attention import (
        flash_attention_bwd_pallas,
        flash_attention_pallas,
    )
    from repro.kernels.forest_traversal import forest_traverse_pallas
    from repro.kernels.split_scan import split_gain_pallas

    for fn in (
        histogram_pallas,
        split_gain_pallas,
        forest_traverse_pallas,
        flash_attention_pallas,
        flash_attention_bwd_pallas,
    ):
        sig = inspect.signature(fn.__wrapped__)
        assert sig.parameters["interpret"].default is None, fn

    bins, node, grad, hess = _rand_case(key, 512, 8, 16, 4)
    auto = histogram_pallas(bins, node, grad, hess, 4, 16)
    explicit = histogram_pallas(bins, node, grad, hess, 4, 16, interpret=True)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(explicit))


# ----------------------------------------------------- quantized traversal
def _quantized_case(key, n, f, n_bins, n_trees, depth, live):
    from repro.trees.forest import Forest

    bins, feat, thr, leaf = _rand_forest_case(key, n, f, n_bins, n_trees, depth)
    forest = Forest(
        feature=feat, threshold=thr, leaf_value=leaf,
        n_trees=jnp.asarray(live, jnp.int32),
        base_score=jnp.asarray(0.0, jnp.float32),
    )
    return bins, forest


@pytest.mark.parametrize("mode", ["int8", "fp16"])
@pytest.mark.parametrize("n,f,n_bins,n_trees,depth,live", FOREST_SWEEP)
def test_quantized_traverse_within_documented_atol(
    key, mode, n, f, n_bins, n_trees, depth, live
):
    """Quantized traversal (both backends) stays within the per-forest
    tolerance ``quantization_atol`` documents: sum over live trees of the
    worst leaf dequantization error."""
    from repro.trees.forest import quantization_atol

    bins, forest = _quantized_case(key, n, f, n_bins, n_trees, depth, live)
    qf = forest.quantize(mode)
    atol = quantization_atol(forest, qf)
    base = np.asarray(
        ref.forest_traverse_ref(
            bins, forest.feature, forest.threshold, forest.leaf_value,
            forest.n_trees, depth,
        )
    )
    for backend in ("ref", "pallas"):
        out = np.asarray(
            ops.forest_traverse(
                bins, qf.feature, qf.threshold, qf.leaf_value, qf.n_trees,
                depth, backend=backend, leaf_scale=qf.leaf_scale,
            )
        )
        assert np.max(np.abs(out - base), initial=0.0) <= atol + 1e-6, backend
    if live == 0:
        np.testing.assert_array_equal(base, np.zeros_like(base))


@pytest.mark.parametrize("mode", ["int8", "fp16"])
def test_quantized_traverse_pallas_bitwise_vs_oracle(key, mode):
    """On the SAME quantized payload the interpret-mode kernel and the
    vectorized oracle dequantize with identical float ops — bitwise."""
    bins, forest = _quantized_case(key, 300, 10, 32, 17, 4, 9)
    qf = forest.quantize(mode)
    q_ref = ref.forest_traverse_ref(
        bins, qf.feature, qf.threshold, qf.leaf_value, qf.n_trees, 4,
        leaf_scale=qf.leaf_scale,
    )
    q_pal = ops.forest_traverse(
        bins, qf.feature, qf.threshold, qf.leaf_value, qf.n_trees, 4,
        backend="pallas", leaf_scale=qf.leaf_scale,
    )
    np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q_pal))


@pytest.mark.parametrize("n,f,n_bins,n_trees,depth,live,k", MULTI_OUT_SWEEP)
def test_quantized_multi_output_parity(key, n, f, n_bins, n_trees, depth, live, k):
    """K-output quantized traversal keeps the per-column t % K contract
    within the documented tolerance on both backends."""
    from repro.trees.forest import quantization_atol

    bins, forest = _quantized_case(key, n, f, n_bins, n_trees, depth, live)
    qf = forest.quantize("int8")
    atol = quantization_atol(forest, qf)
    base = np.asarray(
        ref.forest_traverse_ref(
            bins, forest.feature, forest.threshold, forest.leaf_value,
            forest.n_trees, depth, n_outputs=k,
        )
    )
    for backend in ("ref", "pallas"):
        out = np.asarray(
            ops.forest_traverse(
                bins, qf.feature, qf.threshold, qf.leaf_value, qf.n_trees,
                depth, backend=backend, n_outputs=k, leaf_scale=qf.leaf_scale,
            )
        )
        assert out.shape == (n, k)
        assert np.max(np.abs(out - base), initial=0.0) <= atol + 1e-6, backend


def test_f32_path_ignores_quantization_args(key):
    """The f32 layout must lower the exact historical program: passing a
    leaf_scale alongside f32 leaves changes nothing, bitwise."""
    bins, feat, thr, leaf = _rand_forest_case(key, 256, 8, 32, 16, 4)
    nt = jnp.asarray(11, jnp.int32)
    plain = ops.forest_traverse(bins, feat, thr, leaf, nt, 4, backend="pallas")
    scaled = ops.forest_traverse(
        bins, feat, thr, leaf, nt, 4, backend="pallas",
        leaf_scale=jnp.full((16,), 5.0, jnp.float32),
    )
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(scaled))
    plain_r = ops.forest_traverse(bins, feat, thr, leaf, nt, 4, backend="ref")
    scaled_r = ops.forest_traverse(
        bins, feat, thr, leaf, nt, 4, backend="ref",
        leaf_scale=jnp.full((16,), 5.0, jnp.float32),
    )
    np.testing.assert_array_equal(np.asarray(plain_r), np.asarray(scaled_r))


def test_quantize_roundtrip_and_mode(key):
    """dequantize() inverts the packing to within the per-tree bound, dead
    slots come back masked-safe, and the mode rides the dtype."""
    _, forest = _quantized_case(key, 8, 6, 64, 10, 3, 7)
    for mode in ("int8", "fp16"):
        qf = forest.quantize(mode)
        assert qf.mode == mode
        deq = qf.dequantize()
        live = np.arange(10) < 7
        np.testing.assert_array_equal(
            np.asarray(deq.feature), np.asarray(forest.feature)
        )
        np.testing.assert_array_equal(
            np.asarray(deq.threshold)[live], np.asarray(forest.threshold)[live]
        )
        np.testing.assert_array_equal(np.asarray(deq.threshold)[~live], 0)
        if mode == "int8":
            bound = np.asarray(qf.leaf_scale)[:, None] / 2 + 1e-7
        else:
            bound = np.abs(np.asarray(forest.leaf_value)) * 2.0**-11 + 1e-7
        assert (
            np.abs(np.asarray(deq.leaf_value) - np.asarray(forest.leaf_value))
            <= bound
        ).all()


def test_quantize_range_checks(key):
    """Bin ids that do not fit the packed threshold dtype must raise, and
    unknown modes must raise — never silently wrap."""
    _, forest = _quantized_case(key, 8, 6, 64, 4, 3, 4)
    with pytest.raises(ValueError, match="int8|fp16"):
        forest.quantize("int4")
    wide = forest._replace(
        threshold=forest.threshold.at[0, 0].set(200)  # n_bins > 128
    )
    with pytest.raises(ValueError, match="int8"):
        wide.quantize("int8")
    wide.quantize("fp16")  # 200 fits int16
    huge = forest._replace(threshold=forest.threshold.at[0, 0].set(40000))
    with pytest.raises(ValueError, match="int16"):
        huge.quantize("fp16")
