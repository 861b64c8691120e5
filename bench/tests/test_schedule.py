import math

import numpy as np
import pytest

from bench import data as D
from bench import schedule as S

SEED = 2**33 + 17


def test_schedule_repeats_exactly():
    a = S.open_loop(SEED, 100.0, 20.0, 1, 1024)
    b = S.open_loop(SEED, 100.0, 20.0, 1, 1024)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    c = S.open_loop(SEED + 1, 100.0, 20.0, 1, 1024)
    assert not np.array_equal(a[0], c[0])
    # every seed offers the same gaps and sizes, in another order
    assert np.allclose(np.sort(np.diff(a[0], prepend=0)), np.sort(np.diff(c[0], prepend=0)))
    assert np.array_equal(np.sort(a[1]), np.sort(c[1]))


def _ks(sample, cdf):
    x = np.sort(sample)
    n = len(x)
    f = cdf(x)
    return max(np.max(np.arange(1, n + 1) / n - f), np.max(f - np.arange(n) / n))


def test_gaps_are_exponential_and_sizes_log_uniform():
    rate, seconds = 100.0, 20.0
    offsets, sizes = S.open_loop(SEED, rate, seconds, 1, 1024)
    gaps = np.diff(offsets, prepend=0.0)
    assert len(gaps) == 2000
    assert _ks(gaps, lambda x: 1 - np.exp(-rate * x)) < 0.01
    assert abs(offsets[-1] - seconds) / seconds < 0.01
    assert sizes.min() >= 1 and sizes.max() <= 1024
    # P(size <= s) for floor(exp(U * log(1025))) is log(s + 1) / log(1025)
    values = np.unique(sizes)
    ecdf = np.searchsorted(np.sort(sizes), values, side="right") / len(sizes)
    assert np.max(np.abs(ecdf - np.log(values + 1) / math.log(1025))) < 0.01
    assert abs(sizes.mean() - 1024 / math.log(1025)) < 3.0


def test_request_rows_repeat():
    assert np.array_equal(S.request_rows(SEED, 100, 28), S.request_rows(SEED, 100, 28))


def test_program_seed_keeps_high_bits():
    assert D.program_seed(5) != D.program_seed(2**33 + 5)
    assert 0 <= D.program_seed(2**40) < 2**31


def test_dense_data_repeats_and_keeps_its_properties():
    a = D.dense_dataset(SEED, 20000, 28, 64)
    b = D.dense_dataset(SEED, 20000, 28, 64)
    for x, y in zip(a, b):
        if hasattr(x, "shape"):
            assert np.array_equal(np.asarray(x), np.asarray(y))
    bins = np.asarray(a.bins)
    assert bins.min() == 0 and bins.max() == 63
    # bins of N(0, 1) features under the normal quantile edges are uniform
    counts = np.bincount(bins.ravel(), minlength=64) / bins.size
    assert np.all(np.abs(counts - 1 / 64) < 0.002)
    assert abs(float(a.labels.mean()) - 0.5) < 0.05
    m = np.asarray(a.multiplicity)
    assert m[0] == np.round(20000 / np.sum(1.0 / np.arange(1, 20001)))
    assert m.min() == 1 and np.all(np.diff(m) <= 0)


def test_sparse_data_repeats_and_keeps_its_properties():
    from repro.trees.binning import to_dense

    a = D.sparse_dataset(SEED, 3000, 8000, 12, 64, 24)
    b = D.sparse_dataset(SEED, 3000, 8000, 12, 64, 24)
    for f in ("indices", "codes", "feat_rows", "feat_codes", "zero_bin"):
        assert np.array_equal(np.asarray(getattr(a.bins, f)), np.asarray(getattr(b.bins, f)))
    dense = np.asarray(to_dense(a.bins))
    nnz = (dense != 0).sum(axis=1)
    assert nnz.max() == 12 and nnz.mean() > 11.9  # few repeated columns
    assert set(np.unique(dense)) <= {0, 63}
    # the feature-major store holds the same entries, rows ascending
    fr = np.asarray(a.bins.feat_rows)
    again = np.zeros_like(dense)
    for f in range(fr.shape[0]):
        rows = fr[f][fr[f] >= 0]
        assert np.all(np.diff(rows) > 0)
        again[rows, f] = 63
    assert np.array_equal(again, dense)
    assert abs(float(a.labels.mean()) - 0.5) < 0.05


def test_sparse_geometry_outside_the_binary_case_is_refused():
    with pytest.raises(ValueError, match="quantile edges"):
        D.sparse_dataset(SEED, 3000, 100, 20, 64, 1000)


def test_sparse_width_is_fixed_and_checked():
    a = D.sparse_dataset(SEED, 3000, 8000, 12, 64, 24)
    b = D.sparse_dataset(SEED + 1, 3000, 8000, 12, 64, 24)
    assert a.bins.feat_rows.shape == b.bins.feat_rows.shape == (8000, 24)
    with pytest.raises(ValueError, match="feature-major width"):
        D.sparse_dataset(SEED, 3000, 8000, 12, 64, 3)
