"""The on-device generators: the seed fixes the arrays, and the label
distributions are the ones the configurations state."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from bench.data import dna_like, mnist8m_like  # noqa: E402


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 7])
def test_same_seed_same_arrays(seed):
    a = dna_like.make(harness.data_key(seed), 512, 16, 2)
    b = dna_like.make(harness.data_key(seed), 512, 16, 2)
    c = dna_like.make(harness.data_key(seed + 1), 512, 16, 2)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))


def test_seeds_past_32_bits_differ():
    a = harness.data_key(7)
    b = harness.data_key(2**33 + 7)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        harness.data_key(-1)


def test_dna_labels_balanced_and_features_binary():
    X, y = dna_like.make(harness.data_key(3), 20_000, 64, 2)
    X, y = np.asarray(X), np.asarray(y)
    assert set(np.unique(X).tolist()) == {0.0, 1.0}
    assert abs(X.mean() - dna_like.SPARSITY) < 0.01
    assert set(np.unique(y).tolist()) == {-1.0, 1.0}
    assert abs((y > 0).mean() - 0.5) < 0.02


def test_mnist8m_all_classes_present():
    X, y = mnist8m_like.make(harness.data_key(4), 20_000, 32, 10)
    X, y = np.asarray(X), np.asarray(y)
    assert y.dtype == np.int32
    assert sorted(np.unique(y).tolist()) == list(range(10))
    assert X.min() >= 0.0 and X.max() <= 1.0
    a = mnist8m_like.make(harness.data_key(4), 20_000, 32, 10)
    np.testing.assert_array_equal(X, np.asarray(a[0]))
