"""The on-device Graph500 Kronecker generator: quadrant frequencies,
label permutation, and the seed."""
from __future__ import annotations

import numpy as np
import pytest

from bench import kron

INIT = dict(a=0.57, b=0.19, c=0.19)


def edges(seed, scale=10, permute=True, blocks=4):
    return kron.kronecker_edges(seed, scale=scale, edge_factor=16,
                                permute=permute, blocks=blocks, **INIT)


def test_sizes_and_ids():
    src, dst = edges(5)
    assert src.dtype == dst.dtype == np.int32
    assert len(src) == len(dst) == 16 << 10
    assert 0 <= src.min() and max(src.max(), dst.max()) < 1 << 10


@pytest.mark.parametrize("bit", [0, 5, 9])
def test_quadrant_frequencies_per_bit(bit):
    """Unpermuted, each id bit picks the Graph500 quadrant: neither bit
    0.57, destination only 0.19, source only 0.19, both 0.05."""
    src, dst = edges(11, permute=False)
    s = (src >> bit) & 1
    d = (dst >> bit) & 1
    freq = np.array([np.mean((s == i) & (d == j))
                     for i, j in ((0, 0), (0, 1), (1, 0), (1, 1))])
    # 16384 draws: a binomial share's standard error is under 0.004
    np.testing.assert_allclose(freq, [0.57, 0.19, 0.19, 0.05], atol=0.02)


def test_labels_are_one_permutation_of_the_unpermuted_graph():
    plain = edges(3, permute=False)
    perm = edges(3, permute=True)
    mapping = {}
    for u, p in zip(np.concatenate(plain), np.concatenate(perm)):
        assert mapping.setdefault(int(u), int(p)) == int(p)
    assert len(set(mapping.values())) == len(mapping)      # injective
    # degree no longer follows the id: vertex 0 (all bits 0, the
    # heaviest unpermuted) is moved
    assert mapping[0] != 0


def test_same_seed_same_graph():
    """Seeds past 32 bits are taken whole."""
    a = edges(2**31 + 77)
    for x, y in zip(a, edges(2**31 + 77)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], edges(2**31 + 78)[0])
    assert not np.array_equal(a[0], edges(2**32 + 2**31 + 77)[0])


def test_refuses_uneven_blocks():
    with pytest.raises(ValueError):
        edges(1, scale=4, blocks=3)
