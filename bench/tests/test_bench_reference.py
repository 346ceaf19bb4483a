"""The reference's dual layout: every triangle constraint has one cell."""

import itertools

import pytest

from bench import reference


@pytest.mark.parametrize("n", [3, 4, 7, 16])
def test_triangle_cells_cover_every_triplet_once(n):
    seen = []
    for d in range(len(reference.diagonals(n))):
        t, c, i, j, k = reference.triangle_cells(n, d)
        assert ((0 <= i) & (i < j) & (j < k) & (k < n)).all()
        assert (j - i - 1 == t).all()
        seen += list(zip(i.tolist(), j.tolist(), k.tolist()))
    assert sorted(seen) == list(itertools.combinations(range(n), 3))
