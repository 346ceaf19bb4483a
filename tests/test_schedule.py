"""Schedule correctness: coverage, conflict-freedom, load balance."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 containers lack hypothesis; @given tests skip
    from conftest import given, settings, st

from repro.core import schedule as sched


@pytest.mark.parametrize("n", [3, 4, 5, 8, 13, 20])
def test_enumeration_covers_T_exactly_once(n):
    trips = sched.enumerate_triplets(n)
    assert trips.shape == (sched.n_triplets(n), 3)
    seen = set(map(tuple, trips.tolist()))
    expect = {
        (i, j, k)
        for i in range(n)
        for j in range(i + 1, n)
        for k in range(j + 1, n)
    }
    assert seen == expect
    assert len(trips) == len(seen)  # no duplicates


@pytest.mark.parametrize("n", [5, 9, 14, 24])
def test_diagonals_are_conflict_free(n):
    for d in sched.diagonal_list(n):
        assert sched.validate_conflict_free(d), (d.i, d.k)


@given(st.integers(min_value=3, max_value=40))
@settings(max_examples=20, deadline=None)
def test_property_conflict_free_and_partition(n):
    diags = sched.diagonal_list(n)
    total = 0
    for d in diags:
        # Within a diagonal, (i, k) pairs are distinct and i+k is constant.
        s = d.i + d.k
        assert np.all(s == s[0])
        assert len(set(d.i.tolist())) == d.num_sets
        assert np.all(d.k >= d.i + 2)
        total += d.num_triplets
    assert total == sched.n_triplets(n)


@given(st.integers(min_value=3, max_value=28))
@settings(max_examples=15, deadline=None)
def test_property_two_triplets_share_le_one_index(n):
    rng = np.random.default_rng(n)
    for d in sched.diagonal_list(n):
        if d.num_sets < 2:
            continue
        # sample pairs of sets rather than all (keeps the property test fast)
        for _ in range(10):
            a, b = rng.choice(d.num_sets, size=2, replace=False)
            ia, ka = int(d.i[a]), int(d.k[a])
            ib, kb = int(d.i[b]), int(d.k[b])
            ja = rng.integers(ia + 1, ka)
            jb = rng.integers(ib + 1, kb)
            assert len({ia, ja, ka} & {ib, jb, kb}) <= 1


def test_padded_schedule_consistent():
    n = 17
    s = sched.build_schedule(n)
    assert s.num_diagonals == len(sched.diagonal_list(n))
    # masked entries are -1; active ones satisfy k >= i+2
    m = s.set_mask
    assert np.all(s.diag_i[~m] == -1)
    assert np.all(s.diag_k[m] >= s.diag_i[m] + 2)
    # padding to lane multiples
    s128 = sched.build_schedule(n, pad_sets_to=8)
    assert s128.max_sets % 8 == 0


def test_device_assignment_balance():
    # paper Fig. 3: r mod p keeps per-processor triplet counts balanced
    n, p = 200, 16
    d = max(sched.diagonal_list(n), key=lambda d: d.num_sets)
    asg = sched.device_assignment(d.num_sets, p)
    loads = np.zeros(p)
    for r, sz in zip(asg, d.sizes):
        loads[r] += sz
    assert loads.max() <= 1.5 * max(loads.mean(), 1.0)


# ------------------------------------------------- schedule-native layout
@pytest.mark.parametrize("n,nb,procs", [(5, 1, 1), (8, 3, 1), (13, 4, 2), (16, 2, 3)])
def test_layout_covers_all_duals_once(n, nb, procs):
    """Every triplet contributes exactly 3 duals; the layout's conversion
    maps must cover each dense slot exactly once, with no slab collisions."""
    lay = sched.build_layout(n, num_buckets=nb, procs=procs)
    assert lay.num_duals == 3 * sched.n_triplets(n)
    seen_dense = set()
    for bl in lay.buckets:
        # no two duals share a slab slot
        assert len(np.unique(bl.slab_index)) == bl.num_duals
        a, b, c = bl.dense_index
        seen_dense.update(zip(a.tolist(), b.tolist(), c.tolist()))
    expect = set()
    for (i, j, k) in sched.enumerate_triplets(n):
        expect.update({(i, j, k), (i, k, j), (j, k, i)})
    assert seen_dense == expect


@given(n=st.integers(3, 20), nb=st.integers(1, 5), procs=st.integers(1, 4))
@settings(max_examples=20, deadline=None)
def test_property_layout_roundtrip(n, nb, procs):
    """dense → slabs → dense is the identity on the support of real duals."""
    lay = sched.build_layout(n, num_buckets=nb, procs=procs)
    rng = np.random.default_rng(n * 100 + nb * 10 + procs)
    ytri = np.zeros((n, n, n))
    for (i, j, k) in sched.enumerate_triplets(n):
        ytri[i, j, k], ytri[i, k, j], ytri[j, k, i] = rng.uniform(size=3)
    slabs = sched.dense_to_duals(lay, ytri, np.float64)
    np.testing.assert_array_equal(sched.duals_to_dense(lay, slabs), ytri)


def test_layout_matches_device_assignment():
    """Folded-lane placement follows the paper's Fig. 3 r mod p rule: lane f
    of a diagonal holds sets (f, C-1-f) and goes to device f mod p."""
    n, p = 14, 3
    lay = sched.build_layout(n, num_buckets=1, procs=p)
    diags = sched.diagonal_list(n)
    bl = lay.buckets[0]
    for r, d in enumerate(diags):
        C = d.num_sets
        for f in range((C + 1) // 2):
            dev, slot = f % p, f // p
            assert bl.i[dev, r, slot] == d.i[f]
            assert bl.k[dev, r, slot] == d.k[f]
            assert bl.sizes[dev, r, slot] == d.k[f] - d.i[f] - 1
            cB = C - 1 - f
            if cB > f:
                assert bl.i2[dev, r, slot] == d.i[cB]
                assert bl.k2[dev, r, slot] == d.k[cB]
            else:
                assert bl.i2[dev, r, slot] == -1
                assert bl.sizes2[dev, r, slot] == 0


def test_layout_folded_lanes_have_uniform_height():
    """Folding pairs set f with set C-1-f, whose sizes sum to a constant —
    all *paired* lanes of a diagonal have exactly equal height (the odd
    middle set rides alone at no more than that height)."""
    n = 23
    lay = sched.build_layout(n, num_buckets=1, procs=1)
    bl = lay.buckets[0]
    heights = bl.sizes + bl.sizes2  # (1, D, Cl)
    for r in range(heights.shape[1]):
        lane = bl.i[0, r] >= 0
        paired = lane & (bl.i2[0, r] >= 0)
        h = heights[0, r]
        if paired.any():
            assert h[paired].max() == h[paired].min(), (r, h)
            assert h[lane].max() == h[paired].max()


def test_layout_memory_is_3_choose_n3_plus_padding():
    """The whole point: folded slab memory tracks 3·C(n,3) (padding factor
    < 1.7 at modest bucket counts), well under the dense n^3 tensor."""
    n = 40
    lay = sched.build_layout(n, num_buckets=8, procs=1)
    slab_floats = sum(bl.slab_size for bl in lay.buckets)
    real = 3 * sched.n_triplets(n)
    assert slab_floats >= real  # covers every dual
    assert slab_floats <= 1.7 * real  # bounded padding
    assert slab_floats < n ** 3  # strictly under the dense tensor


@pytest.mark.parametrize("n,nb,procs", [(3, 1, 1), (17, 3, 2), (40, 6, 1),
                                        (41, 4, 4)])
def test_slab_dims_match_layout(n, nb, procs):
    """The cheap shape planner agrees with the full layout build."""
    lay = sched.build_layout(n, num_buckets=nb, procs=procs)
    want = [(b.slab_shape[1], b.slab_shape[3], b.slab_shape[4])
            for b in lay.buckets]
    assert sched.slab_dims(n, num_buckets=nb, procs=procs) == want


def _fold_is_contiguous(bl, dev: int = 0) -> bool:
    """Whether every live slot f of device ``dev``, on every diagonal of
    ``bl``, holds i1 = x+f, k1 = z-f, i2 = x+C-1-f, k2 = z-C+1+f, with
    (x, z) the diagonal's first set and C its set count: the band
    engine's precondition (fused_pass._band_engine)."""
    for r in range(bl.num_diagonals):
        i1, k1, i2, k2 = (a[dev, r] for a in (bl.i, bl.k, bl.i2, bl.k2))
        f = np.flatnonzero(i1 >= 0)
        x, z = int(bl.i[0, r, 0]), int(bl.k[0, r, 0])
        c = (z - x - 2) // 2 + 1
        p = i2[f] >= 0  # paired lanes (an odd diagonal's middle set is not)
        if not (np.array_equal(i1[f], x + f) and np.array_equal(k1[f], z - f)
                and np.array_equal(i2[f][p], (x + c - 1 - f)[p])
                and np.array_equal(k2[f][p], (z - c + 1 + f)[p])):
            return False
    return True


@given(n=st.integers(3, 60), nb=st.integers(1, 6))
@settings(max_examples=25, deadline=None)
def test_property_single_device_fold_is_contiguous(n, nb):
    """With one device, lane f of a diagonal holds set f and its partner
    C-1-f, so a lane's row and column slices are rows and columns of
    dense blocks of X at offsets fixed per diagonal."""
    for bl in sched.build_layout(n, num_buckets=nb, procs=1).buckets:
        assert _fold_is_contiguous(bl)


def test_dealt_fold_is_not_contiguous():
    """Dealt round robin over four devices, a device's slots hold lanes
    f, f+4, ...: its sets are strided, which is why the sharded delta
    path keeps the element-indexed engine."""
    lay = sched.build_layout(24, num_buckets=2, procs=4)
    assert not all(_fold_is_contiguous(bl, dev) for bl in lay.buckets
                   for dev in range(4))
