"""System-level invariants (hypothesis property tests).

These are the paper's mathematical guarantees, checked as executable
properties of the implementation rather than single examples.
"""

import numpy as np
import pytest

try:
    from hypothesis import example, given, settings, strategies as st
except ImportError:  # tier-1 containers lack hypothesis; @given tests skip
    from conftest import example, given, settings, st

from repro.core import convergence, dykstra, problems
from repro.core.parallel_dykstra import ParallelSolver


def _metric_matrix(n, rng):
    """A guaranteed-metric distance matrix: shortest paths of a random
    positive graph (metric closure)."""
    w = rng.uniform(0.2, 1.0, (n, n))
    w = np.minimum(w, w.T)
    np.fill_diagonal(w, 0.0)
    # Floyd–Warshall
    d = w.copy()
    for k in range(n):
        d = np.minimum(d, d[:, k][:, None] + d[k, :][None, :])
    return np.triu(d, 1)


@given(n=st.integers(4, 12), seed=st.integers(0, 10**6))
@settings(max_examples=15, deadline=None)
def test_metric_input_is_fixed_point(n, seed):
    """If D already satisfies all triangle inequalities, the l2-nearness
    solution is D itself and one pass changes nothing (all θ = 0)."""
    rng = np.random.default_rng(seed)
    d = _metric_matrix(n, rng)
    p = problems.metric_nearness_l2(d)
    assert convergence.max_violation(p, d) <= 1e-9
    st_ = ParallelSolver(p).run(passes=1)
    np.testing.assert_allclose(np.asarray(st_.x), d, rtol=1e-5, atol=1e-6)
    # schedule-native dual slabs: every dual must stay (near) zero
    assert max(float(np.abs(np.asarray(y)).max()) for y in st_.yd) <= 1e-6


@given(n=st.integers(4, 10), seed=st.integers(0, 10**6))
@example(n=7, seed=0)
@settings(max_examples=10, deadline=None)
def test_duals_nonnegative_and_violation_decreases(n, seed):
    """θ ≥ 0, and the dual objective never falls. Dykstra is exact
    coordinate ascent on the dual, whose value here (b = 0) is
    −(ε/2)·xᵀWx (core/convergence.py), so xᵀW x must not rise from pass
    to pass. The max violation itself is not monotone: at n=7, seed=0 it
    is 3.0e-8 after 2 passes and 2.5e-2 after 5, as the dual corrections
    push x back out of the feasible set; it falls only in the limit."""
    rng = np.random.default_rng(seed)
    d = np.triu((rng.uniform(0, 1, (n, n)) > 0.5).astype(float), k=1)
    p = problems.metric_nearness_l2(d)
    iu = np.triu_indices(n, 1)
    # f32 iterates: x'Wx is exact up to rounding of x (measured rises stay
    # under 1e-7 of d'Wd).
    floor = 1e-6 * max(float(np.sum(p.w[iu] * d[iu] ** 2)), 1.0)

    def xwx(s):
        x = np.asarray(s.x, np.float64)
        return float(np.sum(p.w[iu] * x[iu] ** 2))

    solver = ParallelSolver(p)
    st_ = solver.run(passes=2)
    q = [xwx(st_)]
    for _ in range(20):
        st_ = solver.run(st_, passes=1)
        q.append(xwx(st_))
    assert min(float(np.asarray(y).min()) for y in st_.yd) >= -1e-6  # θ ≥ 0
    assert max(np.diff(q)) <= floor


@given(seed=st.integers(0, 10**6))
@settings(max_examples=8, deadline=None)
def test_dykstra_invariant_x_equals_x0_minus_duals(seed):
    """Dykstra maintains x = x0 − (1/ε)W⁻¹Aᵀy exactly (the relation behind
    the cheap duality gap; DESIGN.md §2) — reconstruct x from the duals."""
    n = 8
    rng = np.random.default_rng(seed)
    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    p = problems.metric_nearness_l2(d)
    st_ = dykstra.solve_serial(p, max_passes=3, order="schedule")
    # rebuild: x = d + (1/(eps w)) Σ_constraints y_i * (∓a_i)
    x_rec = p.x0().copy()
    for a in range(n):
        for b in range(a + 1, n):
            for c in range(n):
                if c in (a, b):
                    continue
                y = st_.ytri[a, b, c]
                if y == 0.0:
                    continue
                ac = (min(a, c), max(a, c))
                bc = (min(b, c), max(b, c))
                x_rec[a, b] -= y / (p.eps * p.w[a, b])
                x_rec[ac] += y / (p.eps * p.w[ac])
                x_rec[bc] += y / (p.eps * p.w[bc])
    np.testing.assert_allclose(x_rec, st_.x, rtol=1e-8, atol=1e-10)


@given(n=st.integers(4, 9), seed=st.integers(0, 10**6), passes=st.integers(1, 4))
@settings(max_examples=8, deadline=None)
def test_parallel_equals_serial_property(n, seed, passes):
    """Property form of the §III.A theorem: the conflict-free reordering
    never changes the iterate, for any instance and pass count."""
    rng = np.random.default_rng(seed)
    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    p = problems.metric_nearness_l2(d)
    st_ser = dykstra.solve_serial(p, max_passes=passes, order="schedule")
    st_par = ParallelSolver(p).run(passes=passes)
    np.testing.assert_allclose(np.asarray(st_par.x), st_ser.x,
                               rtol=3e-4, atol=3e-5)


def test_solution_symmetric_under_relabeling():
    """Permuting the points permutes the solution (schedule introduces no
    labeling bias in the fixed point)."""
    n = 9
    rng = np.random.default_rng(3)
    dfull = rng.uniform(0, 1, (n, n))
    dfull = np.triu(dfull, 1) + np.triu(dfull, 1).T
    perm = rng.permutation(n)

    def solve(dm):
        p = problems.metric_nearness_l2(np.triu(dm, 1))
        stx = ParallelSolver(p).run(passes=300)
        x = np.asarray(stx.x, np.float64)
        return np.triu(x, 1) + np.triu(x, 1).T

    x1 = solve(dfull)
    x2 = solve(dfull[np.ix_(perm, perm)])
    np.testing.assert_allclose(x2, x1[np.ix_(perm, perm)], atol=2e-3)
