"""Project-and-Forget active-set subsystem (DESIGN.md §13).

Pins the four claims the subsystem makes:

  * ORACLE — the sparse solve lands on the SAME full-constraint
    certificate as the dense solver (violation ≤ tol, LP objective
    within 1e-6 relative) on planted-partition CC-LP instances, with
    and without slab compaction;
  * FIXED POINTS — with everything active the sparse pass IS the dense
    pass (bitwise), and forget/revive only moves zeros around;
  * COMPACTION — one pass over compacted slabs is bitwise one masked
    pass over the full slabs (compaction skips time, never math), and
    the dual/mask plan round-trips exactly;
  * ROBUSTNESS — an absurdly aggressive forget tolerance (drop
    everything every round) still converges, because the revival probe
    re-admits what the iterate starts to violate.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import problems
from repro.core.parallel_dykstra import ParallelSolver
from repro.graphs import generators, jaccard
from repro.sparse import SparseSolver


@pytest.fixture()
def x64():
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


def _cc_problem(n, seed=0, eps=0.05):
    adj, _ = generators.planted_partition(n, seed=seed)
    dissim, w = jaccard.signed_instance(adj)
    return problems.correlation_clustering_lp(dissim, w, eps=eps)


def _certificates_match(info_s, info_d, tol):
    assert info_s["converged"], info_s
    assert info_d["converged"], info_d
    assert info_s["max_violation"] <= tol
    assert info_d["max_violation"] <= tol
    lp_s, lp_d = info_s["lp_objective"], info_d["lp_objective"]
    assert abs(lp_s - lp_d) <= 1e-6 * max(1.0, abs(lp_d)), (lp_s, lp_d)


# ----------------------------------------------------------- oracle
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sparse_matches_full_constraint_oracle(x64, seed):
    p = _cc_problem(20, seed=seed)
    tol = 1e-5
    sp = SparseSolver(p, bucket_diagonals=3, forget_every=5,
                     dtype=jnp.float64)
    st, info_s = sp.run_until(tol=tol, max_passes=400)
    dn = ParallelSolver(p, bucket_diagonals=3, dtype=jnp.float64)
    _, info_d = dn.run_until(tol=tol, max_passes=400)
    _certificates_match(info_s, info_d, tol)
    assert info_s["active_fraction"] <= 1.0
    assert info_s["rounds"] >= 1


def test_sparse_oracle_with_compaction(x64):
    p = _cc_problem(20, seed=1)
    tol = 1e-5
    sp = SparseSolver(
        p, bucket_diagonals=3, forget_every=5, compact_every=2,
        compact_pad=4, dtype=jnp.float64,
    )
    st, info_s = sp.run_until(tol=tol, max_passes=400)
    dn = ParallelSolver(p, bucket_diagonals=3, dtype=jnp.float64)
    _, info_d = dn.run_until(tol=tol, max_passes=400)
    _certificates_match(info_s, info_d, tol)
    assert info_s["compactions"] >= 1
    # dense interchange duals expand through the compaction plan
    dd = sp.duals_to_dense(st)
    assert np.all(np.isfinite(dd))


# ------------------------------------------------------ fixed points
def test_all_active_sparse_pass_is_dense_pass_bitwise(x64):
    p = _cc_problem(14, seed=4)
    sp = SparseSolver(p, bucket_diagonals=2, forget_every=10,
                     dtype=jnp.float64)
    dn = ParallelSolver(p, bucket_diagonals=2, dtype=jnp.float64)
    st_s = sp.run(passes=3)
    # pass by pass, as the sparse run goes: a multi-pass scan may fuse,
    # and round, differently from one pass
    st_d = dn.init_state()
    for _ in range(3):
        st_d = dn.run(st_d, passes=1)
    np.testing.assert_array_equal(np.asarray(st_s.x), np.asarray(st_d.x))
    # duals agree on every real cell (sparse pins padding/ghost cells at
    # 0.0 whereas the dense pass leaves them don't-care)
    for ys, yd, sl in zip(st_s.yd, st_d.yd, sp._slabs):
        act = np.broadcast_to(
            np.asarray(sl["valid"])[:, None], np.asarray(ys).shape
        )
        np.testing.assert_array_equal(
            np.asarray(ys)[act], np.asarray(yd)[act]
        )


def test_forget_zeroes_duals_and_shrinks_mask(x64):
    p = _cc_problem(16, seed=5)
    sp = SparseSolver(p, bucket_diagonals=2, forget_every=10,
                     dtype=jnp.float64)
    st = sp.run(passes=6)
    st2 = sp._forget_revive(st, sp._slabs, 0.0, 0.5 * 1e-4)
    shrank = False
    for sl, am0, am, yb in zip(sp._slabs, st.amask, st2.amask, st2.yd):
        am0, am = np.asarray(am0), np.asarray(am)
        assert not np.any(am & ~np.asarray(sl["valid"]))  # am ⊆ valid
        # duals outside the new mask are pinned at exactly 0.0
        off = np.broadcast_to(~am[:, None], np.asarray(yb).shape)
        assert np.all(np.asarray(yb)[off] == 0.0)
        shrank |= am.sum() < am0.sum()
    assert shrank  # some constraints really were slack after 6 passes
    assert sp.active_fraction(st2) < sp.active_fraction(st)


def test_revive_reactivates_violated_cells(x64):
    p = _cc_problem(16, seed=5)
    sp = SparseSolver(p, bucket_diagonals=2, dtype=jnp.float64)
    st = sp.run(passes=2)
    # forget EVERYTHING (ftol=inf): survivors are exactly the cells the
    # revival probe flags as violated beyond rtol.
    st2 = sp._forget_revive(st, sp._slabs, np.inf, 1e-9)
    for sl, am in zip(sp._slabs, st2.amask):
        viol = np.asarray(
            sl["valid"] & (sp._bucket_slack(st.x, sl) > 1e-9)
        )
        np.testing.assert_array_equal(np.asarray(am), viol)
        # revived cells restart from y = 0
    for yb, am in zip(st2.yd, st2.amask):
        off = np.broadcast_to(
            ~np.asarray(am)[:, None], np.asarray(yb).shape
        )
        assert np.all(np.asarray(yb)[off] == 0.0)


# -------------------------------------------------------- compaction
def test_compact_pass_is_masked_full_pass_bitwise(x64):
    p = _cc_problem(18, seed=6)
    kw = dict(bucket_diagonals=3, forget_every=3, dtype=jnp.float64)
    sp = SparseSolver(p, **kw, compact_every=2, compact_pad=4)
    st = sp.run(passes=6)
    rtol = 0.5 * 1e-4
    st = sp._forget_revive(st, sp._slabs, 0.0, rtol)
    assert sp.active_fraction(st) < 1.0
    stc = sp._recompact(st, rtol)
    assert sp._plan is not None
    # the full-slab twin runs the SAME mask over the uncompacted slabs
    full = SparseSolver(p, **kw)
    ams, yds = sp._expand_to_full(stc)
    stf = dataclasses.replace(
        stc,
        yd=[jnp.asarray(y, sp.dtype) for y in yds],
        amask=[jnp.asarray(m) for m in ams],
    )
    out_c = sp._masked_pass_fn()(stc, sp._slabs)
    out_f = full._masked_pass_fn()(stf, full._slabs)
    np.testing.assert_array_equal(np.asarray(out_c.x), np.asarray(out_f.x))
    ams_c, yds_c = sp._expand_to_full(out_c)
    for y_c, y_f, m in zip(yds_c, out_f.yd, ams_c):
        np.testing.assert_array_equal(
            y_c[m[:, None] & np.ones((1, 3, 1, 1), bool)],
            np.asarray(y_f)[m[:, None] & np.ones((1, 3, 1, 1), bool)],
        )


def test_compaction_plan_roundtrip(x64):
    p = _cc_problem(16, seed=7)
    sp = SparseSolver(
        p, bucket_diagonals=2, forget_every=3, compact_every=1,
        compact_pad=4, dtype=jnp.float64,
    )
    st = sp.run(passes=6)
    st = sp._forget_revive(st, sp._slabs, 0.0, 1e-5)
    stc = sp._recompact(st, 1e-5)
    rng = np.random.default_rng(0)
    for pb, sl in zip(sp._plan.buckets, sp._slabs):
        y = rng.normal(size=pb.comp_shape)  # (D', 3, T', Cl')
        y = np.where(np.asarray(sl["valid"])[:, None], y, 0.0)
        # expand → compact is the identity on compacted coordinates
        np.testing.assert_array_equal(pb.compact_duals(pb.expand_duals(y)), y)
        m = np.asarray(sl["valid"])
        np.testing.assert_array_equal(pb.compact_mask(pb.expand_mask(m)), m)
        # expanded mask stays within the full staged act mask
        assert pb.expand_mask(m).shape == (
            pb.full_shape[0], pb.full_shape[2], pb.full_shape[3]
        )


# -------------------------------------------------------- robustness
def test_aggressive_forget_still_converges(x64):
    p = _cc_problem(16, seed=8)
    tol = 1e-4
    sp = SparseSolver(
        p, bucket_diagonals=2, forget_every=2, forget_tol=1e9,
        dtype=jnp.float64,
    )
    st, info = sp.run_until(tol=tol, max_passes=600)
    assert info["converged"]
    assert info["max_violation"] <= tol
    dn = ParallelSolver(p, bucket_diagonals=2, dtype=jnp.float64)
    _, info_d = dn.run_until(tol=tol, max_passes=600)
    lp_s, lp_d = info["lp_objective"], info_d["lp_objective"]
    assert abs(lp_s - lp_d) <= 1e-5 * max(1.0, abs(lp_d))


def test_active_fraction_decays_with_telemetry():
    p = _cc_problem(30, seed=9)
    sp = SparseSolver(
        p, bucket_diagonals=4, forget_every=5, forget_tol=1e-6,
        compact_every=2, compact_pad=8,
    )
    st, info = sp.run_until(tol=1e-3, max_passes=200)
    assert info["converged"]
    traj = np.asarray(info["active_trajectory"])
    assert traj.size == min(info["rounds"], traj.size) and traj.size >= 1
    assert info["active_fraction"] < 0.9
    assert info["active_fraction"] == pytest.approx(
        sp.active_fraction(st)
    )
    assert info["rounds"] >= 2
    assert len(info["round_stats"]) >= 1
    for wall, passes, af in info["round_stats"]:
        assert wall >= 0.0 and passes >= 0 and 0.0 <= af <= 1.0


# ------------------------------------------------------------- stubs
def test_runtime_mode_stubs_raise():
    p = _cc_problem(12, seed=10)
    with pytest.raises(NotImplementedError, match="batched sparse"):
        SparseSolver.batched([p])
    with pytest.raises(NotImplementedError, match="sharded sparse"):
        SparseSolver.sharded(p)
    with pytest.raises(NotImplementedError, match="kernel route"):
        SparseSolver(p, use_kernel=True)
    with pytest.raises(NotImplementedError, match="fused execution"):
        SparseSolver(p, fused=False)
    sp = SparseSolver(p)
    with pytest.raises(NotImplementedError, match="no fixed-slab"):
        sp._one_pass(sp.init_state())
    with pytest.raises(ValueError, match="stop_rule"):
        sp.run_until(tol=1e-3, max_passes=2, stop_rule="bogus")
