"""Batched solve service (DESIGN.md §8): ghost-padding fixed points,
batched-vs-solo parity (stop pass and iterate to 1e-10 in float64,
mixed-n batches including padded-ghost and empty slots), device pivot
rounding parity with the numpy oracle, the new stop rules and the
residual trajectory of ``run_until``, the micro-batching scheduler, and
the end-to-end graph -> clustering pipeline."""

import jax
import numpy as np
import pytest

from repro.core import engine, problems, rounding, schedule as sched
from repro.core.parallel_dykstra import ParallelSolver
from repro.graphs import generators, jaccard
from repro.serve import buckets as bk
from repro.serve.batching import BatchedSolver
from repro.serve.pipeline import cluster_graphs, round_device_batch
from repro.serve.scheduler import BatchScheduler


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


def _l2_problem(n, seed=0):
    rng = np.random.default_rng(seed)
    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    return problems.metric_nearness_l2(d)


# ------------------------------------------------------------- bucketing
def test_bucket_for_ladder():
    assert bk.bucket_for(10) == 32
    assert bk.bucket_for(32) == 32
    assert bk.bucket_for(33) == 64
    with pytest.raises(ValueError):
        bk.bucket_for(500)


def test_pad_problem_ghost_contract():
    p = _cc_problem(11, seed=2)
    pp = bk.pad_problem(p, 16)
    assert pp.n == 16 and pp.eps == p.eps and pp.box == p.box
    # inert ghost data: x0/f0 are exactly 0 on every ghost cell
    assert np.all(pp.x0()[11:, :] == 0) and np.all(pp.x0()[:, 11:] == 0)
    assert np.all(pp.f0()[11:, :] == 0) and np.all(pp.f0()[:, 11:] == 0)
    np.testing.assert_array_equal(pp.d[:11, :11], p.d)
    np.testing.assert_array_equal(pp.w[:11, :11], p.w)
    with pytest.raises(ValueError):
        bk.pad_problem(p, 10)


def test_family_mismatch_rejected():
    fam = bk.family_of(_cc_problem(10), np.float64)
    solver = BatchedSolver(16, batch=2, family=fam, num_buckets=2)
    with pytest.raises(ValueError):
        solver.stack([_l2_problem(10)])
    with pytest.raises(ValueError):
        solver.stack([_cc_problem(8)] * 3)  # more instances than slots


# ---------------------------------------------------- ghost fixed points
def test_ghost_cells_are_fixed_points(x64):
    """Padded standalone solve: ghost triangles are structurally masked
    (active step count == 3 real triangle visits per pass == C(n_real,3)
    steps) and ghost cells of X/F and the pair/box duals never move."""
    n_real, bucket_n = 11, 16
    pp = bk.pad_problem(_cc_problem(n_real, seed=4), bucket_n)
    solver = ParallelSolver(pp, dtype=np.float64, bucket_diagonals=3,
                            n_real=n_real)
    active = sum(int(np.asarray(b["act"]).sum()) for b in solver.staged_buckets)
    assert active == sched.n_triplets(n_real)
    st = solver.run(passes=7)
    for arr, name in ((st.x, "x"), (st.f, "f")):
        a = np.asarray(arr)
        assert np.all(a[n_real:, :] == 0) and np.all(a[:, n_real:] == 0), name
    for arr in (st.ypair, st.ybox):
        a = np.asarray(arr)
        assert np.all(a[:, n_real:, :] == 0) and np.all(a[:, :, n_real:] == 0)


def test_padded_solve_converges_to_native_optimum(x64):
    """The padded schedule visits the real constraints in a different
    order, so trajectories differ — but the strictly convex QP has one
    optimum, and both drivers must land on it."""
    n_real, bucket_n = 12, 16
    p = _l2_problem(n_real, seed=1)
    pad = ParallelSolver(bk.pad_problem(p, bucket_n), dtype=np.float64,
                         bucket_diagonals=3, n_real=n_real)
    nat = ParallelSolver(p, dtype=np.float64, bucket_diagonals=3)
    stp, ip = pad.run_until(tol=1e-8, max_passes=2000, check_every=50)
    stn, inn = nat.run_until(tol=1e-8, max_passes=2000, check_every=50)
    assert ip["converged"] and inn["converged"]
    np.testing.assert_allclose(
        np.asarray(stp.x)[:n_real, :n_real], np.asarray(stn.x),
        rtol=0, atol=1e-6,
    )


# ------------------------------------------------- batched vs solo parity
@pytest.mark.parametrize("stop_rule", ["absolute", "plateau"])
def test_batched_matches_solo_mixed_n(x64, stop_rule):
    """Every instance of a mixed-n B=4 batch (two ghost-padded, one at
    native bucket size, one empty slot) must stop at exactly the pass its
    standalone padded run_until stops at, with the identical iterate to
    1e-10 — the batched engine is the solo engine, vmapped."""
    bucket_n, B = 14, 4
    probs = [_cc_problem(14, seed=0), _cc_problem(10, seed=1),
             _cc_problem(12, seed=2)]
    fam = bk.family_of(probs[0], np.float64)
    bs = BatchedSolver(bucket_n, batch=B, family=fam, num_buckets=3)
    inst = bs.stack(probs)  # slot 3 stays empty
    kw = dict(tol=1e-4, max_passes=60, check_every=5, stop_rule=stop_rule)
    st, info = bs.run_until(inst, **kw)
    xb = np.asarray(st.x)
    for i, p in enumerate(probs):
        solo = ParallelSolver(bk.pad_problem(p, bucket_n), dtype=np.float64,
                              bucket_diagonals=3, n_real=p.n)
        sst, sinfo = solo.run_until(**kw)
        assert info["passes"][i] == sinfo["passes"], (i, stop_rule)
        assert bool(info["converged"][i]) == sinfo["converged"], i
        assert np.abs(xb[i] - np.asarray(sst.x)).max() <= 1e-10, i
        assert abs(info["max_violation"][i] - sinfo["max_violation"]) <= 1e-10
        assert abs(info["duality_gap"][i] - sinfo["duality_gap"]) <= 1e-10
    # the empty slot converges at the first possible check (plateau needs
    # two checks: the first has no objective baseline) and stays all-zero
    expect = 5 if stop_rule == "absolute" else 10
    assert bool(info["converged"][3]) and info["passes"][3] == expect
    assert np.all(xb[3] == 0)


def test_batched_max_passes_and_resume(x64):
    """tol=0 never converges: every slot must stop at exactly max_passes
    (partial final chunk included), and re-running at the same target is
    a no-op that still reports a finite stopping vector."""
    fam = bk.family_of(_cc_problem(8), np.float64)
    bs = BatchedSolver(10, batch=2, family=fam, num_buckets=2)
    inst = bs.stack([_cc_problem(8, seed=3), _cc_problem(10, seed=4)])
    st, info = bs.run_until(inst, tol=0.0, max_passes=7, check_every=3)
    assert list(info["passes"]) == [7, 7]
    assert not info["converged"].any()
    st2, info2 = bs.run_until(inst, state=st, tol=0.0, max_passes=7,
                              check_every=3)
    assert list(info2["passes"]) == [7, 7]
    assert np.all(np.isfinite(info2["max_violation"]))
    np.testing.assert_array_equal(np.asarray(st2.x), np.asarray(st.x))


# ------------------------------------------------- device pivot rounding
def test_pivot_round_device_matches_numpy(x64):
    rng = np.random.default_rng(7)
    n = 15
    x = np.triu(rng.uniform(0, 1, (n, n)), 1)
    orders = rounding.pivot_orders(n, seed=5, trials=4)
    for t in range(4):
        lab_np = rounding.pivot_round(x, seed=5 + t)
        lab_dev = np.asarray(
            rounding.pivot_round_device(x, orders[t].astype(np.int32))
        )
        np.testing.assert_array_equal(lab_np, lab_dev)
    # vmapped over trials
    vlab = jax.vmap(lambda o: rounding.pivot_round_device(x, o))(
        orders.astype(np.int32)
    )
    for t in range(4):
        np.testing.assert_array_equal(
            np.asarray(vlab[t]), rounding.pivot_round(x, seed=5 + t)
        )


def test_pivot_round_device_ghosts(x64):
    """Ghosts never pivot, never join a ball, come back labelled -1; the
    real labels equal numpy rounding with the order restricted to real
    nodes."""
    rng = np.random.default_rng(8)
    n, npad = 12, 18
    x = np.triu(rng.uniform(0, 1, (n, n)), 1)
    xp = np.zeros((npad, npad))
    xp[:n, :n] = x
    order = np.random.default_rng(3).permutation(npad).astype(np.int32)
    lab = np.asarray(rounding.pivot_round_device(xp, order, n_real=n))
    assert np.all(lab[n:] == -1)
    lab_np = rounding.pivot_round(x, order=order[order < n])
    np.testing.assert_array_equal(lab[:n], lab_np)


def test_cc_cost_device_matches_numpy(x64):
    rng = np.random.default_rng(9)
    n = 14
    dis = (rng.uniform(size=(n, n)) > 0.5).astype(float)
    w = rng.uniform(0.1, 2.0, (n, n))
    lab = rng.integers(0, 4, n)
    mask = np.triu(np.ones((n, n), bool), 1)
    c_np = rounding.cc_cost(lab, dis, w)
    c_dev = float(rounding.cc_cost_device(lab, dis, w, mask))
    assert abs(c_np - c_dev) < 1e-9


def test_round_device_batch_certificate(x64):
    """Device best-of-trials certificate on a perfectly clustered LP
    point recovers the clusters with ~zero cost."""
    n, npad = 10, 16
    truth = np.array([0] * 5 + [1] * 5)
    x = np.triu(np.where(truth[:, None] == truth[None, :], 0.0, 1.0), 1)
    xp = np.zeros((npad, npad))
    xp[:n, :n] = x
    dis = np.pad(x, ((0, npad - n), (0, npad - n)))
    w = np.ones((npad, npad))
    cert = round_device_batch(xp, dis, w, n, trials=3, seed=0)
    assert cert["cc_cost"] == 0.0 and cert["num_clusters"] == 2
    same = cert["labels"][:, None] == cert["labels"][None, :]
    np.testing.assert_array_equal(same, truth[:, None] == truth[None, :])


# ------------------------------------------- stop rules & residual export
def test_stop_converged_rules():
    import jax.numpy as jnp

    viol = jnp.asarray([1e-5, 0.5])  # slot 0 feasible, slot 1 not
    gap = jnp.asarray([5.0, 1e-9])
    obj = jnp.asarray([100.0, 100.0])
    prev = jnp.asarray([100.0, 100.0])
    tol = 0.05
    # absolute: the raw gap 5.0 fails everywhere; slot 1 is infeasible
    assert list(engine.stop_converged("absolute", tol, viol, gap, obj, prev)) \
        == [False, False]
    # rel_gap: 5.0 <= 0.05*(1+100) passes for the feasible slot only
    assert list(engine.stop_converged("rel_gap", tol, viol, gap, obj, prev)) \
        == [True, False]
    # plateau: unchanged objective passes for the feasible slot only
    assert list(engine.stop_converged("plateau", tol, viol, gap, obj, prev)) \
        == [True, False]
    with pytest.raises(ValueError):
        engine.stop_converged("bogus", 1e-4, viol, gap, obj, prev)


def test_run_until_stop_rules(x64):
    """rel_gap/plateau must stop a solve the absolute pair would keep
    running (the CC duality gap closes far slower than feasibility), and
    bogus rules are rejected up front."""
    p = _cc_problem(12, seed=6)
    base = ParallelSolver(p, dtype=np.float64, bucket_diagonals=2)
    _, ia = base.run_until(tol=1e-3, max_passes=120, check_every=5)
    passes = {}
    for rule in ("rel_gap", "plateau"):
        solver = ParallelSolver(p, dtype=np.float64, bucket_diagonals=2)
        _, info = solver.run_until(tol=1e-3, max_passes=120, check_every=5,
                                   stop_rule=rule)
        assert info["stop_rule"] == rule
        assert info["converged"]
        assert info["max_violation"] < 1e-3
        passes[rule] = info["passes"]
        assert info["passes"] <= ia["passes"]
    with pytest.raises(ValueError):
        base.run_until(stop_rule="bogus")


def test_run_until_residual_trajectory(x64):
    """info['residuals'] must be exactly the chunk-boundary ||Δx||_inf
    values of the solve, ring-buffered to the most recent
    residual_history chunks, and mirrored to solver.last_residuals."""
    p = _l2_problem(12, seed=3)
    solver = ParallelSolver(p, dtype=np.float64, bucket_diagonals=2)
    st, info = solver.run_until(tol=0.0, max_passes=12, check_every=3)
    res = info["residuals"]
    assert res.shape == (4,) and np.all(np.isfinite(res)) and np.all(res > 0)
    assert solver.last_residuals is res
    # oracle: recompute the chunk boundary states with the plain runner
    ref = ParallelSolver(p, dtype=np.float64, bucket_diagonals=2)
    s = ref.init_state()
    expect = []
    for _ in range(4):
        s2 = ref.run(s, passes=3)
        expect.append(float(np.max(np.abs(np.asarray(s2.x) - np.asarray(s.x)))))
        s = s2
    np.testing.assert_allclose(res, expect, rtol=0, atol=1e-14)
    # ring wrap: only the last 2 chunks survive with residual_history=2
    solver2 = ParallelSolver(p, dtype=np.float64, bucket_diagonals=2)
    _, info2 = solver2.run_until(tol=0.0, max_passes=12, check_every=3,
                                 residual_history=2)
    np.testing.assert_allclose(info2["residuals"], expect[-2:], atol=1e-14)


# ------------------------------------------- ghost-aware dual statistics
def test_padded_dual_stats_match_legacy_oracle(x64):
    """device_metrics(include_duals=True) on a ghost-padded solver (a
    PR-4 NotImplementedError) must reduce exactly the REAL (< n_real)
    duals: the ghost-aware valid masks drop ghost-set cells, whose
    values are don't-care under fused execution. Oracle: the legacy
    (fused=False) twin restores masked outputs, so its dense conversion
    is clean and the host stats over it are the truth."""
    from repro.core import convergence

    n_real, bucket_n = 11, 14
    pp = bk.pad_problem(_cc_problem(n_real, seed=4), bucket_n)
    fused = ParallelSolver(pp, dtype=np.float64, bucket_diagonals=3,
                           n_real=n_real)
    # pass by pass, as the legacy twin runs: duals of order 1e-31 (active
    # or not) depend on how XLA fuses a multi-pass loop
    st = fused.init_state()
    for _ in range(5):
        st = fused.run(st, passes=1)
    dev = fused.device_metrics(st, include_duals=True)
    legacy = ParallelSolver(pp, dtype=np.float64, bucket_diagonals=3,
                            n_real=n_real, fused=False)
    stl = legacy.run(passes=5)
    oracle = convergence.triangle_dual_stats(legacy.duals_to_dense(stl))
    for k in ("dual_min", "dual_max", "dual_l1", "active_constraints"):
        assert abs(dev[k] - oracle[k]) <= 1e-10 + 1e-10 * abs(oracle[k]), k
    # the host oracle still has no ghost support and must keep raising
    with pytest.raises(NotImplementedError):
        fused.metrics(st, include_duals=True)


def test_ghost_aware_slab_valid_masks_count(x64):
    """Ghost-aware masks mark exactly 3·C(n_real, 3) cells — one per
    real triangle dual — for any padding amount."""
    for n, nr, nb in ((14, 11, 3), (16, 16, 2), (12, 0, 2)):
        lay = sched.build_layout(n, num_buckets=nb, procs=1)
        masks = sched.slab_valid_masks(lay, n_real=nr)
        assert sum(int(m.sum()) for m in masks) == 3 * sched.n_triplets(nr)


def test_batched_dual_stats_match_dense_oracle(x64):
    """Per-instance batched dual stats (ghost-aware traced masks) must
    equal host stats over each instance's own duals converted densely and
    restricted to the real [:n_real]^3 cube (ghost-set cells land outside
    it by the largest-index argument)."""
    from repro.core import convergence

    probs = [_cc_problem(14, seed=0), _cc_problem(10, seed=1)]
    fam = bk.family_of(probs[0], np.float64)
    bs = BatchedSolver(14, batch=3, family=fam, num_buckets=3)
    inst = bs.stack(probs)  # slot 2 empty
    st, _ = bs.run_until(inst, tol=1e-4, max_passes=40, check_every=5)
    stats = bs.dual_stats(st, inst)
    for i, p in enumerate(probs + [None]):
        nr = 0 if p is None else p.n
        yd_i = [np.asarray(y[i]) for y in st.yd]
        dense = sched.duals_to_dense(bs.layout, yd_i)[:nr, :nr, :nr]
        oracle = convergence.triangle_dual_stats(dense)
        for k in ("dual_min", "dual_max", "dual_l1", "active_constraints"):
            got, want = float(stats[k][i]), float(oracle[k])
            assert abs(got - want) <= 1e-10 + 1e-10 * abs(want), (k, i)
    # the empty slot reduces over nothing: zero-folded stats
    assert stats["active_constraints"][2] == 0 and stats["dual_l1"][2] == 0


# ------------------------------------------- batched residual trajectories
def test_batched_residuals_match_solo(x64):
    """info['residuals'] row i must be exactly the chunk-boundary
    ||Δx||_inf trajectory solo run_until exports for instance i — a
    slot's cursor freezes with it, later cells stay -1."""
    probs = [_cc_problem(14, seed=0), _cc_problem(10, seed=1)]
    fam = bk.family_of(probs[0], np.float64)
    bs = BatchedSolver(14, batch=3, family=fam, num_buckets=3)
    inst = bs.stack(probs)
    kw = dict(tol=1e-4, max_passes=60, check_every=5)
    _, info = bs.run_until(inst, **kw)
    res = info["residuals"]
    assert res.shape == (3, 16)
    assert bs.last_residuals is res
    for i, p in enumerate(probs):
        solo = ParallelSolver(bk.pad_problem(p, 14), dtype=np.float64,
                              bucket_diagonals=3, n_real=p.n)
        _, sinfo = solo.run_until(**kw)
        sres = sinfo["residuals"]
        k = len(sres)
        np.testing.assert_allclose(res[i][:k], sres, rtol=0, atol=1e-14)
        assert np.all(res[i][k:] == -1.0)
    # ring wrap: only the most recent R chunks survive, oldest first
    bs2 = BatchedSolver(14, batch=3, family=fam, num_buckets=3)
    _, info2 = bs2.run_until(inst, tol=0.0, max_passes=20, check_every=5,
                             residual_history=2)
    solo = ParallelSolver(bk.pad_problem(probs[0], 14), dtype=np.float64,
                          bucket_diagonals=3, n_real=probs[0].n)
    _, sinfo2 = solo.run_until(tol=0.0, max_passes=20, check_every=5,
                               residual_history=2)
    np.testing.assert_allclose(
        info2["residuals"][0], sinfo2["residuals"], rtol=0, atol=1e-14
    )


# ------------------------------------------------ big-instance routing
def test_scheduler_routes_big_instance_sharded(x64):
    """An above-ladder instance must bypass the queue and solve NOW on a
    dedicated ShardedSolver.run_until slot at native n, with the result
    matching a direct sharded solve exactly and the stats counting it."""
    from repro.core.sharded_dykstra import ShardedSolver
    from repro.launch import mesh as mesh_lib

    kw = dict(tol=1e-3, max_passes=8, check_every=4)
    sch = BatchScheduler(ladder=(12,), batch=2, dtype=np.float64, **kw)
    big = _cc_problem(16, seed=7)
    sch.submit(big, tag="big")
    assert sch.pending == 0  # never queued
    r = sch.results()["big"]
    assert r["route"] == "sharded"
    assert r["bucket_n"] == 16 and r["n"] == 16
    assert r["x"].shape == (16, 16) and r["x_pad"] is r["x"]
    direct = ShardedSolver(big, mesh_lib.make_solver_mesh(),
                           dtype=np.float64, num_buckets=6)
    st, info = direct.run_until(**kw)
    np.testing.assert_array_equal(r["x"], np.asarray(st.x))
    assert r["passes"] == info["passes"]
    assert r["converged"] == info["converged"]
    assert abs(r["max_violation"] - info["max_violation"]) < 1e-12
    stats = sch.stats()
    assert stats["sharded_done"] == 1
    assert stats["instances_done"] == 1
    assert stats["occupancy"] == 0.0  # no batch slots consumed
    # ladder traffic still batches normally alongside
    sch.submit(_cc_problem(10, seed=1), tag="small")
    sch.drain()
    assert sch.stats()["sharded_done"] == 1
    assert sch.results()["small"]["route"] == "batch"


def test_pipeline_big_instance_end_to_end(x64):
    """Mixed ladder + above-ladder stream through cluster_graphs: the big
    graph routes sharded, gets the same certificate plumbing, and the
    label contract holds on both routes."""
    adjs = generators.graph_batch([10, 18], kind="sbm", seed=3)
    results, stats = cluster_graphs(
        adjs, ladder=(12,), batch=1, tol=1e-3, max_passes=40,
        check_every=10, trials=3, dtype=np.float64,
    )
    routes = {r["route"] for r in results}
    assert routes == {"batch", "sharded"}
    for r in results:
        labs = np.unique(r["labels"])
        np.testing.assert_array_equal(labs, np.arange(len(labs)))
        assert r["cc_cost"] >= r["lp_lower_bound"] - 1e-9
        assert r["labels"].shape == (r["n"],)
    big = next(r for r in results if r["route"] == "sharded")
    assert big["bucket_n"] == big["n"] == 18
    assert stats["sharded_done"] == 1


# ------------------------------------------------------ prewarm compiles
def test_scheduler_prewarm_warm_cold_stats(x64):
    """warmup(family) pre-compiles every ladder rung: the first real
    batch of a prewarmed slot dispatches warm; an unwarmed family is
    cold once, warm after."""
    fam = bk.family_of(_cc_problem(8), np.float64)
    sch = BatchScheduler(ladder=(10, 12), batch=2, dtype=np.float64,
                         tol=1e-3, max_passes=6, check_every=3)
    timings = sch.warmup(fam)
    assert set(timings) == {10, 12} and all(t >= 0 for t in timings.values())
    s0 = sch.stats()["prewarm"]
    assert s0 == {"buckets": 2, "warm_dispatches": 0, "cold_dispatches": 0}
    sch.submit(_cc_problem(9, seed=0), tag="a")
    sch.submit(_cc_problem(10, seed=1), tag="b")  # fills bucket 10
    s1 = sch.stats()["prewarm"]
    assert s1["warm_dispatches"] == 1 and s1["cold_dispatches"] == 0
    # different family (l2, no f) was never warmed -> cold, then warm
    sch.submit(_l2_problem(9, seed=2), tag="c")
    sch.submit(_l2_problem(9, seed=3), tag="d")
    s2 = sch.stats()["prewarm"]
    assert s2["cold_dispatches"] == 1
    sch.submit(_l2_problem(9, seed=4), tag="e")
    sch.submit(_l2_problem(9, seed=5), tag="f")
    s3 = sch.stats()["prewarm"]
    assert s3["warm_dispatches"] == 2 and s3["cold_dispatches"] == 1
    assert set(sch.results()) == {"a", "b", "c", "d", "e", "f"}


# ------------------------------------------------------------- scheduler
def test_scheduler_batches_and_stats(x64):
    clock = [0.0]
    sch = BatchScheduler(
        ladder=(12, 16), batch=2, deadline_s=1.0, dtype=np.float64,
        clock=lambda: clock[0], tol=1e-3, max_passes=6, check_every=3,
    )
    sch.cache.num_buckets = 2
    # two n<=12 requests -> full bucket-12 batch dispatches on submit
    sch.submit(_cc_problem(10, seed=0), tag="a")
    assert sch.pending == 1
    sch.submit(_cc_problem(12, seed=1), tag="b")
    assert sch.pending == 0 and set(sch.results()) == {"a", "b"}
    # a lone n=14 request waits for the deadline
    sch.submit(_cc_problem(14, seed=2), tag="c")
    sch.poll()
    assert sch.pending == 1  # not old enough
    clock[0] = 2.0
    sch.poll()
    assert sch.pending == 0 and "c" in sch.results()
    # same bucket again -> compile-cache hit
    sch.submit(_cc_problem(9, seed=3), tag="d")
    sch.submit(_cc_problem(11, seed=4), tag="e")
    stats = sch.stats()
    assert stats["instances_done"] == 5
    assert stats["batches_run"] == 3
    assert stats["occupancy"] == pytest.approx(5 / 6)
    assert stats["compile_cache"]["misses"] == 2  # bucket 12 and 16
    assert stats["compile_cache"]["hits"] == 1
    r = sch.results()["a"]
    assert r["x"].shape == (10, 10) and r["bucket_n"] == 12
    assert r["passes"] <= 6


def test_scheduler_result_matches_solo(x64):
    """A scheduler round trip returns exactly the standalone padded
    run_until solve of each request."""
    p = _cc_problem(9, seed=5)
    sch = BatchScheduler(ladder=(12,), batch=2, dtype=np.float64,
                         tol=1e-4, max_passes=40, check_every=5)
    sch.submit(p, tag="only")
    out = sch.drain()["only"]
    solo = ParallelSolver(bk.pad_problem(p, 12), dtype=np.float64,
                          bucket_diagonals=6, n_real=p.n)
    sst, sinfo = solo.run_until(tol=1e-4, max_passes=40, check_every=5)
    assert out["passes"] == sinfo["passes"]
    assert np.abs(out["x"] - np.asarray(sst.x)[:9, :9]).max() <= 1e-10


# -------------------------------------------------------------- pipeline
def test_pipeline_end_to_end(x64):
    """B=3 mixed-n batch of planted-partition graphs through the full
    pipeline: valid contiguous labels, sane certificates, occupancy 1."""
    adjs = generators.graph_batch([10, 12, 14], kind="sbm", seed=1)
    results, stats = cluster_graphs(
        adjs, ladder=(16,), batch=3, tol=1e-3, max_passes=80,
        check_every=10, trials=4, dtype=np.float64,
    )
    assert len(results) == 3
    for r, adj in zip(results, adjs):
        n = adj.shape[0]
        assert r["n"] == n and r["bucket_n"] == 16
        assert r["labels"].shape == (n,)
        labs = np.unique(r["labels"])
        np.testing.assert_array_equal(labs, np.arange(len(labs)))
        assert r["num_clusters"] == len(labs)
        assert r["cc_cost"] >= 0
        # LP objective is a lower bound on the rounded cost
        assert r["cc_cost"] >= r["lp_lower_bound"] - 1e-9
    assert stats["instances_done"] == 3
    assert stats["batches_run"] == 1
    assert stats["occupancy"] == pytest.approx(1.0)
