"""Fused-pass execution (DESIGN.md §4): oracle parity for the fused jnp
reference and the whole-bucket Pallas megakernel (interpret mode), static
staging consistency with ``folded_geometry`` bit-for-bit, and the jitted
multi-pass runner's contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 containers lack hypothesis; @given tests skip
    from conftest import given, settings, st

from repro.core import dykstra, problems, schedule as sched
from repro.core.parallel_dykstra import ParallelSolver, folded_geometry

PASSES = 3


@pytest.fixture()
def x64():
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


def _l2_problem(n, seed=0):
    rng = np.random.default_rng(seed)
    return problems.metric_nearness_l2(np.triu(rng.uniform(0, 1, (n, n)), k=1))


# ------------------------------------------------- fused pass vs the oracle
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["fused-ref", "fused-megakernel"])
@pytest.mark.parametrize("buckets", [1, 4])
def test_fused_pass_matches_serial_oracle(x64, use_kernel, buckets):
    """>= 3 fused passes in float64 track the serial oracle to 1e-5 — the
    fused staging/megakernel reorganizes execution, never the math."""
    n = 14
    p = _l2_problem(n, seed=3)
    st_ser = dykstra.solve_serial(p, max_passes=PASSES, order="schedule")
    solver = ParallelSolver(
        p, dtype=np.float64, use_kernel=use_kernel, bucket_diagonals=buckets
    )
    assert solver.fused
    st = solver.run(passes=PASSES)
    np.testing.assert_allclose(np.asarray(st.x), st_ser.x, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        solver.duals_to_dense(st), st_ser.ytri, atol=1e-5, rtol=1e-5
    )


def test_fused_pass_matches_oracle_cc_lp(x64):
    """Pair-constraint family through the fused multi-pass runner."""
    n = 11
    rng = np.random.default_rng(5)
    dis = np.triu((rng.uniform(0, 1, (n, n)) > 0.5).astype(float), k=1)
    p = problems.correlation_clustering_lp(dis, eps=0.05)
    st_ser = dykstra.solve_serial(p, max_passes=PASSES, order="schedule")
    solver = ParallelSolver(p, dtype=np.float64, bucket_diagonals=3)
    st = solver.run(passes=PASSES)
    np.testing.assert_allclose(np.asarray(st.x), st_ser.x, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(st.f), st_ser.f, atol=1e-5, rtol=1e-5)


BAND_CASES = [(n, nb) for n in (5, 14, 23, 40) for nb in (1, 3, 6)]


def _assert_band_matches_index(p, dtype, buckets, index_engine=False):
    """Bucket by bucket, on the second of two passes (non-zero duals),
    the band engine (``ops.fused_bucket_pass``) leaves X bitwise as the
    jnp reference's element gathers and scatters do (and, with
    ``index_engine``, as the index engine of the tiled vector path), and
    every live dual cell as the reference does. The cases cover odd and
    even set counts, padded lanes, single-set diagonals and both
    families' first and last diagonals."""
    from repro.kernels.metric_project import ops
    from repro.kernels.metric_project.ref import fused_bucket_pass_ref

    solver = ParallelSolver(p, dtype=dtype, bucket_diagonals=buckets)
    ref = jax.jit(fused_bucket_pass_ref)
    st = solver.init_state()
    x, yd = st.x, []
    for b, yb in zip(solver._buckets, st.yd):
        x, yb = ref(x, yb, b)
        yd.append(yb)
    live = sched.slab_valid_masks(solver.layout)
    for b, yb, m in zip(solver._buckets, yd, live):
        rx, ry = ref(x, yb, b)
        kx, ky = ops.fused_bucket_pass(x, yb, b)
        np.testing.assert_array_equal(np.asarray(rx), np.asarray(kx))
        np.testing.assert_array_equal(np.asarray(ry)[m[0]],
                                      np.asarray(ky)[m[0]])
        if index_engine:
            ix, _ = _engine_bucket_pass("vector-tiled", x, yb, b, b["act"])
            np.testing.assert_array_equal(np.asarray(ix), np.asarray(kx))
        x = rx
    assert np.abs(np.asarray(x) - np.asarray(st.x)).max() > 0


@pytest.mark.parametrize("n,buckets", [(16, 2)] + BAND_CASES)
def test_megakernel_matches_fused_ref_bitwise(n, buckets):
    """The megakernel and the jnp reference share fused_step op-for-op, so
    X must agree bitwise in float32 on a non-trivial dual state."""
    p = _l2_problem(n, seed=9)
    _assert_band_matches_index(p, np.float32, buckets,
                               index_engine=(n, buckets) == (16, 2))
    if (n, buckets) != (16, 2):
        return
    # dual slabs agree on every real (non-padding) cell via the dense maps
    a = ParallelSolver(p, bucket_diagonals=2, use_kernel=False).run(passes=3)
    b = ParallelSolver(p, bucket_diagonals=2, use_kernel=True).run(passes=3)
    np.testing.assert_array_equal(
        ParallelSolver(p, bucket_diagonals=2).duals_to_dense(a),
        ParallelSolver(p, bucket_diagonals=2).duals_to_dense(b),
    )


# ------------------------------------------------- gen-3 megakernel (§10)
@pytest.mark.parametrize("n,buckets", [(14, 2)] + BAND_CASES)
def test_megakernel_solo_bitwise_f64(x64, n, buckets):
    """Gen-3 solo path in float64 interpret mode: bitwise-equal X to
    ``ref.fused_bucket_pass_ref`` bucket-for-bucket (the staging engines
    reorganize execution, never the arithmetic)."""
    _assert_band_matches_index(_l2_problem(n, seed=21), np.float64, buckets)


@given(n=st.integers(3, 80), nb=st.integers(1, 8), procs=st.integers(1, 4))
@settings(max_examples=40, deadline=None)
def test_property_band_blocks_stay_inside_the_padding(n, nb, procs):
    """``lax.dynamic_slice`` clamps a start that runs off the array, which
    moves the whole window: every block of every diagonal, at every
    device's lane offset (devices whose lanes on a short diagonal are all
    padding included), must lie inside X padded by ``_band_pads`` sized
    for all the devices' lanes."""
    from repro.kernels.metric_project import fused_pass

    for bl in sched.build_layout(n, num_buckets=nb, procs=procs).buckets:
        T, F = bl.T, bl.lanes
        top, bottom, left, right = fused_pass._band_pads(n, T, procs * F)
        for r in range(bl.num_diagonals):
            for dev in range(procs):
                at, size, _ = fused_pass._band_blocks(
                    int(bl.i[0, r, 0]), int(bl.k[0, r, 0]), T, F, dev * F)
                for k, (row, col) in at.items():
                    rows, cols = size[k]
                    assert -top <= row and row + rows <= n + bottom, (k, r)
                    assert -left <= col and col + cols <= n + right, (k, r)


def _sharded_stage(n, procs, dtype, seed):
    """One bucket of an L2 nearness problem dealt over ``procs`` devices:
    the layout, an upper-triangular X, non-zero duals and the staged
    gains of ``ShardedSolver``, all numpy."""
    p = _l2_problem(n, seed=seed)
    lay = sched.build_layout(n, num_buckets=1, procs=procs)
    bl = lay.buckets[0]
    sb = sched.build_static_stage(lay, p.w, dtype)[0]
    one, eps = dtype(1.0), dtype(p.eps)
    g_row, g_col = (one / sb.w_row) / eps, (one / sb.w_col) / eps
    g_ikp = (one / sb.w_ikp) / eps
    g_sel = np.where(sb.seg, g_ikp[:, :, 1][:, :, None, :],
                     g_ikp[:, :, 0][:, :, None, :]).astype(dtype)
    dinv = (one / (g_row + g_sel + g_col)).astype(dtype)
    rng = np.random.default_rng(seed)
    x = np.triu(rng.uniform(0, 1, (n, n)), 1).astype(dtype)
    y = (rng.uniform(0, 0.2, bl.slab_shape) * sb.active[:, :, None]).astype(
        dtype)
    return bl, sb, (g_row, g_col, g_sel, dinv), x, y


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("procs", [2, 3, 4])
@pytest.mark.parametrize("n", [5, 14, 23, 40])
def test_sharded_band_delta_matches_index_engine(x64, n, procs, dtype):
    """One diagonal of one device in sharded mode: the band engine at the
    device's lane offset, reading X from its padded copy and adding the
    act-masked deltas into a padded zero buffer, leaves in the interior
    bitwise the delta matrix the index engine scatters into zeros, and
    the same live duals; ``clear`` zeros the buffer again. Every diagonal
    and device is checked, among them diagonals whose lane count is not a
    multiple of ``procs`` and devices that hold only padding lanes."""
    from repro.kernels.metric_project import fused_pass

    bl, sb, gains, x, y = _sharded_stage(n, procs, dtype, seed=n + procs)
    T, F = bl.T, bl.lanes
    top, bottom, left, right = fused_pass._band_pads(n, T, procs * F)
    sweep = fused_pass._vector_sweep(4)
    swap = lambda a: jnp.swapaxes(a, 1, 2)
    lanes = np.stack([bl.i, bl.k, bl.sizes, bl.i2, bl.k2, bl.sizes2])
    geom = np.stack([sb.J, sb.iN, sb.kN])

    def step(engine, xv, out, segv, ops):
        yv, *gv, act = (a[None] for a in ops)
        return fused_pass._diag_step(xv, out, engine, segv, yv, *gv, act,
                                     sweep)

    @jax.jit
    def band(lane, first, f0, seg, ops):
        xp = jnp.pad(jnp.asarray(x)[None], ((0, 0), (top, bottom),
                                             (left, right)))
        eng = fused_pass._band_engine(lane, seg, (top, left), swap, first,
                                      f0)
        out, ny = step(eng, xp, jnp.zeros_like(xp), seg, ops)
        return out[:, top:top + n, left:left + n], ny, eng[2](out)

    @jax.jit
    def index(lane, geo, seg, ops):
        eng = fused_pass._index_engine(lane, geo)
        return step(eng, jnp.asarray(x)[None], jnp.zeros((1, n, n), dtype),
                    seg, ops)

    uneven = padding_only = 0
    for r in range(bl.num_diagonals):
        first = np.array([bl.i[0, r, 0], bl.k[0, r, 0]], np.int32)
        lanes_d = int((bl.i[:, r] >= 0).sum())
        uneven += lanes_d % procs != 0
        for dev in range(procs):
            padding_only += not (bl.i[dev, r] >= 0).any()
            seg = sb.seg[dev, r]
            ops = [a[dev, r] for a in (y, *gains, sb.active)]
            db, yb, cleared = band(lanes[:, dev, r], first,
                                   np.int32(dev * F), seg, ops)
            di, yi = index(lanes[:, dev, r], geom[:, dev, r], seg, ops)
            np.testing.assert_array_equal(np.asarray(db), np.asarray(di))
            live = np.broadcast_to(sb.active[dev, r][None, None], yb.shape)
            np.testing.assert_array_equal(np.asarray(yb)[live],
                                          np.asarray(yi)[live])
            assert not np.asarray(cleared).any()
    assert uneven and (padding_only or procs == 2 and n == 5)


def test_bucket_programs_count_their_engine():
    """Each bucket program counts, when traced, the engine it lowered to:
    the band engine for a single-device bucket and for the sharded path
    (a device's contiguous share of each diagonal's lanes, merged over a
    mesh axis), the index engine for the tiled vector engine."""
    from jax.sharding import Mesh, PartitionSpec as P

    from repro import obs
    from repro.kernels.metric_project import fused_pass

    p = _l2_problem(12, seed=4)
    solver = ParallelSolver(p, bucket_diagonals=3)
    st = solver.run(passes=1)
    n_of = lambda: {e: obs.snapshot().get(f"repro.bucket.engine.{e}",
                                          {"n": 0})["n"]
                    for e in ("band", "index")}
    before = n_of()
    for b, yb in zip(solver._buckets, st.yd):
        _engine_bucket_pass("vector", st.x, yb, b, b["act"])
    mid = n_of()
    assert mid == {"band": before["band"] + 3, "index": before["index"]}
    b, d = solver._buckets[0], 0
    take = lambda *keys: jnp.stack([b[k][d] for k in keys])
    first = jnp.stack([b["i"][d:d + 1, 0], b["k"][d:d + 1, 0]], axis=-1)
    mesh = Mesh(np.array(jax.devices()[:1]), ("solver",))
    sharded = jax.shard_map(
        lambda *a: fused_pass.fused_bucket_pass_pallas(
            *a, axis_name="solver"),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    )
    sharded(
        st.x[None], st.yd[0][None, d:d + 1],
        take("i", "k", "s", "i2", "k2", "s2")[:, None],
        *(b[k][None, d:d + 1] for k in ("g_row", "g_col", "g_sel", "dinv",
                                          "act")),
        b["seg"][d:d + 1], take("J", "iN", "kN")[:, None], first,
    )
    _engine_bucket_pass("vector-tiled", st.x, st.yd[0], b, b["act"])
    assert n_of() == {"band": mid["band"] + 1, "index": mid["index"] + 1}


@pytest.mark.parametrize("sizes", [(12, 9, 12, None), (12, 7, None)],
                         ids=["B4", "B3"])
def test_megakernel_batched_mixed_ghost_bitwise(x64, sizes):
    """One (B, ...) megakernel call per bucket — mixed-n slots with
    ghost padding and one all-ghost empty slot — must be bitwise-equal to
    the vmapped jnp fused reference, end-to-end through ``run_until``
    (X, per-instance pass counters, stopping vectors, dual stats)."""
    from repro.serve.batching import BatchedSolver
    from repro.serve.buckets import family_of

    ps = [None if m is None else _l2_problem(m, seed=i + 1)
          for i, m in enumerate(sizes)]
    B = len(ps)
    fam = family_of(ps[0], np.float64)
    ref = BatchedSolver(12, B, fam, num_buckets=3)
    ker = BatchedSolver(12, B, fam, num_buckets=3, use_kernel=True)
    inst = ref.stack(ps)
    sta, ia = ref.run_until(inst, tol=1e-5, max_passes=30, check_every=5)
    stb, ib = ker.run_until(inst, tol=1e-5, max_passes=30, check_every=5)
    np.testing.assert_array_equal(np.asarray(sta.x), np.asarray(stb.x))
    np.testing.assert_array_equal(ia["passes"], ib["passes"])
    np.testing.assert_array_equal(ia["max_violation"], ib["max_violation"])
    assert ib["converged"][B - 1]  # the empty slot converges immediately
    da, db = ref.dual_stats(sta, inst), ker.dual_stats(stb, inst)
    for key in da:
        np.testing.assert_array_equal(da[key], db[key])


def test_megakernel_ghost_cells_fixed_points():
    """Ghost rows/columns of a padded instance are structural fixed
    points of the kernel pass (DESIGN.md §8/§10): the staged act masks
    zero every ghost delta, so ghost cells stay exactly 0.0 and the live
    block matches the jnp fused reference path bitwise — no jnp fallback
    is involved (the probe runs the n_live-masked violation kernel)."""
    from repro.serve.buckets import pad_problem

    n, npad = 10, 14
    p = _l2_problem(n, seed=3)
    pp = pad_problem(p, npad)
    ref = ParallelSolver(pp, bucket_diagonals=2, n_real=n)
    ker = ParallelSolver(pp, bucket_diagonals=2, n_real=n, use_kernel=True)
    sta, ia = ref.run_until(tol=1e-4, max_passes=30, check_every=5)
    stb, ib = ker.run_until(tol=1e-4, max_passes=30, check_every=5)
    xb = np.asarray(stb.x)
    np.testing.assert_array_equal(np.asarray(sta.x), xb)
    assert ia["passes"] == ib["passes"]
    assert ia["max_violation"] == ib["max_violation"]
    ghost = np.zeros((npad, npad), bool)
    ghost[n:, :] = True
    ghost[:, n:] = True
    assert np.all(np.abs(xb[ghost]) == 0.0)


def test_megakernel_compile_counter():
    """Weights-as-operands contract (DESIGN.md §10): new instances and
    new batches reuse the SAME compiled kernel program — the jit cache
    of the megakernel entrypoint must not grow when a second weight set
    (solo) or a second instance batch (batched) runs through it."""
    from repro.kernels.metric_project import ops
    from repro.kernels.metric_project.ref import fused_bucket_pass_ref
    from repro.serve.batching import BatchedSolver
    from repro.serve.buckets import family_of

    counter = getattr(ops._fused_pass_jit, "_cache_size", None)
    if counter is None:
        pytest.skip("jit cache introspection unavailable")

    a = ParallelSolver(_l2_problem(13, seed=1), bucket_diagonals=2,
                       use_kernel=True)
    a.run(passes=2)
    size_solo = counter()
    assert size_solo > 0
    b = ParallelSolver(_l2_problem(13, seed=2), bucket_diagonals=2,
                       use_kernel=True)
    b.run(passes=2)
    assert counter() == size_solo  # second weight set: zero recompiles

    fam = family_of(_l2_problem(10, seed=1), np.float32)
    solver = BatchedSolver(10, 3, fam, num_buckets=2, use_kernel=True)
    inst1 = solver.stack([_l2_problem(10, seed=3), _l2_problem(7, seed=4)])
    solver.run_until(inst1, tol=1e-4, max_passes=10, check_every=5)
    size_batched = counter()
    inst2 = solver.stack([_l2_problem(9, seed=5), _l2_problem(10, seed=6),
                          _l2_problem(8, seed=7)])
    solver.run_until(inst2, tol=1e-4, max_passes=10, check_every=5)
    assert counter() == size_batched  # new batch: zero recompiles


def test_demoted_gen1_fallback_warns():
    """use_kernel=True with fused=False has no kernel path (gen-1 is
    test-oracle-only): asking for it is an error, never a silent jnp
    sweep."""
    p = _l2_problem(10, seed=2)
    with pytest.raises(ValueError, match="test-oracle"):
        ParallelSolver(p, use_kernel=True, fused=False, bucket_diagonals=2)


def test_gen1_oracle_vs_gen3_parity(x64):
    """Gen-1 (``diagonal_sweep_slab``, demoted to test-oracle status) vs
    gen-3 on one diagonal. The generations intentionally differ in float
    association — gen-1 divides by (w, eps) at runtime, gen-3 consumes
    staged gains — so cross-generation agreement is tight-tolerance in
    f64 while each generation stays bitwise-pinned to its own jnp oracle
    (gen-1 in test_kernels.py, gen-3 above)."""
    import jax.numpy as jnp

    from repro.kernels.metric_project import ops

    p = _l2_problem(12, seed=1)
    solver = ParallelSolver(p, dtype=np.float64, bucket_diagonals=2)
    st = solver.run(passes=2)
    b, yb = solver._buckets[0], st.yd[0]
    d = 0
    i1, k1, s1 = b["i"][d], b["k"][d], b["s"][d]
    i2, k2, s2 = b["i2"][d], b["k2"][d], b["s2"][d]
    J, iN, kN = b["J"][d], b["iN"][d], b["kN"][d]
    act, seg = b["act"][d], b["seg"][d]
    x = st.x
    get = lambda a, idx, f: a.at[idx].get(mode="fill", fill_value=f)
    rowb, colb = get(x, (iN, J), 0.0), get(x, (J, kN), 0.0)
    xikp = jnp.stack([get(x, (i1, k1), 0.0), get(x, (i2, k2), 0.0)])
    w = jnp.asarray(p.w, jnp.float64)
    w_row, w_col = get(w, (iN, J), 1.0), get(w, (J, kN), 1.0)
    w_ikp = jnp.stack([get(w, (i1, k1), 1.0), get(w, (i2, k2), 1.0)])
    nr1, nc1, nx1, _ = ops.diagonal_sweep_slab(
        rowb, colb, xikp, yb[d], w_row, w_col, w_ikp, act, seg,
        float(p.eps)
    )
    sc = lambda a, idx, v: a.at[idx].add(v, mode="drop",
                                         unique_indices=True)
    x1 = sc(x, (iN, J), jnp.where(act, nr1 - rowb, 0))
    x1 = sc(x1, (J, kN), jnp.where(act, nc1 - colb, 0))
    x1 = sc(x1, (i1, k1), jnp.where(s1 > 0, nx1[0] - xikp[0], 0))
    x1 = sc(x1, (i2, k2), jnp.where(s2 > 0, nx1[1] - xikp[1], 0))
    one = {key: v[d:d + 1] for key, v in b.items()
           if hasattr(v, "shape") and v.shape[:1] == (yb.shape[0],)}
    nx, _ = ops.fused_bucket_pass(x, yb[d:d + 1], one)
    np.testing.assert_allclose(
        np.asarray(x1), np.asarray(nx), rtol=1e-13, atol=1e-14
    )


def test_legacy_path_matches_oracle(x64):
    """``fused=False`` (the benchmark baseline) still tracks the oracle."""
    n = 12
    p = _l2_problem(n, seed=11)
    st_ser = dykstra.solve_serial(p, max_passes=2, order="schedule")
    solver = ParallelSolver(p, dtype=np.float64, fused=False,
                            bucket_diagonals=2)
    st = solver.run(passes=2)
    np.testing.assert_allclose(np.asarray(st.x), st_ser.x, atol=1e-5, rtol=1e-5)


# --------------------------------------------------- static staging slabs
@given(n=st.integers(5, 22), nb=st.integers(1, 4), procs=st.integers(1, 3))
@settings(max_examples=15, deadline=None)
def test_property_static_stage_matches_folded_geometry(n, nb, procs):
    """build_static_stage's precomputed geometry/mask slabs must agree
    BIT-FOR-BIT with the jnp folded_geometry every solver path shares —
    any drift would silently desynchronize the fused pass from the
    conflict-free schedule."""
    lay = sched.build_layout(n, num_buckets=nb, procs=procs)
    rng = np.random.default_rng(n * 100 + nb * 10 + procs)
    w = np.triu(rng.uniform(0.5, 2.0, (n, n)), k=1)
    w = w + w.T + np.eye(n)
    stage = sched.build_static_stage(lay, w)
    for bl, sb in zip(lay.buckets, stage):
        for dev in range(procs):
            for r in range(bl.slab_shape[1]):
                J, iN, kN, act, seg = folded_geometry(
                    jnp.asarray(bl.i[dev, r]), jnp.asarray(bl.k[dev, r]),
                    jnp.asarray(bl.sizes[dev, r]), jnp.asarray(bl.i2[dev, r]),
                    jnp.asarray(bl.k2[dev, r]), jnp.asarray(bl.sizes2[dev, r]),
                    bl.T,
                )
                np.testing.assert_array_equal(np.asarray(J), sb.J[dev, r])
                np.testing.assert_array_equal(np.asarray(iN), sb.iN[dev, r])
                np.testing.assert_array_equal(np.asarray(kN), sb.kN[dev, r])
                np.testing.assert_array_equal(np.asarray(act),
                                              sb.active[dev, r])
                np.testing.assert_array_equal(np.asarray(seg),
                                              sb.seg[dev, r])


def test_static_stage_weights_active_cells():
    """Active cells of the staged weight slabs equal W at the folded
    indices; masked cells are finite (sanitized to the fill value)."""
    n = 15
    lay = sched.build_layout(n, num_buckets=2, procs=1)
    rng = np.random.default_rng(4)
    w = np.triu(rng.uniform(0.5, 2.0, (n, n)), k=1)
    w = w + w.T + np.eye(n)
    stage = sched.build_static_stage(lay, w)
    for sb in stage:
        act = sb.active
        np.testing.assert_array_equal(
            sb.w_row[act], w[sb.iN[act], sb.J[act]].astype(np.float32)
        )
        np.testing.assert_array_equal(
            sb.w_col[act], w[sb.J[act], sb.kN[act]].astype(np.float32)
        )
        assert np.isfinite(sb.w_row).all() and (sb.w_row > 0).all()
        assert np.isfinite(sb.w_col).all() and (sb.w_col > 0).all()
        assert np.isfinite(sb.w_ikp).all() and (sb.w_ikp > 0).all()


def test_static_stage_preserves_zero_weights_on_active_cells():
    """Sanitization must touch MASKED cells only: a user-supplied zero
    weight on a real pair reaches the staged slabs verbatim (the serial
    oracle's 1/w = inf semantics), never silently replaced by the fill."""
    n = 9
    lay = sched.build_layout(n, num_buckets=1, procs=1)
    w = np.ones((n, n))
    w[2, 5] = w[5, 2] = 0.0
    stage = sched.build_static_stage(lay, w)
    hits = 0
    for sb in stage:
        act = sb.active
        zero_row = act & (sb.iN == 2) & (sb.J == 5)
        zero_col = act & (sb.J == 2) & (sb.kN == 5)
        hits += int(zero_row.sum()) + int(zero_col.sum())
        assert (sb.w_row[zero_row] == 0).all()
        assert (sb.w_col[zero_col] == 0).all()
    assert hits > 0  # the pair really is visited by the schedule


# ------------------------------------------------------ multi-pass runner
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["fused-ref", "fused-megakernel"])
def test_multi_pass_runner_equals_repeated_single_pass(use_kernel):
    """One scan over P passes must produce exactly the same state as P
    single-pass runs (the scan only removes dispatch, never reorders);
    through the band engine it leaves X as the jnp reference does."""
    n = 13
    p = _l2_problem(n, seed=6)
    solver = ParallelSolver(p, bucket_diagonals=3, use_kernel=use_kernel)
    st_scan = solver.run(passes=4)
    if use_kernel:
        st_ref = ParallelSolver(p, bucket_diagonals=3).run(passes=4)
        np.testing.assert_array_equal(np.asarray(st_scan.x),
                                      np.asarray(st_ref.x))
    st_loop = solver.init_state()
    for _ in range(4):
        st_loop = solver.run(st_loop, passes=1)
    np.testing.assert_array_equal(np.asarray(st_scan.x), np.asarray(st_loop.x))
    for a, b in zip(st_scan.yd, st_loop.yd):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(st_scan.passes) == 4


def test_runner_probe_trajectory():
    """The periodic probe reports a per-pass ||Δx||_inf trajectory: finite,
    non-negative, and shrinking as Dykstra converges; probe_every gates
    which passes are measured (-1 elsewhere)."""
    n = 12
    p = _l2_problem(n, seed=8)
    solver = ParallelSolver(p, bucket_diagonals=2)
    solver.run(passes=6)
    res = np.asarray(solver.last_residuals)
    assert res.shape == (6,)
    assert (res >= 0).all()
    assert res[5] < res[0]

    sparse = ParallelSolver(p, bucket_diagonals=2, probe_every=3)
    sparse.run(passes=6)
    res3 = np.asarray(sparse.last_residuals)
    assert (res3[[0, 1, 3, 4]] == -1).all()
    np.testing.assert_allclose(res3[[2, 5]], res[[2, 5]], rtol=1e-6)


def test_zero_passes_is_identity():
    p = _l2_problem(10, seed=1)
    solver = ParallelSolver(p)
    st = solver.init_state()
    st2 = solver.run(st, passes=0)
    np.testing.assert_array_equal(np.asarray(st2.x), np.asarray(st.x))


# ------------------------------ masked-cell fixed points (DESIGN.md §13)
def _engine_bucket_pass(engine, x, yb, stage, am):
    """One bucket pass with a DYNAMIC act mask through one engine. The
    mask is a runtime operand on every path — exactly how SparseSolver
    threads its active masks."""
    from repro.kernels.metric_project import fused_pass
    from repro.kernels.metric_project import ref as kref

    if engine == "ref":
        return kref.fused_bucket_pass_ref(x, yb, dict(stage) | {"act": am})
    lanes = jnp.stack(
        [stage[k] for k in ("i", "k", "s", "i2", "k2", "s2")]
    )
    geom = jnp.stack([stage["J"], stage["iN"], stage["kN"]])
    one = lambda a: a[None]
    nx, ny = fused_pass.fused_bucket_pass_pallas(
        x[None], yb[None], lanes, one(stage["g_row"]),
        one(stage["g_col"]), one(stage["g_sel"]), one(stage["dinv"]),
        one(am), stage["seg"], geom,
        block_c=2 if engine == "vector-tiled" else 128,
        interpret=True, mode="tpu" if engine == "tpu" else "vector",
    )
    return nx[0], ny[0]


@pytest.mark.parametrize(
    "engine", ["vector", "vector-tiled", "tpu"]
)
def test_property_masked_cells_are_fixed_points(engine):
    """Ghost cells AND dynamically forgotten cells are structural fixed
    points of the fused pass, on every engine (extends the ghost parity
    test above to Project-and-Forget's runtime masks, DESIGN.md §13):

      * masked cells contribute ZERO delta to X — garbage duals parked
        on masked cells (ghost, padding, or forgotten) must not change
        the X output by a single bit;
      * the engine agrees bitwise with the jnp reference under the same
        dynamic mask;
      * ghost rows/columns of the padded iterate stay exactly 0.0.
    """
    from repro.serve.buckets import pad_problem

    n, npad = 10, 13
    p = pad_problem(_l2_problem(n, seed=7), npad)
    solver = ParallelSolver(p, bucket_diagonals=2, n_real=n)
    st = solver.run(passes=2)  # non-zero duals, non-trivial iterate
    rng = np.random.default_rng(42)
    x_ref = x_eng = st.x
    for b, yb in zip(solver._buckets, st.yd):
        act = np.asarray(b["act"])
        # dynamic mask: forget ~40% of the (ghost-masked) active cells
        am = jnp.asarray(act & (rng.random(act.shape) < 0.6))
        y_clean = jnp.where(am[:, None], yb, 0.0)
        y_dirty = jnp.where(am[:, None], yb, 777.0)  # masked-cell garbage
        rx, ry = _engine_bucket_pass("ref", x_ref, y_clean, b, am)
        rx_d, _ = _engine_bucket_pass("ref", x_ref, y_dirty, b, am)
        np.testing.assert_array_equal(np.asarray(rx), np.asarray(rx_d))
        ex, ey = _engine_bucket_pass(engine, x_eng, y_clean, b, am)
        ex_d, _ = _engine_bucket_pass(engine, x_eng, y_dirty, b, am)
        np.testing.assert_array_equal(np.asarray(ex), np.asarray(ex_d))
        np.testing.assert_array_equal(np.asarray(rx), np.asarray(ex))
        # active dual cells agree across engines (masked are don't-care)
        amn = np.asarray(am)
        np.testing.assert_array_equal(
            np.asarray(ry)[amn[:, None] & np.ones((1, 3, 1, 1), bool)],
            np.asarray(ey)[amn[:, None] & np.ones((1, 3, 1, 1), bool)],
        )
        x_ref, x_eng = rx, ex
    ghost = np.zeros((npad, npad), bool)
    ghost[n:, :] = True
    ghost[:, n:] = True
    assert np.all(np.asarray(x_eng)[ghost] == 0.0)
