"""Device-resident convergence engine (DESIGN.md §7): float64 property
tests that the device metrics match the host numpy oracle
(core/convergence.py) to 1e-10 — with/without f, with/without box, jnp and
interpret-kernel probes, single-device and sharded — that ``run_until``
stops at exactly the pass the host-driven chunk loop would, and that the
direct slab→slab re-shard permutation equals the dense round-trip oracle."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 containers lack hypothesis; @given tests skip
    from conftest import given, settings, st

from repro.core import convergence, problems, schedule as sched
from repro.core.parallel_dykstra import ParallelSolver
from repro.core.sharded_dykstra import ShardedSolver
from repro.launch import elastic

TOL = 1e-10


@pytest.fixture()
def x64():
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


def _problem(n, seed=0, kind="l2"):
    rng = np.random.default_rng(seed)
    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    if kind == "l2":
        return problems.metric_nearness_l2(d)
    if kind == "l1":  # f, no box
        return problems.metric_nearness_l1(d, eps=0.05)
    return problems.correlation_clustering_lp((d > 0.5).astype(float), eps=0.05)


def _assert_reports_match(host: dict, dev: dict, tol=TOL):
    assert set(host) == set(dev)
    for k in host:
        assert abs(host[k] - dev[k]) <= tol + tol * abs(host[k]), (
            k, host[k], dev[k],
        )


# ----------------------------------------------- device metrics vs oracle
@pytest.mark.parametrize("kind", ["l2", "l1", "cc"])
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp-probe", "pallas-probe"])
def test_device_metrics_match_host_oracle(x64, kind, use_kernel):
    """Every scalar of the device report — objectives, duality gap, max
    violation, slab-native dual stats — must match convergence.report
    (fed by duals_to_dense) to 1e-10 in float64."""
    solver = ParallelSolver(
        _problem(14, seed=3, kind=kind), dtype=np.float64,
        use_kernel=use_kernel, bucket_diagonals=3,
    )
    st_ = solver.run(passes=3)
    _assert_reports_match(
        solver.metrics(st_, include_duals=True),
        solver.device_metrics(st_, include_duals=True),
    )


@pytest.mark.parametrize("kind", ["l2", "l1", "cc"])
def test_device_metrics_match_host_oracle_sharded(x64, kind):
    mesh = Mesh(np.array(jax.devices()[:1]), ("solver",))
    solver = ShardedSolver(
        _problem(12, seed=5, kind=kind), mesh, dtype=np.float64, num_buckets=2
    )
    st_ = solver.run(passes=3)
    _assert_reports_match(
        solver.metrics(st_, include_duals=True),
        solver.device_metrics(st_, include_duals=True),
    )


@given(n=st.integers(5, 18), seed=st.integers(0, 50))
@settings(max_examples=10, deadline=None)
def test_property_device_metrics_match_oracle(n, seed):
    """Random instances, random pass counts: device == host to 1e-10."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        kind = ["l2", "l1", "cc"][seed % 3]
        solver = ParallelSolver(
            _problem(n, seed=seed, kind=kind), dtype=np.float64,
            bucket_diagonals=1 + seed % 3,
        )
        st_ = solver.run(passes=1 + seed % 4)
        _assert_reports_match(
            solver.metrics(st_, include_duals=True),
            solver.device_metrics(st_, include_duals=True),
        )
    finally:
        jax.config.update("jax_enable_x64", old)


def test_device_metrics_fresh_state(x64):
    """Zero-pass state: duals all zero, violation from x0 alone — exercises
    the stats' empty/zero edge (min/max fold a 0 in like the dense form)."""
    solver = ParallelSolver(_problem(10, seed=1), dtype=np.float64)
    st_ = solver.init_state()
    _assert_reports_match(
        solver.metrics(st_, include_duals=True),
        solver.device_metrics(st_, include_duals=True),
    )


# ------------------------------------------------------------- run_until
def _host_loop(solver, tol, max_passes, chunk):
    """The PR-2 host-driven reference loop: run a chunk, report on host,
    stop on the stopping pair."""
    st_ = solver.init_state()
    done = 0
    while done < max_passes:
        k = min(chunk, max_passes - done)
        st_ = solver.run(st_, passes=k)
        done += k
        m = solver.metrics(st_)
        if m["max_violation"] < tol and abs(m["duality_gap"]) < tol:
            break
    return st_, done


@pytest.mark.parametrize("chunk", [3, 4])
def test_run_until_stops_at_host_loop_pass(x64, chunk):
    """The fused while_loop must stop at exactly the chunk boundary the
    host-driven loop stops at, with the identical iterate."""
    solver = ParallelSolver(_problem(16, seed=0), dtype=np.float64)
    tol = 1e-3
    st_host, done = _host_loop(solver, tol, 60, chunk)
    st_dev, info = solver.run_until(tol=tol, max_passes=60, check_every=chunk)
    assert info["passes"] == done
    assert info["converged"]
    assert 0 < done < 60
    np.testing.assert_array_equal(np.asarray(st_dev.x), np.asarray(st_host.x))


def test_run_until_respects_max_passes_and_remainder(x64):
    """tol=0 never converges: the runner must stop at exactly max_passes,
    including a final partial chunk (host semantics k=min(chunk, rem))."""
    solver = ParallelSolver(_problem(10, seed=2), dtype=np.float64)
    st_, info = solver.run_until(tol=0.0, max_passes=7, check_every=3)
    assert info["passes"] == 7 and not info["converged"]
    # the guarded partial chunk must be bit-identical to 7 plain passes
    np.testing.assert_array_equal(
        np.asarray(st_.x), np.asarray(solver.run(passes=7).x)
    )
    # cumulative semantics: resuming with the same target is a no-op but
    # still reports a real stopping pair.
    st2, info2 = solver.run_until(st_, tol=0.0, max_passes=7, check_every=3)
    assert info2["passes"] == 7
    assert np.isfinite(info2["max_violation"])
    np.testing.assert_array_equal(np.asarray(st2.x), np.asarray(st_.x))
    # and the stopping pair equals the host oracle's
    m = solver.metrics(st_)
    assert abs(info["max_violation"] - m["max_violation"]) < TOL
    assert abs(info["duality_gap"] - m["duality_gap"]) < TOL


def test_run_until_sharded(x64):
    mesh = Mesh(np.array(jax.devices()[:1]), ("solver",))
    solver = ShardedSolver(_problem(12, seed=4), mesh, dtype=np.float64,
                           num_buckets=2)
    tol = 1e-3
    st_host, done = _host_loop(solver, tol, 40, 5)
    st_dev, info = solver.run_until(tol=tol, max_passes=40, check_every=5)
    assert info["passes"] == done and info["converged"]
    np.testing.assert_allclose(
        np.asarray(st_dev.x), np.asarray(st_host.x), rtol=1e-12, atol=1e-12
    )


_SHARDED8_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import problems
    from repro.core.sharded_dykstra import ShardedSolver

    rng = np.random.default_rng(7)
    n = 14
    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    p = problems.metric_nearness_l2(d)
    mesh = Mesh(np.array(jax.devices()), ("solver",))
    solver = ShardedSolver(p, mesh, dtype=np.float64, num_buckets=3)
    st, info = solver.run_until(tol=1e-3, max_passes=40, check_every=5)
    assert info["converged"], info
    host = solver.metrics(st, include_duals=True)
    dev = solver.device_metrics(st, include_duals=True)
    for k in host:
        assert abs(host[k] - dev[k]) <= 1e-10 + 1e-10 * abs(host[k]), (
            k, host[k], dev[k])
    print("ENGINE8_OK", info["passes"])
    """
)


@pytest.mark.multidevice
def test_engine_sharded_8_devices_subprocess():
    """True multi-device engine: the psum-max violation probe and the
    while_loop runner on 8 host devices must match the host oracle."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED8_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ENGINE8_OK" in out.stdout


# ------------------------------------------------- direct slab→slab reshard
@pytest.mark.parametrize("p_old,p_new", [(1, 3), (3, 2), (2, 8)])
def test_reshard_direct_matches_dense_oracle(p_old, p_new):
    """The device-side composed slab→slab permutation must reproduce the
    dense (n, n, n) round trip AND the host-float64 permutation
    bit-for-bit — a gather moves values, it never rounds."""
    n, nb = 13, 2
    rng = np.random.default_rng(p_old * 10 + p_new)
    lay = sched.build_layout(n, num_buckets=nb, procs=p_old)
    slabs = [rng.uniform(0, 1, bl.slab_shape).astype(np.float32)
             for bl in lay.buckets]
    # zero the padding cells (real states keep padding at don't-care, but
    # the dense oracle drops it; the permutation only moves real cells)
    for s, m in zip(slabs, sched.slab_valid_masks(lay)):
        s[~m] = 0.0
    a, la = elastic.reshard_duals(slabs, n, p_old, p_new, nb)
    b, lb = elastic.reshard_duals_dense(slabs, n, p_old, p_new, nb)
    c, _ = elastic.reshard_duals_host(slabs, n, p_old, p_new, nb)
    assert [x.shape for x in a] == [x.shape for x in b]
    for sa, sb, sc in zip(a, b, c):
        assert isinstance(sa, jax.Array)  # the device path stays on device
        np.testing.assert_array_equal(np.asarray(sa), sb)
        np.testing.assert_array_equal(sb, sc)
    assert la.procs == lb.procs == p_new


def test_reshard_device_padding_garbage_dropped():
    """Fused-execution states carry don't-care padding values; the
    device permutation must mask them out, never copy them."""
    n, nb = 11, 2
    lay = sched.build_layout(n, num_buckets=nb, procs=2)
    rng = np.random.default_rng(0)
    slabs = [rng.uniform(0, 1, bl.slab_shape).astype(np.float32)
             for bl in lay.buckets]  # padding cells hold garbage
    a, la = elastic.reshard_duals(slabs, n, 2, 3, nb)
    clean = [np.array(s) for s in slabs]
    for s, m in zip(clean, sched.slab_valid_masks(lay)):
        s[~m] = 0.0
    b, _ = elastic.reshard_duals_host(clean, n, 2, 3, nb)
    for sa, sb, m in zip(a, b, sched.slab_valid_masks(la)):
        np.testing.assert_array_equal(np.asarray(sa), sb)
        assert np.all(np.asarray(sa)[~m] == 0.0)


def test_reshard_device_mesh_placement():
    """With a target mesh the new slabs come back committed + sharded on
    the solver axis (slabs never round-trip through the host)."""
    from jax.sharding import NamedSharding

    n, nb = 10, 2
    lay = sched.build_layout(n, num_buckets=nb, procs=2)
    rng = np.random.default_rng(1)
    slabs = [rng.uniform(0, 1, bl.slab_shape).astype(np.float32)
             for bl in lay.buckets]
    mesh = Mesh(np.array(jax.devices()[:1]), ("solver",))
    a, _ = elastic.reshard_duals(slabs, n, 2, 1, nb, mesh=mesh)
    b, _ = elastic.reshard_duals_host(slabs, n, 2, 1, nb)
    for sa, sb in zip(a, b):
        assert isinstance(sa.sharding, NamedSharding)
        assert sa.sharding.mesh.axis_names == ("solver",)
        np.testing.assert_array_equal(np.asarray(sa), sb)


# --------------------------------------------- 2-D-grid violation kernel
@pytest.mark.parametrize(
    "n,block,block_r",
    [(40, 8, 16), (97, 4, 32), (9, 8, 128), (50, 16, 8)],
)
def test_violation_kernel_2d_grid_matches_jnp(n, block, block_r):
    """The 2-D grid (apex × row blocks) must reduce to the exact jnp
    oracle value at sizes needing MULTIPLE row blocks per apex block —
    the regime where the old whole-matrix kernel would have required a
    resident (npad, npad) block."""
    import jax.numpy as jnp

    from repro.core import metrics_device
    from repro.kernels.metric_project.violation import (
        max_triangle_violation_pallas,
    )

    rng = np.random.default_rng(n)
    x = np.triu(rng.uniform(0, 1, (n, n)), 1)
    mask = jnp.triu(jnp.ones((n, n), bool), 1)
    xs = metrics_device.symmetrize(mask, jnp.asarray(x))
    want = float(metrics_device.triangle_violation(xs))
    got = float(
        max_triangle_violation_pallas(xs, block=block, block_r=block_r)
    )
    assert want == got
    if n > block_r:
        assert -(-max(n, block) // block_r) > 1  # really multi-row-block


def test_slab_valid_masks_count():
    """Masks mark exactly 3·C(n, 3) real cells across the layout."""
    for n, nb, procs in ((9, 1, 1), (14, 3, 2)):
        lay = sched.build_layout(n, num_buckets=nb, procs=procs)
        masks = sched.slab_valid_masks(lay)
        total = sum(int(m.sum()) for m in masks)
        assert total == 3 * sched.n_triplets(n)


# --------------------------------------------- engine keys & host parity
def test_device_metrics_keys_match_host_report():
    p = _problem(9, seed=6, kind="cc")
    solver = ParallelSolver(p)
    st_ = solver.run(passes=2)
    host = solver.metrics(st_)
    dev = solver.device_metrics(st_)
    assert set(host) == set(dev)
    host_d = solver.metrics(st_, include_duals=True)
    dev_d = solver.device_metrics(st_, include_duals=True)
    assert set(host_d) == set(dev_d)
    assert {"dual_min", "dual_max", "dual_l1", "active_constraints"} <= set(dev_d)


def test_run_until_staged_operand_mode(x64):
    """The staged arrays are a jit operand of the runners, not embedded
    constants: the runner still stops at exactly max_passes through a
    partial chunk, on the same iterate as plain passes."""
    solver = ParallelSolver(_problem(10, seed=2), dtype=np.float64)
    traced = []
    one_pass = solver._one_pass

    def spy(st):
        leaf = jax.tree.leaves(solver._staged_view())[0]
        traced.append(isinstance(leaf, jax.core.Tracer))
        return one_pass(st)

    solver._one_pass = spy
    st_, info = solver.run_until(tol=0.0, max_passes=7, check_every=3)
    assert traced and all(traced)
    solver._one_pass = one_pass
    assert info["passes"] == 7 and not info["converged"]
    np.testing.assert_array_equal(
        np.asarray(st_.x), np.asarray(solver.run(passes=7).x)
    )
    st2, info2 = solver.run_until(st_, tol=0.0, max_passes=7, check_every=3)
    assert info2["passes"] == 7
    np.testing.assert_array_equal(np.asarray(st2.x), np.asarray(st_.x))
