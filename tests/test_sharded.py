"""Distributed solver: exactness vs the serial oracle, dual-slab round trip,
and a true multi-device run in a subprocess (8 host devices)."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import dykstra, problems
from repro.core.sharded_dykstra import ShardedSolver


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("solver",))


def _problem(n, seed=0, cc=False):
    rng = np.random.default_rng(seed)
    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    if cc:
        return problems.correlation_clustering_lp((d > 0.5).astype(float), eps=0.05)
    return problems.metric_nearness_l2(d)


@pytest.mark.parametrize("n,buckets", [(8, 1), (13, 3)])
def test_sharded_p1_matches_serial(n, buckets):
    p = _problem(n)
    st_ser = dykstra.solve_serial(p, max_passes=2, order="schedule")
    solver = ShardedSolver(p, _mesh1(), num_buckets=buckets)
    st = solver.run(passes=2)
    np.testing.assert_allclose(np.asarray(st.x), st_ser.x, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        solver.duals_to_dense(st), st_ser.ytri, rtol=2e-4, atol=2e-5
    )


def test_sharded_cc_lp_p1():
    p = _problem(9, seed=2, cc=True)
    st_ser = dykstra.solve_serial(p, max_passes=3, order="schedule")
    st = ShardedSolver(p, _mesh1(), num_buckets=2).run(passes=3)
    np.testing.assert_allclose(np.asarray(st.x), st_ser.x, rtol=3e-4, atol=3e-5)
    np.testing.assert_allclose(np.asarray(st.f), st_ser.f, rtol=3e-4, atol=3e-5)


def test_sharded_metrics_report():
    p = _problem(10, seed=4)
    solver = ShardedSolver(p, _mesh1())
    st = solver.run(passes=20)
    m = solver.metrics(st)
    assert m["max_violation"] < 0.05
    assert np.isfinite(m["duality_gap"])


_SUBPROCESS_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.core import dykstra, problems
    from repro.core.sharded_dykstra import ShardedSolver

    assert len(jax.devices()) == 8
    n = 14
    rng = np.random.default_rng(7)
    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    p = problems.metric_nearness_l2(d)
    st_ser = dykstra.solve_serial(p, max_passes=2, order="schedule")
    mesh = Mesh(np.array(jax.devices()), ("solver",))
    solver = ShardedSolver(p, mesh, num_buckets=3)
    st = solver.run(passes=2)
    np.testing.assert_allclose(np.asarray(st.x), st_ser.x, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(solver.duals_to_dense(st), st_ser.ytri,
                               rtol=2e-4, atol=2e-5)
    print("SHARDED8_OK")
    """
)


@pytest.mark.multidevice
def test_sharded_8_devices_subprocess():
    """True multi-device execution: 8 host devices, r mod 8 set assignment,
    per-device dual slabs, exact delta psum — must equal the serial oracle."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED8_OK" in out.stdout


def test_packed_delta_mode_matches_psum_p1():
    p = _problem(11, seed=9)
    a = ShardedSolver(p, _mesh1(), num_buckets=2, delta_mode="psum").run(passes=2)
    b = ShardedSolver(p, _mesh1(), num_buckets=2, delta_mode="packed").run(passes=2)
    np.testing.assert_allclose(np.asarray(a.x), np.asarray(b.x), rtol=1e-6, atol=1e-7)


_PACKED_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.core import dykstra, problems
    from repro.core.sharded_dykstra import ShardedSolver

    n = 14
    rng = np.random.default_rng(7)
    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    p = problems.metric_nearness_l2(d)
    st_ser = dykstra.solve_serial(p, max_passes=2, order="schedule")
    mesh = Mesh(np.array(jax.devices()), ("solver",))
    solver = ShardedSolver(p, mesh, num_buckets=3, delta_mode="packed")
    st = solver.run(passes=2)
    np.testing.assert_allclose(np.asarray(st.x), st_ser.x, rtol=2e-4, atol=2e-5)
    print("PACKED8_OK")
    """
)


@pytest.mark.multidevice
def test_packed_delta_8_devices_subprocess():
    """§Perf H3 exactness: packed all_gather delta exchange on 8 real host
    devices must equal the serial oracle."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _PACKED_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PACKED8_OK" in out.stdout


# ------------------------------------------------ fused multi-pass runner
def test_sharded_fused_scan_matches_host_loop_bitwise():
    """DESIGN.md §9 runner contract: ``run(passes=P)`` (one jitted scan
    over the shard_map pass) must produce bit-identical state to P
    host-looped single-pass dispatches, emit the P-pass residual
    trajectory, and treat ``run(st, 0)`` as the identity."""
    p = _problem(12, seed=3)
    solver = ShardedSolver(p, _mesh1(), num_buckets=2)
    st_scan = solver.run(passes=3)
    res = np.asarray(solver.last_residuals)
    st_loop = solver.init_state()
    for _ in range(3):
        st_loop = solver._pass_fn(st_loop)
    np.testing.assert_array_equal(np.asarray(st_scan.x), np.asarray(st_loop.x))
    for a, b in zip(st_scan.yd, st_loop.yd):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(st_scan.passes) == 3
    assert res.shape == (3,) and np.all(res > 0)
    assert solver.run(st_scan, passes=0) is st_scan


def test_sharded_kernel_matches_fused_jnp_bitwise():
    """DESIGN.md §10: ``use_kernel=True`` routes every diagonal through
    the gen-3 megakernel in delta-output mode — X and the dense dual
    maps must equal the jnp fused path bitwise (the kernel emits the
    same act-masked delta matrix the jnp path scatters)."""
    p = _problem(13, seed=0)
    a = ShardedSolver(p, _mesh1(), num_buckets=3).run(passes=2)
    b = ShardedSolver(p, _mesh1(), num_buckets=3, use_kernel=True).run(
        passes=2
    )
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
    probe = ShardedSolver(p, _mesh1(), num_buckets=3)
    np.testing.assert_array_equal(
        probe.duals_to_dense(a), probe.duals_to_dense(b)
    )


def test_sharded_kernel_rejects_packed_mode():
    """The megakernel emits the psum delta matrix directly; the packed
    compact exchange has no kernel path and must refuse loudly."""
    p = _problem(9, seed=1)
    with pytest.raises(ValueError, match="psum"):
        ShardedSolver(p, _mesh1(), use_kernel=True, delta_mode="packed")


_KERNEL8_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.core import problems
    from repro.core.sharded_dykstra import ShardedSolver

    assert len(jax.devices()) == 8
    n = 14
    rng = np.random.default_rng(7)
    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    p = problems.metric_nearness_l2(d)
    mesh = Mesh(np.array(jax.devices()), ("solver",))
    a = ShardedSolver(p, mesh, num_buckets=3).run(passes=2)
    b = ShardedSolver(p, mesh, num_buckets=3, use_kernel=True).run(passes=2)
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
    probe = ShardedSolver(p, mesh, num_buckets=3)
    np.testing.assert_array_equal(
        probe.duals_to_dense(a), probe.duals_to_dense(b)
    )
    print("KERNEL8_OK")
    """
)


@pytest.mark.multidevice
def test_sharded_kernel_8_devices_subprocess():
    """True multi-device megakernel execution: on 8 host devices the
    gen-3 delta-output kernel inside shard_map must equal the jnp fused
    path bit-for-bit (per-device deltas scattered into zeros, one exact
    psum per diagonal)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _KERNEL8_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "KERNEL8_OK" in out.stdout


def test_sharded_fused_baseline_matches_serial():
    """``fused=False`` (the benchmark baseline: legacy sweep, one
    dispatch per pass) must still match the serial oracle."""
    p = _problem(10, seed=5)
    st_ser = dykstra.solve_serial(p, max_passes=2, order="schedule")
    solver = ShardedSolver(p, _mesh1(), num_buckets=2, fused=False)
    st = solver.run(passes=2)
    np.testing.assert_allclose(np.asarray(st.x), st_ser.x, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        solver.duals_to_dense(st), st_ser.ytri, rtol=2e-4, atol=2e-5
    )


_FUSED8_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.core import problems
    from repro.core.sharded_dykstra import ShardedSolver
    from repro.launch import elastic

    n = 14
    rng = np.random.default_rng(7)
    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    p = problems.metric_nearness_l2(d)
    mesh = Mesh(np.array(jax.devices()), ("solver",))
    solver = ShardedSolver(p, mesh, num_buckets=3)
    # fused P-pass scan (ONE compiled program) == P host-looped passes
    st_scan = solver.run(passes=3)
    st_loop = solver.init_state()
    for _ in range(3):
        st_loop = solver._pass_fn(st_loop)
    np.testing.assert_array_equal(np.asarray(st_scan.x), np.asarray(st_loop.x))
    for a, b in zip(st_scan.yd, st_loop.yd):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # device-side reshard of the LIVE sharded slabs, 8 -> 4 devices,
    # output left sharded on a 4-device mesh == dense round-trip oracle
    mesh4 = Mesh(np.array(jax.devices()[:4]), ("solver",))
    new_slabs, lay = elastic.reshard_duals(
        st_scan.yd, n, 8, 4, 3, mesh=mesh4
    )
    oracle, _ = elastic.reshard_duals_dense(
        [np.asarray(s) for s in st_scan.yd], n, 8, 4, 3
    )
    for sa, sb in zip(new_slabs, oracle):
        assert len(sa.sharding.device_set) == 4, sa.sharding
        np.testing.assert_array_equal(np.asarray(sa), sb)
    assert lay.procs == 4
    print("FUSED8_OK")
    """
)


@pytest.mark.multidevice
def test_sharded_fused_8_devices_subprocess():
    """True multi-device fused runtime: the P-pass scan on 8 host devices
    must equal P host-looped dispatches bit-for-bit, and the device-side
    reshard of the live sharded state must equal the dense oracle with
    slabs left sharded."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _FUSED8_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FUSED8_OK" in out.stdout


@pytest.mark.parametrize("mode", ["psum", "packed"])
def test_sharded_stage_spans_and_merge_bytes(mode):
    """The constructor records the stage spans in ParallelSolver's order
    (layout once, slabs 1 + buckets, put 2 + buckets); each traced bucket
    program adds the bytes it all-reduces to ``repro.shard.merge.bytes``:
    per diagonal the (n, n) f32 delta in psum mode, the (2T + 7, p, Cl)
    packed segments in packed mode."""
    from repro import obs

    n = 10
    obs.reset()
    solver = ShardedSolver(_problem(n, seed=3, cc=True), _mesh1(),
                           num_buckets=3, use_kernel=mode == "psum",
                           delta_mode=mode)
    nb = len(solver.layout.buckets)
    assert {k: v["n"] for k, v in obs.snapshot().items()} == {
        "repro.stage.layout": 1, "repro.stage.slabs": 1 + nb,
        "repro.stage.put": 2 + nb}
    solver.run_until(tol=0.0, max_passes=2, check_every=1)
    per_diag = [
        n * n if mode == "psum" else (2 * bl.T + 7) * bl.procs * bl.lanes
        for bl in solver.layout.buckets
    ]
    want = sum(bl.num_diagonals * c * 4
               for bl, c in zip(solver.layout.buckets, per_diag))
    assert obs.snapshot()["repro.shard.merge.bytes"]["n"] == want
