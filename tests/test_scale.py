"""Scale campaign machinery (DESIGN.md §14): lane-blocked violation
kernel parity (bitwise vs the jnp oracle), the slab entry + kernel-backed
sharded probe, donated async snapshots, the multi-process mesh entry, and
the campaign's memory-model cube-root law."""

import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core import metrics_device, problems
from repro.core.sharded_dykstra import ShardedSolver
from repro.kernels.metric_project import ops as kops
from repro.kernels.metric_project.violation import (
    max_triangle_violation_pallas,
    max_triangle_violation_slab_pallas,
)
from repro.launch import mesh as mesh_lib
from repro.train import checkpoint as ckpt_lib


def _sym(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, n))
    x = np.abs(x + x.T).astype(np.float32)
    np.fill_diagonal(x, 0.0)
    return jnp.asarray(x)


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("solver",))


def _problem(n, seed=0):
    rng = np.random.default_rng(seed)
    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    return problems.metric_nearness_l2(d)


# --------------------------------------------- lane-blocked kernel parity
# npad spans >= 3 column blocks in every case (the tentpole's VMEM
# geometry); bitwise equality because max is association-free.
@pytest.mark.parametrize(
    "n,block,block_r,block_c",
    [
        (50, 8, 16, 16),  # npad=64: 4 column blocks, non-multiple n
        (97, 4, 32, 32),  # npad=128: 4 column blocks
        (33, 8, 8, 8),  # npad=40: 5 column blocks
        (64, 16, 16, 16),  # exact multiple: no padding at all
        (40, 8, 16, 24),  # block_c != block_r (lcm padding)
    ],
)
def test_lane_blocked_kernel_bitwise_vs_jnp(n, block, block_r, block_c):
    xs = _sym(n, seed=n)
    want = metrics_device.triangle_violation(xs)
    got = max_triangle_violation_pallas(
        xs, block=block, block_r=block_r, block_c=block_c
    )
    assert float(want) == float(got)


def test_lane_blocked_matches_full_width():
    """block_c=None (the pre-§14 single full-width column block) and the
    lane-blocked grid agree bitwise on the same matrix."""
    xs = _sym(45, seed=1)
    full = max_triangle_violation_pallas(xs, block=8, block_r=16)
    laned = max_triangle_violation_pallas(xs, block=8, block_r=16, block_c=8)
    assert float(full) == float(laned)


def test_lane_blocked_kernel_ghost_padding():
    """Ghost-padded instance (n_live < n): the kernel masks every triangle
    touching an index >= n_live, matching the jnp oracle bitwise."""
    n, live = 41, 29
    x = _sym(n, seed=3)
    xs = metrics_device.symmetrize(metrics_device.live_pair_mask(n, live), x)
    want = metrics_device.triangle_violation(xs, n_live=live)
    got = max_triangle_violation_pallas(
        xs, block=8, block_r=16, block_c=16, n_live=live
    )
    assert float(want) == float(got)


def test_ops_triangle_violation_threads_block_c():
    xs = _sym(26, seed=5)
    want = metrics_device.triangle_violation(xs)
    assert float(kops.triangle_violation(xs, block_c=8)) == float(want)
    assert float(kops.triangle_violation(xs)) == float(want)


# ------------------------------------------------------------- slab entry
def test_slab_partition_covers_full_reduction():
    """Contiguous apex slabs (including a zero-padded tail slab) pmax to
    exactly the full-matrix reduction — the sharded probe's algebra."""
    n, m = 40, 16  # 3 slabs: [0,16), [16,32), [32,48) with 8 padding rows
    xs = _sym(n, seed=8)
    vs = []
    for k in range(3):
        sl = xs[k * m:(k + 1) * m]
        if sl.shape[0] < m:
            sl = jnp.pad(sl, ((0, m - sl.shape[0]), (0, 0)))
        vs.append(
            max_triangle_violation_slab_pallas(
                sl, jnp.int32(k * m), xs, block=8, block_r=16, block_c=16
            )
        )
    want = metrics_device.triangle_violation(xs)
    assert float(jnp.max(jnp.stack(vs))) == float(want)


def test_slab_entry_rejects_unaligned_rows():
    xs = _sym(20, seed=2)
    with pytest.raises(AssertionError, match="multiple of the apex block"):
        max_triangle_violation_slab_pallas(xs[:10], jnp.int32(0), xs, block=8)


# ------------------------------------------- kernel-backed sharded probe
def test_sharded_kernel_probe_matches_jnp_p1():
    xs = _sym(37, seed=4)
    want = metrics_device.triangle_violation(xs)
    got = metrics_device.triangle_violation_sharded_kernel(
        xs, _mesh1(), block=8, block_r=16, block_c=16
    )
    assert float(want) == float(got)
    got_live = metrics_device.triangle_violation_sharded_kernel(
        xs, _mesh1(), n_live=20
    )
    assert float(got_live) == float(
        metrics_device.triangle_violation(xs, n_live=20)
    )


def test_sharded_solver_use_kernel_routes_probe():
    """use_kernel flips the sharded stopping probe to the Pallas slab
    kernel; run_until must land on the identical certificate and pass
    count (the probes are bitwise-equal)."""
    p = _problem(18, seed=6)
    a = ShardedSolver(p, _mesh1(), num_buckets=3, use_kernel=True,
                      probe_block_c=16)
    b = ShardedSolver(p, _mesh1(), num_buckets=3, use_kernel=False)
    _, ia = a.run_until(tol=1e-3, max_passes=30, check_every=5)
    _, ib = b.run_until(tol=1e-3, max_passes=30, check_every=5)
    assert float(ia["max_violation"]) == float(ib["max_violation"])
    assert int(ia["passes"]) == int(ib["passes"])
    assert bool(ia["converged"]) and bool(ib["converged"])


# ------------------------------------------ jnp apex-block padding guard
def test_apex_block_clamped_and_guarded():
    """apex_block > n no longer sweeps phantom blocks (clamped to n), and
    every blocking agrees with every other bitwise."""
    xs = _sym(23, seed=9)
    base = metrics_device.triangle_violation(xs, apex_block=1)
    for ab in (4, 7, 16, 23, 64, 1000):
        assert float(metrics_device.triangle_violation(xs, apex_block=ab)) \
            == float(base)


def test_sharded_jnp_probe_n_live():
    xs = _sym(21, seed=10)
    want = metrics_device.triangle_violation(xs, n_live=15)
    got = metrics_device.triangle_violation_sharded(
        xs, _mesh1(), n_live=15
    )
    assert float(want) == float(got)


# --------------------------------------------------- donated snapshots
def test_snapshot_device_copy_is_independent():
    tree = {"x": jnp.arange(6.0), "y": [jnp.ones((2, 2))]}
    live, snap = ckpt_lib.snapshot_device(tree)
    assert live is tree
    np.testing.assert_array_equal(np.asarray(snap["x"]), np.arange(6.0))
    # donate path (a no-op alias copy on CPU backends) still round-trips
    live2, snap2 = ckpt_lib.snapshot_device(tree, donate=True)
    np.testing.assert_array_equal(
        np.asarray(live2["x"]), np.asarray(snap2["x"])
    )


def test_save_async_donate_roundtrip():
    tree = {"x": jnp.arange(12.0).reshape(3, 4), "n": jnp.int32(7)}
    d = tempfile.mkdtemp()
    th, live = ckpt_lib.save_async(d, 5, tree, donate=True)
    th.join()
    ckpt_lib.wait_pending()
    got, manifest = ckpt_lib.restore(d, tree)
    assert manifest["step"] == 5
    np.testing.assert_array_equal(
        np.asarray(got["x"]), np.arange(12.0).reshape(3, 4)
    )
    # the returned live tree stays usable after the writer finished
    assert float(jnp.sum(live["x"])) == 66.0


def test_maybe_save_donate_idiom():
    tree = {"x": jnp.ones(4)}
    d = tempfile.mkdtemp()
    mgr = ckpt_lib.CheckpointManager(d, every=10)
    handle, tree = mgr.maybe_save(3, tree, donate=True)  # off cadence
    assert handle is None
    handle, tree = mgr.maybe_save(10, tree, donate=True)
    assert handle is not None
    ckpt_lib.wait_pending()
    _, manifest = ckpt_lib.restore(d, tree)
    assert manifest["step"] == 10
    with pytest.raises(ValueError, match="asynchronous"):
        mgr.maybe_save(20, tree, donate=True, asynchronous=False)


# -------------------------------------------------- multi-process mesh
def test_initialize_distributed_single_process_noop():
    assert mesh_lib.initialize_distributed() is False
    assert mesh_lib.initialize_distributed(num_processes=1) is False


def test_make_global_solver_mesh():
    mesh = mesh_lib.make_global_solver_mesh()
    assert mesh.axis_names == ("solver",)
    assert mesh.devices.size == len(jax.devices())
    with pytest.raises(RuntimeError, match="global list"):
        mesh_lib.make_global_solver_mesh(len(jax.devices()) + 1)


def test_device_memory_bytes_reports():
    keep = jnp.ones((64, 64))  # ensure something is live
    total, source = mesh_lib.device_memory_bytes()
    assert source in ("device_stats", "live_arrays")
    assert total >= keep.nbytes


# ------------------------------------------------ campaign memory model
def test_feasible_ladder_cube_root_law():
    """The acceptance bar's scaling: the 8-device ladder tops out at
    >= 2x the single-device largest-n for both campaign budgets (the
    dual-slab bytes grow ~n^3, so largest-n ~ (p*B)^(1/3))."""
    from benchmarks import scale_campaign as sc

    for budget in (sc.SMOKE_BUDGET_MB, sc.FULL_BUDGET_MB):
        l1 = sc.feasible_ladder(1, budget)
        l8 = sc.feasible_ladder(8, budget)
        assert l1 and l8
        assert l8[-1] >= 2 * l1[-1], (budget, l1[-1], l8[-1])
    # the smoke cap keeps the CI leg bounded
    assert sc.feasible_ladder(8, 1e9, cap=sc.SMOKE_CAP)[-1] <= sc.SMOKE_CAP


# ------------------------------------------------- 8-device subprocess
_PROBE8_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import metrics_device, problems
    from repro.core.sharded_dykstra import ShardedSolver

    assert len(jax.devices()) == 8
    mesh = Mesh(np.array(jax.devices()), ("solver",))
    rng = np.random.default_rng(11)
    n = 26
    x = rng.normal(size=(n, n))
    xs = jnp.asarray(np.abs(x + x.T).astype(np.float32))
    want = metrics_device.triangle_violation(xs)
    got = metrics_device.triangle_violation_sharded_kernel(
        xs, mesh, block=4, block_r=8, block_c=8)
    assert float(want) == float(got), (float(want), float(got))

    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    p = problems.metric_nearness_l2(d)
    a = ShardedSolver(p, mesh, num_buckets=3, use_kernel=True,
                      probe_block_c=8)
    b = ShardedSolver(p, mesh, num_buckets=3, use_kernel=False)
    _, ia = a.run_until(tol=1e-3, max_passes=40, check_every=5)
    _, ib = b.run_until(tol=1e-3, max_passes=40, check_every=5)
    assert float(ia["max_violation"]) == float(ib["max_violation"])
    assert int(ia["passes"]) == int(ib["passes"])
    print("PROBE8_OK")
    """
)


@pytest.mark.multidevice
def test_kernel_probe_8_devices_subprocess():
    """True 8-device run: the kernel-backed sharded probe (contiguous
    apex slabs + pmax) equals the jnp oracle bitwise, and use_kernel
    run_until lands on the jnp route's exact certificate."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _PROBE8_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PROBE8_OK" in out.stdout


@pytest.mark.multidevice
def test_mesh_entry_8_devices_subprocess():
    """The multi-process mesh entry end to end on 8 forced host devices:
    global mesh line + a converged sharded solve certificate."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.mesh",
         "--local-device-count", "8", "--n", "16", "--use-kernel"],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "global_devices=8" in out.stdout
    assert "converged=True" in out.stdout
