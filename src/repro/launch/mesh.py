"""Production mesh construction and the multi-process (multi-host) entry.

Never touches jax device state at import time — everything is a function.
The production topology is a v5e pod: 16×16 = 256 chips per pod, 2 pods for
the multi-pod dry-run. ``data`` carries batch (and the solver's processor
axis), ``model`` carries TP/EP, ``pod`` is the slow inter-pod axis that folds
into data-parallel gradient reduction.

Multi-process leg (DESIGN.md §14): ``initialize_distributed`` wraps
``jax.distributed.initialize`` so a fleet of processes (one per host, or
per-process CPU workers in tests) assemble one global device list, and
``make_global_solver_mesh`` lays the 1-D "solver" axis over it — the
sharded Dykstra solver is topology-agnostic beyond that axis, so the same
``ShardedSolver`` program runs single-host and multi-host. The module is
also an executable smoke (``python -m repro.launch.mesh``): initialize,
build the global mesh, run a small sharded metric-nearness solve, print
the mesh line and the (viol, gap) certificate. Tests exercise it via
``XLA_FLAGS=--xla_force_host_platform_device_count`` subprocesses.
"""

from __future__ import annotations

import os

import numpy as np

import jax
from jax.sharding import Mesh

from repro.launch.compile_cache import use_compile_cache

__all__ = [
    "device_memory_bytes",
    "initialize_distributed",
    "make_global_solver_mesh",
    "make_production_mesh",
    "make_solver_mesh",
    "make_host_mesh",
]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape} but have {len(devices)}; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512"
        )
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_solver_mesh(p: int | None = None) -> Mesh:
    """1-D mesh for the distributed Dykstra solver ('solver' axis = the
    paper's processor count)."""
    devices = jax.devices()
    p = p or len(devices)
    return Mesh(np.asarray(devices[:p]), ("solver",))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh for tests on however many host devices exist."""
    devices = jax.devices()
    need = data * model
    return Mesh(np.asarray(devices[:need]).reshape(data, model), ("data", "model"))


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    local_device_count: int | None = None,
) -> bool:
    """Bring up the multi-process jax runtime when asked; no-op otherwise.

    Returns True when ``jax.distributed.initialize`` ran (multi-process:
    a coordinator address or an explicit process count > 1 was given),
    False for the single-process case — callers never need to branch,
    ``jax.devices()`` is the global list either way.

    ``local_device_count`` forces that many host-platform devices in
    *this* process (the test/bench harness for mesh legs without real
    accelerators). It must take effect before the jax backend
    initializes — call this before any array/device touch, same rule as
    ``jax.distributed.initialize`` itself.
    """
    if local_device_count:
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{int(local_device_count)}"
            ).strip()
    multi = (num_processes or 1) > 1 or coordinator_address is not None
    if not multi:
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def make_global_solver_mesh(p: int | None = None) -> Mesh:
    """1-D "solver" mesh over the GLOBAL device list — the multi-host twin
    of ``make_solver_mesh``. After ``initialize_distributed`` on every
    process, ``jax.devices()`` spans all hosts; each process calls this
    with the same ``p`` (or None = all) and gets the same mesh, and the
    sharded solver's shard_map programs run SPMD across processes."""
    devices = jax.devices()
    p = p or len(devices)
    if p > len(devices):
        raise RuntimeError(
            f"need {p} devices for the solver mesh but the global list has "
            f"{len(devices)} (processes={jax.process_count()})"
        )
    return Mesh(np.asarray(devices[:p]), ("solver",))


def device_memory_bytes() -> tuple[int, str]:
    """Best-effort peak/live device memory: ``(bytes, source)``.

    Prefers the backend's per-device allocator stats
    (``peak_bytes_in_use`` summed over local devices — real accelerators
    report these); falls back to summing the sizes of every live
    ``jax.Array`` (the CPU backend reports no stats). Diagnostic only —
    the scale campaign and the solve launcher's telemetry line both print
    it — never used for control flow.
    """
    total, got = 0, False
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats and "peak_bytes_in_use" in stats:
            total += int(stats["peak_bytes_in_use"])
            got = True
    if got:
        return total, "device_stats"
    live = 0
    for a in jax.live_arrays():
        try:
            live += int(a.nbytes)
        except Exception:
            pass
    return live, "live_arrays"


def main(argv=None) -> int:
    """Multi-process mesh smoke: initialize, build the global solver mesh,
    run a small sharded metric-nearness solve, print the certificate."""
    import argparse
    import time

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--coordinator", default=None,
                    help="coordinator address host:port (multi-process)")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--local-device-count", type=int, default=None,
                    help="force N host-platform devices in this process")
    ap.add_argument("--n", type=int, default=20)
    ap.add_argument("--p", type=int, default=None,
                    help="solver axis size (default: all global devices)")
    ap.add_argument("--buckets", type=int, default=3)
    ap.add_argument("--max-passes", type=int, default=60)
    ap.add_argument("--tol", type=float, default=1e-3)
    ap.add_argument("--use-kernel", action="store_true")
    args = ap.parse_args(argv)
    use_compile_cache()
    dist = initialize_distributed(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
        local_device_count=args.local_device_count,
    )
    mesh = make_global_solver_mesh(args.p)
    print(
        f"mesh: distributed={dist} processes={jax.process_count()} "
        f"process={jax.process_index()} global_devices={len(jax.devices())} "
        f"local_devices={len(jax.local_devices())} "
        f"solver_axis={mesh.devices.size}"
    )

    from repro.core.problems import metric_nearness_l2
    from repro.core.sharded_dykstra import ShardedSolver

    rng = np.random.default_rng(0)
    d = rng.random((args.n, args.n))
    d = (d + d.T) / 2
    np.fill_diagonal(d, 0)
    solver = ShardedSolver(
        metric_nearness_l2(d), mesh, num_buckets=args.buckets,
        use_kernel=args.use_kernel,
    )
    t0 = time.perf_counter()
    _, info = solver.run_until(tol=args.tol, max_passes=args.max_passes,
                               check_every=5)
    dt = time.perf_counter() - t0
    mem, src = device_memory_bytes()
    print(
        f"mesh solve: n={args.n} p={mesh.devices.size} "
        f"passes={int(info['passes'])} converged={bool(info['converged'])} "
        f"viol={float(info['max_violation']):.3e} "
        f"gap={float(info['duality_gap']):.3e} "
        f"mem={mem / 1e6:.1f}MB({src}) ({dt:.1f}s)"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
