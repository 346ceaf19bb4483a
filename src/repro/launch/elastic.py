"""Elastic scaling + fault-tolerance policy.

The framework's failure model for 1000+ node fleets:

  * **Node failure (training)**: jobs are stateless between steps — state
    lives in (checkpoint, data-step counter). On failure the controller
    relaunches with the survivors; ``remesh_plan`` recomputes the mesh and
    the run resumes from the latest atomic checkpoint. Data order is a pure
    function of (seed, step) (train/data.py), so the token stream is
    identical post-restart.

  * **Node failure (solver)**: the layout deals each device a contiguous
    run of every diagonal's folded lanes (``schedule.device_assignment``).
    Because the schedule is deterministic in (n, p), restoring (X, F, dual slabs, pass counter)
    under a NEW p re-shards duals exactly: ``reshard_duals`` applies the
    composed slab→slab permutation as one device-side gather, slabs left
    sharded. Convergence is unaffected — Dykstra tolerates any
    constraint-visit order across passes. This is LIVE code, not policy
    prose: ``degrade_solver`` rebuilds a running ``ShardedSolver`` (live
    state included) onto the survivor mesh mid-solve, and
    ``launch/solve.py`` invokes it at the window boundary where an
    (injected or real) device loss surfaces — the chaos tests in
    tests/test_faults.py pin that the degraded solve's final certificate
    matches the fixed-mesh run. Corrupt-checkpoint walk-back lives in
    ``train/checkpoint.py`` (CRC-verified restore + ``resume_or``); the
    deterministic fault source is ``serve/faults.py`` (DESIGN.md §11).

  * **Stragglers**: folded lanes of near-uniform height give every device
    the same (T, Cl) tile per diagonal, the paper's static balance (its
    Fig. 3 gets it from an ``r mod p`` interleave); diagonal bucketing
    bounds per-scan-step skew. For persistent stragglers
    the controller shrinks p at a pass boundary (this module's remesh) rather
    than blocking on the slow node — cheap because pass boundaries are
    frequent and checkpoints are async.

  * **Pods**: the 'pod' mesh axis only carries data-parallel gradient
    reduction; losing a pod halves global batch but changes no parameter
    sharding, so multi-pod elasticity is a remesh along the cheapest axis.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import schedule as sched

__all__ = [
    "degrade_solver",
    "remesh_plan",
    "reshard_duals",
    "reshard_duals_dense",
    "reshard_duals_host",
    "shrink_mesh",
    "RemeshPlan",
]


@dataclasses.dataclass(frozen=True)
class RemeshPlan:
    old_devices: int
    new_devices: int
    pod: int
    data: int
    model: int

    @property
    def batch_scale(self) -> float:
        """Keep per-device batch constant → global batch scales with data."""
        return (self.pod * self.data) / self.old_devices


def remesh_plan(old_devices: int, new_devices: int, model_parallel: int = 16,
                chips_per_pod: int = 256) -> RemeshPlan:
    """Choose (pod, data, model) for the surviving device count.

    Keeps model-parallel fixed (parameter shardings unchanged → checkpoint
    loads without resharding weights) and absorbs loss into the data axis.
    """
    if new_devices % model_parallel != 0:
        # shrink to the largest multiple — surplus devices idle (hot spares)
        new_devices = (new_devices // model_parallel) * model_parallel
    if new_devices <= 0:
        raise ValueError("not enough devices for one model replica")
    pods = max(1, new_devices // chips_per_pod)
    data = new_devices // (pods * model_parallel)
    return RemeshPlan(old_devices, new_devices, pods, data, model_parallel)


@functools.lru_cache(maxsize=16)
def _reshard_device_fn(n: int, num_buckets: int, p_old: int, p_new: int,
                       dtype_name: str, mesh: Mesh | None):
    """Cached jitted slab→slab permutation program (see reshard_duals).

    The composed permutation is folded into ONE gather table: ``move``
    maps every flat position of the new slab vector to its source
    position in the old one (real cells), and ``valid`` masks the padding
    cells (which stay zero — old padding holds don't-care values under
    fused execution and must never be copied). With ``mesh`` the
    permutation output is then placed sharded on the mesh axis
    (``device_put``; a second step, because one jitted program cannot
    change its device set) — the slabs never round-trip through the host.
    """
    src, dst, size_old, size_new = sched.compose_slab_permutation(
        n, num_buckets, p_old, p_new
    )
    new = sched.build_layout(n, num_buckets=num_buckets, procs=p_new)
    idt = np.int64 if size_old >= np.iinfo(np.int32).max else np.int32
    move = np.zeros(size_new, idt)
    move[dst] = src.astype(idt)
    valid = np.zeros(size_new, bool)
    valid[dst] = True
    move_d, valid_d = jnp.asarray(move), jnp.asarray(valid)
    dtype = jnp.dtype(dtype_name)
    shapes = [bl.slab_shape for bl in new.buckets]

    @jax.jit
    def permute(slabs):
        flat_old = (
            jnp.concatenate([jnp.reshape(s, (-1,)) for s in slabs])
            if slabs else jnp.zeros((0,), dtype)
        )
        moved = jnp.where(
            valid_d, flat_old[move_d].astype(dtype), jnp.zeros((), dtype)
        )
        out, off = [], 0
        for sh in shapes:
            size = int(np.prod(sh))
            out.append(moved[off : off + size].reshape(sh))
            off += size
        return out

    if mesh is None:
        return permute, new, size_old
    # The permutation jit runs wherever the inputs live (the OLD mesh);
    # the result is then placed sharded on the NEW mesh's first axis.
    # Two steps because one jitted program cannot change its device set —
    # an elastic restart by definition does.
    shard = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))

    def permute_and_place(slabs):
        return [jax.device_put(s, shard) for s in permute(slabs)]

    return permute_and_place, new, size_old


def reshard_duals(yd_slabs, n: int, p_old: int, p_new: int,
                  num_buckets: int, dtype=np.float32,
                  mesh: Mesh | None = None):
    """Re-shard solver dual slabs from p_old to p_new devices, on device.

    Applies one **direct slab→slab index permutation**
    (``schedule.compose_slab_permutation``, cached per device-count pair):
    the two layouts' dense conversion maps are composed symbolically, so
    the move is a SINGLE device gather over the real duals — the dense
    (n, n, n) tensor is never materialized and nothing round-trips
    through the host (the historical host-float64 path survives as the
    ``reshard_duals_host`` test oracle). Exact because every triplet's
    slot is determined by the deterministic schedule on both sides, and
    because a gather only moves values (no arithmetic, any dtype).

    Args:
      yd_slabs: per-bucket dual slabs (jax arrays — e.g. a live
        ``ShardedState.yd`` — or numpy); padding cells may hold don't-care
        values (fused execution), they are masked out, never copied.
      mesh: optionally the target mesh; output slabs are then placed
        **sharded on its first axis** (``p_new`` must be divisible by
        the mesh size), so at the 512-chip dry-run scale the permutation
        runs on device and the slabs end up sharded, never hostside.

    Returns (new_slabs, new_layout): slabs shaped ``(p_new, D, 3, T, Cl)``
    per bucket, matching ShardedSolver's schedule-native storage.
    """
    fn, new, size_old = _reshard_device_fn(
        int(n), int(num_buckets), int(p_old), int(p_new),
        np.dtype(dtype).name, mesh,
    )
    held = sum(int(np.prod(np.shape(s))) for s in yd_slabs)
    if held != size_old:
        raise ValueError(
            f"slabs hold {held} elements, layout expects {size_old}"
        )
    return fn(list(yd_slabs)), new


def shrink_mesh(mesh: Mesh, p_new: int) -> Mesh:
    """Survivor mesh after device loss: the first ``p_new`` devices of
    the old 1-D solver mesh, same axis name. Deterministic, so a
    degraded run is replayable."""
    devices = np.asarray(mesh.devices).reshape(-1)
    if not 0 < p_new <= devices.size:
        raise ValueError(
            f"cannot shrink a {devices.size}-device mesh to p={p_new}"
        )
    return Mesh(devices[:p_new], mesh.axis_names[:1])


def degrade_solver(solver, state, p_new: int, mesh: Mesh | None = None):
    """Degrade-and-resume after device loss (DESIGN.md §6/§11): rebuild a
    live ``ShardedSolver`` — mid-solve state included — onto a survivor
    mesh of ``p_new`` devices.

    The dual slabs move through ``reshard_duals`` (one device-side
    gather, exact for any dtype); the replicated leaves (x, f, ypair,
    ybox, pass counter) are re-placed on the new mesh with
    ``device_put``. The new solver inherits every configuration knob
    (dtype, bucketing, kernel/delta/fused/unroll/probe), so the degraded
    run continues under the same compiled semantics — the solve then
    proceeds with ``run_until`` as if nothing happened, and converges to
    the same certificate (Dykstra tolerates any constraint-visit order
    across passes; the schedule under the new p is deterministic).

    Returns ``(new_solver, new_state)``.
    """
    from repro.core.sharded_dykstra import ShardedSolver, ShardedState

    p_old = int(solver.nproc)
    new_mesh = mesh if mesh is not None else shrink_mesh(solver.mesh, p_new)
    new_solver = ShardedSolver(
        solver.p,
        new_mesh,
        dtype=solver.dtype,
        num_buckets=solver.num_buckets,
        use_kernel=solver.use_kernel,
        delta_mode=solver.delta_mode,
        fused=solver.fused,
        sweep_unroll=solver.sweep_unroll,
        probe_every=solver.probe_every,
    )
    new_yd, _ = reshard_duals(
        state.yd, solver.n, p_old, int(p_new), solver.num_buckets,
        dtype=solver.dtype, mesh=new_mesh,
    )
    rep = NamedSharding(new_mesh, PartitionSpec())
    put = lambda a: None if a is None else jax.device_put(jnp.asarray(a), rep)
    new_state = ShardedState(
        x=put(state.x),
        f=put(state.f),
        yd=new_yd,
        ypair=put(state.ypair),
        ybox=put(state.ybox),
        passes=put(state.passes),
    )
    return new_solver, new_state


def reshard_duals_host(yd_slabs, n: int, p_old: int, p_new: int,
                       num_buckets: int, dtype=np.float32):
    """Host-numpy float64 twin of ``reshard_duals`` (the historical
    implementation): same composed permutation, applied on host. Kept as
    a test oracle and for offline checkpoint surgery without devices."""
    src, dst, size_old, size_new = sched.compose_slab_permutation(
        n, num_buckets, p_old, p_new
    )
    new = sched.build_layout(n, num_buckets=num_buckets, procs=p_new)
    flat_old = np.concatenate(
        [np.asarray(s, np.float64).reshape(-1) for s in yd_slabs]
    ) if yd_slabs else np.zeros(0, np.float64)
    if flat_old.shape[0] != size_old:
        raise ValueError(
            f"slabs hold {flat_old.shape[0]} elements, layout expects {size_old}"
        )
    flat_new = np.zeros(size_new, dtype=dtype)
    flat_new[dst] = flat_old[src].astype(dtype)
    out, off = [], 0
    for bl in new.buckets:
        out.append(flat_new[off : off + bl.slab_size].reshape(bl.slab_shape))
        off += bl.slab_size
    return out, new


def reshard_duals_dense(yd_slabs: list[np.ndarray], n: int, p_old: int,
                        p_new: int, num_buckets: int, dtype=np.float32):
    """Dense round-trip re-shard (the historical implementation): convert
    old slabs → (n, n, n) → new slabs. O(n^3) host memory — kept ONLY as
    the test oracle `reshard_duals` is validated against."""
    old = sched.build_layout(n, num_buckets=num_buckets, procs=p_old)
    new = sched.build_layout(n, num_buckets=num_buckets, procs=p_new)
    dense = sched.duals_to_dense(old, yd_slabs)
    return sched.dense_to_duals(new, dense, dtype=dtype), new
