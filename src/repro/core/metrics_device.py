"""Device-resident convergence metrics (DESIGN.md §7).

The stopping pair of the paper — (max constraint violation, duality gap) —
was previously computed by `core/convergence.py`: host-side numpy with a
Python loop over apexes, fed by `np.asarray(state.x)` host transfers. That
is fine as a float64 *oracle*, but at production scale the monitor must
live on device with the pass kernel (Veldt et al. and Project-and-Forget
both fold convergence monitoring into the solver loop). This module is the
jnp twin: every function here is pure, jit-safe, and allocates nothing
bigger than one apex block — in particular the duality gap and the
triangle-dual stats are computed **directly from schedule-native dual
slabs** (DESIGN.md §3); nothing ever densifies to (n, n, n).

Numerical contract, pinned by tests/test_engine.py: with float64 inputs
every scalar matches `convergence.report` to 1e-10 — the device engine
reorganizes the reductions (blocked apexes, masked whole-matrix sums), it
never changes the math. Where fp association matters (the triangle slack),
the expression mirrors the host oracle term-for-term.

`DeviceProblem` is the device-resident constant set of a `MetricQP`
(weights, costs, the triu mask); solvers build one per instance and close
over it in their jitted metric programs, so metrics never re-upload
problem data.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.problems import MetricQP

__all__ = [
    "DeviceProblem",
    "duality_gap",
    "live_pair_mask",
    "max_violation",
    "qp_objective",
    "lp_objective",
    "symmetrize",
    "triangle_dual_stats",
    "triangle_violation",
    "triangle_violation_sharded",
    "triangle_violation_sharded_kernel",
]


@dataclasses.dataclass(frozen=True)
class DeviceProblem:
    """Device-resident constants of one MetricQP (compute dtype).

    Plain (non-pytree) dataclass: solvers hold one instance and *close
    over* it inside their jitted metric programs, so the arrays are baked
    in as constants exactly like the staged schedule slabs. The batched
    serve engine instead constructs instances *inside* a vmapped trace —
    every array field (including ``mask``) then carries a leading-axis
    tracer and ``n_real`` is a traced per-instance scalar; all consumers
    below only index/compare these fields, so both uses share one code
    path.

    ``n_real``: number of live points. Indices >= n_real are *ghost*
    padding (DESIGN.md §8): their pairs are excluded from ``mask`` and
    their triangles from the violation reduction. None means all n live.
    """

    n: int
    eps: float
    has_f: bool
    box: tuple[float, float] | None
    mask: jax.Array  # (n, n) bool strict upper triangle (live pairs only)
    d: jax.Array
    w: jax.Array
    c_x: jax.Array
    w_f: jax.Array | None
    c_f: jax.Array | None
    n_real: int | jax.Array | None = None

    @classmethod
    def from_qp(cls, p: MetricQP, dtype, n_real: int | None = None) -> "DeviceProblem":
        asd = lambda a: None if a is None else jnp.asarray(a, dtype)
        return cls(
            n=p.n,
            eps=float(p.eps),
            has_f=bool(p.has_f),
            box=None if p.box is None else (float(p.box[0]), float(p.box[1])),
            mask=live_pair_mask(p.n, n_real),
            d=asd(p.d),
            w=asd(p.w),
            c_x=asd(p.c_x),
            w_f=asd(p.w_f),
            c_f=asd(p.c_f),
            n_real=n_real,
        )


def live_pair_mask(n: int, n_real=None):
    """Strict-upper-triangle mask restricted to live (non-ghost) pairs.

    ``n_real`` may be a python int or a traced scalar (the batched engine
    vmaps it over instances); None means every index is live.
    """
    m = jnp.triu(jnp.ones((n, n), bool), k=1)
    if n_real is None:
        return m
    live = jnp.arange(n, dtype=jnp.int32) < n_real
    return m & live[:, None] & live[None, :]


def symmetrize(mask, x):
    """Strict-upper-triangle iterate → full symmetric matrix (the view the
    apex-blocked triangle reduction and the Pallas kernel both consume)."""
    xs = jnp.where(mask, x, 0.0)
    return xs + xs.T


def _apex_block_max(xs, cs, n_live=None, *, padded: bool = True):
    """Max triangle slack over one block of apexes.

    ``xs`` is the (n, n) symmetric iterate, ``cs`` (B,) int32 apex indices
    (>= n marks padding). For apex c the slack matrix is
    ``xs[a, b] - (xs[a, c] + xs[c, b])`` — the exact expression (and fp
    association) of the host oracle ``convergence.max_violation``; cells
    with a == b, a == c, b == c and padding apexes are masked to -inf.
    ``n_live`` (int or traced scalar) additionally masks every triangle
    touching a ghost index >= n_live (DESIGN.md §8): ghost x cells are 0,
    so e.g. a ghost apex would report the *false* slack x_ab - 0 - 0.

    Padding contract: ``padded=False`` asserts every ``cs`` entry is a
    real apex (< n) and skips both the index clamp and the liveness term
    of the mask — every interior block of an exactly-divisible sweep takes
    this branch; only tail/dealt blocks that may run past n pay for the
    clamp (``triangle_violation`` decides per sweep, the sharded dealing
    always pads so it always passes True).
    """
    n = xs.shape[0]
    a = jnp.arange(n, dtype=jnp.int32)
    if padded:
        live = cs < n
        c = jnp.minimum(cs, n - 1)
    else:
        c = cs
    xb = xs[c]  # (B, n); row c == column c by symmetry
    slack = xs[None, :, :] - (xb[:, :, None] + xb[:, None, :])
    ok = (
        (a[None, :, None] != a[None, None, :])
        & (c[:, None, None] != a[None, :, None])
        & (c[:, None, None] != a[None, None, :])
    )
    if padded:
        ok = ok & live[:, None, None]
    if n_live is not None:
        la = a < n_live
        ok = ok & (c[:, None, None] < n_live) & la[None, :, None] & la[None, None, :]
    return jnp.max(jnp.where(ok, slack, -jnp.inf))


def triangle_violation(xs, *, apex_block: int = 16, n_live=None):
    """Max violation over the triangle family, blocked over apexes.

    ``lax.map`` sweeps apex blocks sequentially so peak memory is one
    (B, n, n) slack block, never the O(n^3) tensor. Returns -inf for
    n < 3 (no triangles); callers floor the combined violation at 0.
    ``n_live`` restricts the reduction to triangles of the first n_live
    indices (ghost padding, DESIGN.md §8).

    Padding contract (guarded below): ``apex_block`` is clamped to n, so
    the swept index table ``nb·apex_block`` overshoots n by *strictly
    less than one block* — the only padding apexes are the tail of the
    last block, masked -inf inside ``_apex_block_max``. Without the clamp
    a large ``apex_block`` at large non-multiple n would silently sweep
    whole blocks of clamped phantom apexes (index min(c, n-1) — masked,
    but each one a full (B, n, n) slack block of wasted work). When n
    divides evenly there is no padding at all and the per-block reduction
    skips the clamp + liveness masking entirely.
    """
    n = xs.shape[0]
    apex_block = max(1, min(int(apex_block), max(n, 1)))
    nb = max(1, -(-n // apex_block))
    assert nb * apex_block - n < apex_block, (n, apex_block, nb)
    padded = nb * apex_block != n
    cs = jnp.arange(nb * apex_block, dtype=jnp.int32).reshape(nb, apex_block)
    per_block = jax.lax.map(
        lambda c: _apex_block_max(xs, c, n_live, padded=padded), cs
    )
    return jnp.max(per_block)


def triangle_violation_sharded(xs, mesh, axis: str = "solver",
                               *, apex_block: int = 8, n_live=None):
    """Multi-device triangle violation: apex blocks are dealt round-robin
    over the mesh axis, each device reduces its share with the same blocked
    kernel, and one ``pmax`` merges the partial maxima — the monitor's
    analogue of the solvers' per-diagonal psum. ``xs`` is replicated.
    The dealt table is padded to the device count, so blocks may run
    arbitrarily far past n (every padding apex masks to -inf)."""
    from jax.sharding import PartitionSpec as P

    n = xs.shape[0]
    p = mesh.devices.size
    apex_block = max(1, min(int(apex_block), max(n, 1)))
    nb = max(1, -(-n // apex_block))
    nb = -(-nb // p) * p  # pad block count to the device count
    cs = jnp.arange(nb * apex_block, dtype=jnp.int32).reshape(
        p, nb // p, apex_block
    )

    def local(xs_rep, blocks):
        blocks = blocks[0]  # drop the unit device axis
        v = jax.lax.map(lambda c: _apex_block_max(xs_rep, c, n_live), blocks)
        return jax.lax.pmax(jnp.max(v), axis)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(axis)), out_specs=P()
    )(xs, cs)


def triangle_violation_sharded_kernel(xs, mesh, axis: str = "solver",
                                      *, block: int = 8, block_r: int = 128,
                                      block_c: int | None = None,
                                      n_live: int | None = None,
                                      interpret: bool | None = None):
    """Kernel-backed multi-device triangle violation (DESIGN.md §14): the
    lane-blocked Pallas slab kernel composed with the apex-dealing
    ``shard_map`` + ``pmax`` of the jnp path above.

    The apex rows are dealt as **contiguous block-aligned slabs**: device
    k reduces apexes [k·m, (k+1)·m) from its (m, npad) shard of the
    row-padded iterate, drawing (a, b) tiles from the replicated ``xs``
    inside the kernel's (apex, column, row) grid — so per-device VMEM per
    grid step is (A + R)·block_c + A·R floats regardless of n, and the
    only cross-device traffic is the final scalar ``pmax``. Contiguous
    (not round-robin) dealing keeps every padding apex at the global tail
    with index >= n, which the kernel masks exactly like grid padding.
    Bitwise-equal to ``triangle_violation`` (max is association-free).

    ``n_live`` is the ghost-padding contract (static int here — the
    sharded solver's shapes are static). ``interpret`` defaults to
    "not on TPU".
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.metric_project.violation import (
        max_triangle_violation_slab_pallas,
    )

    n = xs.shape[0]
    p = mesh.devices.size
    m = -(-n // (p * block)) * block  # block-aligned apex rows per device
    xa = jnp.pad(xs, ((0, p * m - n), (0, 0)))
    live = n if n_live is None else int(min(n_live, n))
    interp = (jax.default_backend() != "tpu") if interpret is None else interpret

    def local(xs_rep, xa_shard):
        off = jax.lax.axis_index(axis).astype(jnp.int32) * m
        v = max_triangle_violation_slab_pallas(
            xa_shard, off, xs_rep, block=block, block_r=block_r,
            block_c=block_c, interpret=interp, n_live=live,
        )
        return jax.lax.pmax(v, axis)

    # pallas_call carries no replication rule, same as the sharded sweep.
    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(axis)), out_specs=P(),
        check_vma=False,
    )(xs, xa)


def max_violation(dp: DeviceProblem, x, f=None, *, tri=None):
    """Max violation over every constraint family (device scalar).

    ``tri`` optionally injects a precomputed triangle-family violation
    (the sharded psum-max or the Pallas kernel); by default the blocked
    jnp reduction runs on the replicated iterate.
    """
    if tri is None:
        tri = triangle_violation(symmetrize(dp.mask, x), n_live=dp.n_real)
    viol = tri
    ninf = -jnp.inf
    if dp.has_f and f is not None:
        pairv = jnp.where(dp.mask, jnp.abs(x - dp.d) - f, ninf)
        viol = jnp.maximum(viol, jnp.max(pairv))
    if dp.box is not None:
        lo, hi = dp.box
        viol = jnp.maximum(viol, jnp.max(jnp.where(dp.mask, x - hi, ninf)))
        viol = jnp.maximum(viol, jnp.max(jnp.where(dp.mask, lo - x, ninf)))
    return jnp.maximum(viol, 0.0)


def qp_objective(dp: DeviceProblem, x, f=None):
    """c'v + (eps/2) v'Wv over the upper triangle (MetricQP.qp_objective)."""
    m = dp.mask
    val = jnp.sum(jnp.where(m, dp.c_x * x + 0.5 * dp.eps * dp.w * x * x, 0.0))
    if dp.has_f:
        val = val + jnp.sum(
            jnp.where(m, dp.c_f * f + 0.5 * dp.eps * dp.w_f * f * f, 0.0)
        )
    return val


def lp_objective(dp: DeviceProblem, x):
    """Σ w |x - d| over the upper triangle (MetricQP.lp_objective)."""
    return jnp.sum(jnp.where(dp.mask, dp.w * jnp.abs(x - dp.d), 0.0))


def duality_gap(dp: DeviceProblem, x, f, ypair, ybox):
    """gap = c'v + eps v'Wv + b'y, from the Dykstra dual invariant
    (DESIGN.md §1). Triangle constraints have b = 0 — their b'y term is
    zero *by construction*, which is exactly why the gap never needs the
    triangle duals, dense or slab-native. Pair/box terms come from the
    (2, n, n) dual matrices.
    """
    m = dp.mask
    val = jnp.sum(jnp.where(m, dp.c_x * x + dp.eps * dp.w * x * x, 0.0))
    if dp.has_f:
        val = val + jnp.sum(
            jnp.where(m, dp.c_f * f + dp.eps * dp.w_f * f * f, 0.0)
        )
        # pair 0: x - f <= d  (b = +d); pair 1: -x - f <= -d  (b = -d)
        val = val + jnp.sum(jnp.where(m, dp.d * ypair[0], 0.0))
        val = val - jnp.sum(jnp.where(m, dp.d * ypair[1], 0.0))
    if dp.box is not None:
        lo, hi = dp.box
        val = val + hi * jnp.sum(jnp.where(m, ybox[0], 0.0))
        val = val - lo * jnp.sum(jnp.where(m, ybox[1], 0.0))
    return val


def triangle_dual_stats(yd, valid_masks):
    """Summary stats of schedule-native triangle dual slabs, reduced
    slab-native — the dense (n, n, n) tensor is never formed.

    ``valid_masks`` (schedule.slab_valid_masks) marks real dual cells;
    padding cells carry don't-care values under fused execution
    (DESIGN.md §4) and must not leak into the reductions. On
    ghost-padded problems pass the ghost-aware masks
    (``slab_valid_masks(layout, n_real)``) — ghost-set cells are
    don't-care too; the masks may also be traced (the batched engine
    builds them per instance from a traced ``n_real``). Matches
    ``convergence.triangle_dual_stats(duals_to_dense(...))`` exactly: the
    dense tensor's structural zeros floor dual_min at 0 and cap dual_max
    from below at 0, so the slab-native min/max fold a 0 in.
    """
    zero = jnp.zeros((), yd[0].dtype if yd else jnp.float32)
    # 3·C(n, 3) real duals pass int32 range at n ≈ 1626 — count in int64
    # where available (exact counts at that scale require x64).
    cnt_dt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    dual_min, dual_max, l1, active = zero, zero, zero, jnp.zeros((), cnt_dt)
    for y, v in zip(yd, valid_masks):
        v = v.reshape(y.shape)
        dual_min = jnp.minimum(dual_min, jnp.min(jnp.where(v, y, jnp.inf)))
        dual_max = jnp.maximum(dual_max, jnp.max(jnp.where(v, y, -jnp.inf)))
        l1 = l1 + jnp.sum(jnp.where(v, jnp.abs(y), 0.0))
        active = active + jnp.sum(jnp.where(v, y != 0, False), dtype=cnt_dt)
    return {
        "dual_min": dual_min,
        "dual_max": dual_max,
        "dual_l1": l1,
        "active_constraints": active,
    }
