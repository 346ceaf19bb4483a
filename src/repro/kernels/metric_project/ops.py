"""Jitted public wrappers for the metric-projection sweep kernels.

On TPU, ``interpret=False`` compiles the Mosaic kernels; on CPU kernels
execute in interpret mode, which is how they are validated against the
jnp references in tests.

Production entry points — all three route the gen-3 megakernel
(``fused_pass.py``, DESIGN.md §10), one compiled program per bucket
shape with per-instance data as runtime operands:

  * ``fused_bucket_pass``         — solo path (``ParallelSolver``): one
    instance lifted to a unit batch axis.
  * ``fused_bucket_pass_batched`` — serve batch path (``BatchedSolver``):
    a whole (B, ...) bucket in one bucket program; new instances or
    batches never recompile (gains/masks are operands).
  * ``fused_bucket_pass_sharded`` — sharded path (``ShardedSolver``,
    inside ``shard_map``): one device's contiguous share of every
    diagonal's lanes, with the per-diagonal delta matrix all-reduced over
    the mesh axis and added to the replicated X inside the bucket program.

Test-oracle / benchmark-only entry points (first-generation per-diagonal
kernel, ``metric_project.py`` — demoted from production routing in PR 6):

  * ``diagonal_sweep``      — six-buffer unfolded contract (matches
    ref.sweep_ref); kernel-validation oracle (tests/test_kernels.py).
  * ``diagonal_sweep_slab`` — schedule-native folded contract (matches
    ref.sweep_ref_slab); kept for the kernel_sweep benchmark baseline and
    the gen-1-vs-gen-3 parity test. No solver routes it anymore.

All route through ``jax.jit``-cached wrappers so repeated sweeps of the
same shape never retrace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.metric_project.fused_pass import fused_bucket_pass_pallas
from repro.kernels.metric_project.metric_project import (
    sweep_pallas,
    sweep_pallas_folded,
)
from repro.kernels.metric_project.violation import max_triangle_violation_pallas

__all__ = [
    "diagonal_sweep",
    "diagonal_sweep_slab",
    "fused_bucket_pass",
    "fused_bucket_pass_batched",
    "fused_bucket_pass_sharded",
    "set_default_block_c",
    "triangle_violation",
]

_DEFAULT_BLOCK_C = 128


def set_default_block_c(block_c: int) -> None:
    """Set the lane-tile size (paper Fig. 7 'tile size' analogue)."""
    global _DEFAULT_BLOCK_C
    _DEFAULT_BLOCK_C = int(block_c)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel_mode() -> str:
    """Gen-3 staging engine: the compiled Pallas sweep on TPUs, the
    vmapped jnp sweep under interpret execution (DESIGN.md §10)."""
    return "tpu" if _on_tpu() else "vector"


# eps is static: sweep_pallas bakes it into the kernel body as a python
# float (it is a problem constant, so this never causes retracing).
@functools.partial(jax.jit, static_argnames=("eps", "block_c", "interpret"))
def _sweep_jit(rowb, colb, xik, y0, y1, y2, w_row, w_col, w_ik, active, eps,
               block_c, interpret):
    return sweep_pallas(
        rowb, colb, xik, y0, y1, y2, w_row, w_col, w_ik, active, eps,
        block_c=block_c, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("eps", "block_c", "interpret"))
def _sweep_folded_jit(rowb, colb, xikp, yslab, w_row, w_col, w_ikp, active,
                      seg, eps, block_c, interpret):
    # in_place is safe here: under jit, XLA copies any donated dual buffer
    # that is still live in the caller; fresh buffers are updated in place.
    nrow, ncol, nxikp, n0, n1, n2 = sweep_pallas_folded(
        rowb, colb, xikp, yslab[0], yslab[1], yslab[2],
        w_row, w_col, w_ikp, active, seg, eps,
        block_c=block_c, interpret=interpret, in_place=True,
    )
    return nrow, ncol, nxikp, jnp.stack([n0, n1, n2])


def diagonal_sweep(rowb, colb, xik, y0, y1, y2, w_row, w_col, w_ik, active,
                   eps, block_c: int | None = None):
    """Gen-1 kernel, unfolded contract — TEST ORACLE ONLY (validated
    against ref.sweep_ref in tests/test_kernels.py; no production path
    routes it)."""
    bc = block_c or _DEFAULT_BLOCK_C
    return _sweep_jit(
        rowb, colb, xik, y0, y1, y2, w_row, w_col, w_ik, active,
        eps=float(eps), block_c=bc, interpret=not _on_tpu(),
    )


def diagonal_sweep_slab(rowb, colb, xikp, yslab, w_row, w_col, w_ikp, active,
                        seg, eps, block_c: int | None = None):
    """Gen-1 kernel, schedule-native folded contract — TEST ORACLE /
    BENCHMARK BASELINE ONLY (the kernel_sweep benchmark and the
    gen-1-vs-gen-3 parity test; no solver routes it since PR 6)."""
    bc = block_c or _DEFAULT_BLOCK_C
    return _sweep_folded_jit(
        rowb, colb, xikp, yslab, w_row, w_col, w_ikp, active, seg,
        eps=float(eps), block_c=bc, interpret=not _on_tpu(),
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "interpret", "mode", "unroll"),
    inline=True,
)
def _fused_pass_jit(x, yslab, lanes, g_row, g_col, g_sel, dinv, act, seg,
                    geom, block_c, interpret, mode, unroll):
    # in_place (the sweep kernel writes its dual block over its input) is
    # safe here: under jit, XLA copies any donated buffer that is still
    # live in the caller. All per-instance data are operands, so every
    # solo/batched/sharded call of one bucket shape hits this one cache
    # entry — zero recompiles across instances (the §10 contract, pinned
    # by tests).
    # inline=True: when a runner jits a whole pass/chunk around this call
    # (BatchedSolver chunks, ShardedSolver passes), the bucket program is
    # inlined into the enclosing jaxpr instead of staying an opaque pjit
    # call — XLA then fuses across bucket boundaries, which is worth ~5%
    # per chunked pass; top-level calls still hit this cache as before.
    return fused_bucket_pass_pallas(
        x, yslab, lanes, g_row, g_col, g_sel, dinv, act, seg, geom,
        block_c=block_c, interpret=interpret, in_place=True, mode=mode,
        unroll=unroll,
    )


def triangle_violation(xs, block: int = 8, block_r: int = 128,
                       block_c: int | None = None,
                       n_live: int | None = None):
    """Max triangle slack of the symmetric iterate (the convergence
    engine's probe; DESIGN.md §7) backed by the lane-blocked 3-D-grid
    Pallas kernel (apex blocks × column blocks × streamed row blocks —
    works at n ≫ 10³ without a VMEM-resident (n, n) matrix); drop-in for
    ``metrics_device.triangle_violation``. ``block_c`` is the lane
    (column) block width: None keeps one full-width column block (the
    pre-§14 tiling, right at n ≲ 2·10³); at larger n pick
    ``block_c ≈ VMEM / (4·block·block_r)`` so the per-step slack tile
    stays resident (DESIGN.md §14). ``n_live`` restricts the reduction
    to triangles whose indices are all < n_live — the ghost-padding
    contract (DESIGN.md §8), so padded serve instances run the kernel
    probe too instead of falling back to jnp."""
    return max_triangle_violation_pallas(
        xs, block=block, block_r=block_r,
        block_c=None if block_c is None else int(block_c),
        interpret=not _on_tpu(),
        n_live=None if n_live is None else int(n_live),
    )


def fused_bucket_pass(x, yslab, bucket, block_c: int | None = None,
                      unroll: int = 4):
    """Whole-bucket fused pass backed by the gen-3 megakernel (solo path);
    drop-in for ``ref.fused_bucket_pass_ref``. ``bucket`` is a staged
    bucket dict (``ParallelSolver.staged_buckets``): lane tables
    i/k/s/i2/k2/s2, geometry J/iN/kN, gains g_row/g_col/g_sel/dinv, masks
    act/seg. The instance is lifted to a unit batch axis, so it shares the
    batched path's compiled program."""
    bc = block_c or _DEFAULT_BLOCK_C
    lanes = jnp.stack(
        [bucket[key] for key in ("i", "k", "s", "i2", "k2", "s2")]
    )
    geom = (bucket["J"], bucket["iN"], bucket["kN"])
    one = lambda a: a[None]
    nx, ny = _fused_pass_jit(
        x[None], yslab[None], lanes,
        one(bucket["g_row"]), one(bucket["g_col"]), one(bucket["g_sel"]),
        one(bucket["dinv"]), one(bucket["act"]), bucket["seg"], geom,
        block_c=bc, interpret=not _on_tpu(), mode=_kernel_mode(),
        unroll=int(unroll),
    )
    return nx[0], ny[0]


def fused_bucket_pass_batched(x, yslab, geo, gains,
                              block_c: int | None = None, unroll: int = 4):
    """Whole-bucket fused pass of a B-instance serve batch in one bucket
    program (DESIGN.md §10). ``geo`` holds the bucket's shared
    statics (lane tables ``i/k/s/i2/k2/s2``, geometry ``J/iN/kN``, the
    ``seg`` mask — pure functions of the bucket shape); ``gains`` the
    per-instance operands stacked with a leading B axis
    (``g_row/g_col/g_sel/dinv`` and the ghost-aware ``act`` mask, as
    built by ``BatchedSolver._aux_one``). Per instance the result matches
    ``ref.fused_bucket_pass_ref`` bitwise on every live cell.

    Args:
      x: (B, n, n) iterates.  yslab: (B, D, 3, T, C) dual slabs.

    Returns (new_x, new_yslab).
    """
    bc = block_c or _DEFAULT_BLOCK_C
    lanes = jnp.stack([geo[key] for key in ("i", "k", "s", "i2", "k2", "s2")])
    geom = (geo["J"], geo["iN"], geo["kN"])
    return _fused_pass_jit(
        x, yslab, lanes, gains["g_row"], gains["g_col"], gains["g_sel"],
        gains["dinv"], gains["act"], geo["seg"], geom,
        block_c=bc, interpret=not _on_tpu(), mode=_kernel_mode(),
        unroll=int(unroll),
    )


def fused_bucket_pass_sharded(x, yslab, work, axis_name: str,
                              block_c: int | None = None, unroll: int = 4):
    """One device's share of a whole bucket through the gen-3 megakernel,
    inside ``shard_map`` (DESIGN.md §10): the band engine reads and writes
    X as dense blocks at this device's lane offset, and after each
    diagonal the device's delta matrix is all-reduced over ``axis_name``
    and added to X. Bitwise-equal to the jnp fused sharded path: both
    write the same act-masked deltas into zeros, and the sets of a
    diagonal are conflict-free, so the all-reduce adds +0.0 from every
    other device.

    Args:
      x: (n, n) replicated iterate.  yslab: (D, 3, T, C) this device's
      dual slab.  work: this device's staged bucket — lane tables
      i/k/sizes/i2/k2/sizes2 (D, C), each diagonal's first set ``first``
      (D, 2), geometry J/iN/kN, gains g_row/g_col/g_sel/dinv and masks
      act/seg (D, T, C).

    Returns (new_x, new_yslab).

    Traced straight into the caller's ``shard_map`` program, not through
    the shared ``_fused_pass_jit`` cache: the program holds a collective
    over the caller's mesh, and its padding depends on the mesh's size.
    """
    bc = block_c or _DEFAULT_BLOCK_C
    lanes = jnp.stack(
        [work[key] for key in ("i", "k", "sizes", "i2", "k2", "sizes2")]
    )
    geom = (work["J"], work["iN"], work["kN"])
    one = lambda a: a[None]
    nx, ny = fused_bucket_pass_pallas(
        x[None], yslab[None], lanes,
        one(work["g_row"]), one(work["g_col"]), one(work["g_sel"]),
        one(work["dinv"]), one(work["act"]), work["seg"], geom,
        work["first"], block_c=bc, interpret=not _on_tpu(), in_place=True,
        mode=_kernel_mode(), unroll=int(unroll), axis_name=axis_name,
    )
    return nx[0], ny[0]
