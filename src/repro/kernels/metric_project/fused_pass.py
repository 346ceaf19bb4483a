"""Pallas TPU megakernel, third generation: one batch- and shard-aware
fused-pass engine behind every sweep path (DESIGN.md §10).

The second-generation kernel (DESIGN.md §4, superseded) fused a whole
bucket into one ``pallas_call`` but baked the staged projection gains and
act masks into the trace as constants and served exactly one instance per
launch. Gen-3 changes the contract, not the math:

  * **Leading instance axis**: a whole serve bucket of B padded instances
    runs as ONE bucket program.
  * **Weights as runtime operands**: the staged gains ``g_row / g_col /
    g_sel / dinv`` and the per-instance (ghost-aware) ``act`` masks arrive
    with a leading batch axis as ordinary operands, never trace constants —
    new instances/batches NEVER trigger recompilation (the §8
    weights-as-operands re-partitioning applied to the kernel itself).
    Only the lane tables, the ``seg`` masks and the folded geometry — pure
    functions of the bucket shape — stay shared.
  * **Delta-output mode** (``out_delta=True``, single diagonal): instead of
    updating X in place the engine scatters the act-masked deltas into a
    zero buffer — exactly the per-device delta matrix the sharded solver
    psum-merges per diagonal (bitwise-equal to the jnp fused path's
    scatter, because both scatter the same ``where(act, new - old, 0)``
    values into zeros).

Both staging engines walk the bucket's diagonals in one loop, carrying
the dual slab and updating it in place. Per diagonal they gather the folded X row/column/carry slices in XLA
(the indexing ``ref.fused_bucket_pass_ref`` uses), sweep them, and
scatter the act-masked deltas back in XLA. They differ only in the sweep:

  * ``mode="tpu"`` (TPU production, the only engine compiled with
    ``interpret=False``): the sweep is a Pallas kernel (``_sweep_kernel``)
    over ``(T, block_c)`` lane tiles of the gathered slices, dual slab,
    gains and masks; grid ``(B, lane blocks)``. Step t reads and writes
    whole rows ``ref[pl.ds(t, 1), :]`` of lane-aligned blocks, so every
    VMEM access is tile-aligned. That is the shape Mosaic accepts: the
    in-kernel per-lane gathers of the earlier engine (windows at dynamic
    lane/sublane offsets, single-row dynamic stores, value
    ``dynamic_slice``) are all refused by the TPU compiler (DESIGN.md §10).
    Nothing of X lives in VMEM and no lane table lives in SMEM.
  * ``mode="vector"`` (CPU / interpret only): the sweep is
    ``ref.fused_diag_sweep`` vmapped over B. With one lane block (every
    production bucket) the bucket program is plain XLA; with several it
    keeps a pallas grid of one diagonal per step (``_fused_kernel_vector``)
    in interpret mode.

VMEM budget (tpu mode, per grid step): 16 ``(T, block_c)`` tiles (11 in,
5 out), double-buffered by the grid pipeline — 32·T·block_c·4 bytes, so
12.6 MiB at T = 768, block_c = 128. The vector engine holds
B·n² floats and is CPU-only by construction.

Exactness note shared by both engines: every scatter outside a lane's
active cells adds an exact 0.0 (act-masked deltas; carry deltas guarded
by ``sizes > 0``), so overlapping windows / wrapped padding indices only
ever add zeros — and X cells are never -0.0 (they start at +0.0 and only
accumulate sums), so zero-adds are bitwise no-ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.metric_project.ref import fused_diag_sweep, fused_step

__all__ = ["fused_bucket_pass_pallas"]


def _sweep_kernel(
    row_ref,    # (T, Cb) folded row slices x[i, j]
    col_ref,    # (T, Cb) folded column slices x[j, k]
    xik_ref,    # (2, Cb) the two folded x_ik carries
    y_ref,      # (3, T, Cb) dual block
    grow_ref,   # (T, Cb) staged gains (runtime operands)
    gcol_ref,
    gsel_ref,
    dinv_ref,
    act_ref,    # (T, Cb) int32 per-instance (ghost-aware) step mask
    seg_ref,    # (T, Cb) int32 shared segment mask
    orow_ref,   # (T, Cb) swept row slices
    ocol_ref,   # (T, Cb) swept column slices
    oxik_ref,   # (2, Cb) final carries
    oy_ref,     # (3, T, Cb) new duals (aliases y_ref when in place)
    *,
    T: int,
    unroll: int,
):
    """Sequential-in-t sweep of one lane block — ``ref.fused_diag_sweep``
    restated on refs, op for op (the shared ``fused_step``)."""

    def body(t, carry):
        xa, xb = carry  # (1, Cb)
        r = pl.ds(t, 1)
        xij, xjk = row_ref[r, :], col_ref[r, :]
        sg = seg_ref[r, :] != 0
        xc = jnp.where(sg, xb, xa)
        nij, nik, njk, t0, t1, t2 = fused_step(
            xij, xc, xjk, y_ref[0, r, :], y_ref[1, r, :], y_ref[2, r, :],
            grow_ref[r, :], gsel_ref[r, :], gcol_ref[r, :], dinv_ref[r, :],
        )
        orow_ref[r, :] = nij
        ocol_ref[r, :] = njk
        oy_ref[0, r, :] = t0
        oy_ref[1, r, :] = t1
        oy_ref[2, r, :] = t2
        nik = jnp.where(act_ref[r, :] != 0, nik, xc)
        return jnp.where(sg, xa, nik), jnp.where(sg, nik, xb)

    # Mosaic unrolls a loop fully or not at all, so the partial unroll is
    # spelled out: ``unroll`` steps per trip, then the remainder.
    def body_u(i, carry):
        for k in range(unroll):
            carry = body(i * unroll + k, carry)
        return carry

    carry = (xik_ref[0:1, :], xik_ref[1:2, :])
    carry = jax.lax.fori_loop(0, T // unroll, body_u, carry)
    xa, xb = jax.lax.fori_loop(T // unroll * unroll, T, body, carry)
    oxik_ref[0:1, :] = xa
    oxik_ref[1:2, :] = xb


def _sweep_tiles(rowb, colb, xikp, y, g_row, g_col, g_sel, dinv, act, seg,
                 *, block_c: int, interpret: bool, unroll: int,
                 alias: bool):
    """The tpu engine's sweep of one diagonal for B instances.

    Shapes: (B, T, C) rowb/colb/gains/act, (B, 2, C) xikp, (B, 3, T, C)
    y, (T, C) seg. Same results as ``fused_diag_sweep`` vmapped over B.
    The lane axis is one block when C <= block_c; otherwise it is padded
    to a multiple of ``block_c`` (a multiple of 128, the TPU lane tile)."""
    B, T, C = rowb.shape
    bc = C if C <= block_c else block_c
    if bc != C and bc % 128:
        raise ValueError(
            f"block_c={block_c} must be a multiple of 128 to tile C={C} lanes"
        )
    Cp = -(-C // bc) * bc

    def padc(a, fill=0):
        if a.shape[-1] == Cp:
            return a
        pad = [(0, 0)] * (a.ndim - 1) + [(0, Cp - C)]
        return jnp.pad(a, pad, constant_values=fill)

    # Masks ship as int32: a dynamic single-row load of a packed (int8 or
    # bool) tile is not a whole-tile access on the TPU.
    mask = lambda m: padc(m.astype(jnp.int32))
    tile = pl.BlockSpec((None, T, bc), lambda b, c: (b, 0, c))
    pair = pl.BlockSpec((None, 2, bc), lambda b, c: (b, 0, c))
    duals = pl.BlockSpec((None, 3, T, bc), lambda b, c: (b, 0, 0, c))
    shared = pl.BlockSpec((T, bc), lambda b, c: (0, c))
    dt = rowb.dtype
    sds = lambda *s: jax.ShapeDtypeStruct(s, dt)
    # 16 (T, bc) tiles double-buffered, plus headroom for Mosaic's own
    # scratch; v5e has 128 MiB of VMEM.
    vmem = 32 * T * bc * dt.itemsize + (8 << 20)
    nrow, ncol, nxikp, ny = pl.pallas_call(
        functools.partial(_sweep_kernel, T=T, unroll=unroll),
        grid=(B, Cp // bc),
        in_specs=[tile, tile, pair, duals] + [tile] * 5 + [shared],
        out_specs=[tile, tile, pair, duals],
        out_shape=[sds(B, T, Cp), sds(B, T, Cp), sds(B, 2, Cp),
                   sds(B, 3, T, Cp)],
        input_output_aliases={3: 3} if alias else {},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(min(vmem, 100 << 20))
        ),
        interpret=interpret,
        name="metric_sweep",
    )(
        padc(rowb), padc(colb), padc(xikp), padc(y), padc(g_row, 1.0),
        padc(g_col, 1.0), padc(g_sel, 1.0), padc(dinv, 1.0), mask(act),
        mask(seg),
    )
    return nrow[..., :C], ncol[..., :C], nxikp[..., :C], ny[..., :C]


def _vector_sweep(unroll: int):
    """The vector engine's sweep: the jnp reference vmapped over B (seg
    shared)."""
    one = functools.partial(fused_diag_sweep, unroll=unroll)
    return jax.vmap(one, in_axes=(0,) * 9 + (None,))


def _gather_diag(xb, lane, geo):
    """Folded row/column slices and the two carries of one diagonal of one
    instance — the gathers of ``ref.fused_bucket_pass_ref``."""
    i1, k1, _, i2, k2, _ = lane
    J, iN, kN = geo
    rowb = xb.at[iN, J].get(mode="fill", fill_value=0.0)
    colb = xb.at[J, kN].get(mode="fill", fill_value=0.0)
    xikp = jnp.stack([
        xb.at[i1, k1].get(mode="fill", fill_value=0.0),
        xb.at[i2, k2].get(mode="fill", fill_value=0.0),
    ])
    return rowb, colb, xikp


def _scatter_diag(outb, lane, geo, ab, rowb, colb, xikp, nrow, ncol, nxikp):
    """Act-masked deltas of one swept diagonal added into ``outb`` — the
    scatters of ``ref.fused_bucket_pass_ref``."""
    i1, k1, s1, i2, k2, s2 = lane
    J, iN, kN = geo
    add = lambda a, idx, v: a.at[idx].add(
        v, mode="drop", unique_indices=True
    )
    outb = add(outb, (iN, J), jnp.where(ab, nrow - rowb, 0))
    outb = add(outb, (J, kN), jnp.where(ab, ncol - colb, 0))
    outb = add(outb, (i1, k1), jnp.where(s1 > 0, nxikp[0] - xikp[0], 0))
    outb = add(outb, (i2, k2), jnp.where(s2 > 0, nxikp[1] - xikp[1], 0))
    return outb


def _diag_step(xv, out, lane, geo, segv, yv, grow, gcol, gsel, dinv, actv,
               sweep):
    """One diagonal for B instances: gather (XLA), ``sweep``, scatter
    (XLA). ``xv`` is the gather source, ``out`` the scatter target (the
    same values in in-place mode; zeros in delta mode)."""
    with jax.named_scope("repro.bucket.gather"):
        rowb, colb, xikp = jax.vmap(
            lambda xb: _gather_diag(xb, lane, geo)
        )(xv)
    with jax.named_scope("repro.bucket.sweep"):
        nrow, ncol, nxikp, ny = sweep(
            rowb, colb, xikp, yv, grow, gcol, gsel, dinv, actv, segv
        )
    scatter = lambda ob, *a: _scatter_diag(ob, lane, geo, *a)
    with jax.named_scope("repro.bucket.scatter"):
        out = jax.vmap(scatter)(
            out, actv, rowb, colb, xikp, nrow, ncol, nxikp
        )
    return out, ny


def _bucket_loop(x, yslab, lanes, g_row, g_col, g_sel, dinv, act, seg,
                 geom, *, sweep, out_delta):
    """One loop over the bucket's diagonals, each a ``_diag_step`` on the
    whole batch — the bucket program of both engines. The dual slab is
    carried and updated in place, one diagonal at a time."""
    D = yslab.shape[1]
    at = lambda a, ax, d: jax.lax.dynamic_index_in_dim(
        a, d, ax, keepdims=False
    )

    @jax.named_scope("repro.bucket")
    def diag(d, carry):
        xc, out, y = carry
        out2, ny = _diag_step(
            xc, out, at(lanes, 1, d), tuple(at(g, 0, d) for g in geom),
            at(seg, 0, d) != 0, at(y, 1, d), at(g_row, 1, d),
            at(g_col, 1, d), at(g_sel, 1, d), at(dinv, 1, d),
            at(act, 1, d) != 0, sweep,
        )
        y = jax.lax.dynamic_update_index_in_dim(y, ny, d, 1)
        # Delta mode gathers from the pristine X every diagonal (D == 1
        # by contract); in-place mode threads the iterate.
        return (xc if out_delta else out2, out2, y)

    out0 = jnp.zeros_like(x) if out_delta else x
    _, nx, ny = jax.lax.fori_loop(0, D, diag, (x, out0, yslab))
    return nx, ny


def _fused_kernel_vector(
    lanes_ref,  # (6, D, Cp) int32 scalar-prefetch lane tables
    x_ref,      # (B, n, n) whole batch (resident)
    y_ref,      # (B, 1, 3, T, Cb)
    grow_ref,   # (B, 1, T, Cb) per-instance staged gains
    gcol_ref,
    gsel_ref,
    dinv_ref,
    act_ref,    # (B, 1, T, Cb) per-instance step mask
    seg_ref,    # (1, T, Cb) shared segment mask
    geom_ref,   # (3, 1, T, Cb) int32 folded geometry: J, iN, kN
    ox_ref,     # (B, n, n) working buffer: X, or the delta matrices
    oy_ref,     # (B, 1, 3, T, Cb)
    *,
    block_c: int,
    unroll: int,
    out_delta: bool,
):
    d = pl.program_id(1)
    cb = pl.program_id(2)

    @pl.when((d == 0) & (cb == 0))
    def _init_x():
        ox_ref[...] = (
            jnp.zeros(ox_ref.shape, ox_ref.dtype) if out_delta
            else x_ref[...]
        )

    col0 = cb * block_c
    lane = jax.lax.dynamic_slice(
        lanes_ref[...], (jnp.int32(0), d, col0), (6, 1, block_c)
    ).reshape(6, block_c)
    xv = x_ref[...] if out_delta else ox_ref[...]
    base = ox_ref[...] if out_delta else xv
    nxv, ny = _diag_step(
        xv, base, lane, geom_ref[...][:, 0], seg_ref[0] != 0,
        y_ref[...][:, 0], grow_ref[...][:, 0], gcol_ref[...][:, 0],
        gsel_ref[...][:, 0], dinv_ref[...][:, 0], act_ref[...][:, 0] != 0,
        _vector_sweep(unroll),
    )
    ox_ref[...] = nxv
    oy_ref[...] = ny[:, None]


def _vector_tiled_pass(x, yslab, lanes, g_row, g_col, g_sel, dinv, act,
                       seg, geom, *, block_c, unroll, out_delta):
    """Multi-block vector engine: a pallas grid of (diagonal, lane block)
    steps in interpret mode. The engine gathers/scatters by index with
    fill/drop semantics, so neither the lane axis nor X needs padding
    beyond whole lane blocks."""
    B, n, _ = x.shape
    _, D, _, T, C = yslab.shape
    bc = block_c
    Cp = -(-C // bc) * bc

    def padc(a, fill):
        if a.shape[-1] == Cp:
            return a
        pad = [(0, 0)] * (a.ndim - 1) + [(0, Cp - C)]
        return jnp.pad(a, pad, constant_values=fill)

    lanes_p = jnp.concatenate(
        [padc(lanes[:2], -1), padc(lanes[2:3], 0),
         padc(lanes[3:5], -1), padc(lanes[5:6], 0)], axis=0
    )
    x_spec = pl.BlockSpec((B, n, n), lambda b, d, c, s: (0, 0, 0))
    y_spec = pl.BlockSpec(
        (B, 1, 3, T, bc), lambda b, d, c, s: (0, d, 0, 0, c)
    )
    tc_spec = pl.BlockSpec((B, 1, T, bc), lambda b, d, c, s: (0, d, 0, c))
    seg_spec = pl.BlockSpec((1, T, bc), lambda b, d, c, s: (d, 0, c))
    geo_spec = pl.BlockSpec((3, 1, T, bc), lambda b, d, c, s: (0, d, 0, c))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1, D, Cp // bc),
        in_specs=[x_spec, y_spec] + [tc_spec] * 5 + [seg_spec, geo_spec],
        out_specs=[x_spec, y_spec],
    )
    nx, ny = pl.pallas_call(
        functools.partial(
            _fused_kernel_vector, block_c=bc, unroll=unroll,
            out_delta=out_delta,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n, n), x.dtype),
            jax.ShapeDtypeStruct((B, D, 3, T, Cp), x.dtype),
        ],
        interpret=True,
    )(
        lanes_p, x, padc(yslab, 0), padc(g_row, 1.0), padc(g_col, 1.0),
        padc(g_sel, 1.0), padc(dinv, 1.0), padc(act, 0), padc(seg, 0),
        padc(jnp.stack(geom), -1),
    )
    return nx, ny[..., :C]


def fused_bucket_pass_pallas(
    x,
    yslab,
    lanes,
    g_row,
    g_col,
    g_sel,
    dinv,
    act,
    seg,
    geom,
    *,
    block_c: int = 128,
    interpret: bool = True,
    in_place: bool = False,
    mode: str = "vector",
    unroll: int = 4,
    out_delta: bool = False,
):
    """One fused pass over a whole bucket of B instances; per instance it
    matches ``ref.fused_bucket_pass_ref`` bitwise on every live cell.

    Args:
      x: (B, n, n) iterates.
      yslab: (B, D, 3, T, C) schedule-native dual slabs.
      lanes: (6, D, C) int32 — i1, k1, s1, i2, k2, s2 lane tables, shared
        across the batch.
      g_row/g_col/g_sel/dinv: (B, D, T, C) per-instance staged gains —
        runtime operands, never trace constants.
      act: (B, D, T, C) per-instance (ghost-aware) step masks.
      seg: (D, T, C) shared segment mask.
      geom: the folded geometry J, iN, kN — three (D, T, C) int32
        arrays (a sequence, or one stacked (3, D, T, C) array).
      block_c: lane block of the sweep (tpu mode: C itself, or a multiple
        of 128 when C is wider).
      mode: "tpu" (Pallas sweep kernel; the TPU engine) or "vector"
        (interpret-only vmapped jnp sweep). Same contract, same results.
      unroll: unroll of the sequential-in-t sweep loop.
      in_place: alias the dual slab input→output inside the sweep kernel
        (enable under jit only, like the earlier generations).
      out_delta: return the act-masked update deltas scattered into zeros
        instead of the updated X (requires D == 1 — the sharded solver's
        per-diagonal psum contract). X is read-only; duals still update.

    Returns (new_x, new_yslab) — (B, n, n) and (B, D, 3, T, C); new_x is
    the delta matrix batch when ``out_delta``.
    """
    if mode not in ("tpu", "vector"):
        raise ValueError(f"unknown megakernel mode {mode!r}")
    if mode == "vector" and not interpret:
        raise ValueError(
            "the vector engine runs in interpret mode only; compiled "
            "kernels use mode='tpu'"
        )
    D, C = yslab.shape[1], yslab.shape[-1]
    if out_delta and D != 1:
        raise ValueError("out_delta requires a single-diagonal call (D=1)")
    geom = tuple(g.astype(jnp.int32) for g in geom)
    operands = (x, yslab, lanes, g_row, g_col, g_sel, dinv, act, seg, geom)
    if mode == "tpu":
        sweep = functools.partial(
            _sweep_tiles, block_c=block_c, interpret=interpret,
            unroll=unroll, alias=in_place,
        )
    elif block_c >= C:
        # Single lane block: the vector engine runs as plain XLA — a
        # pallas grid of one step would add only whole-buffer copies
        # around the identical body.
        sweep = _vector_sweep(unroll)
    else:
        return _vector_tiled_pass(
            *operands, block_c=block_c, unroll=unroll, out_delta=out_delta
        )
    return _bucket_loop(*operands, sweep=sweep, out_delta=out_delta)
