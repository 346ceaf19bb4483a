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
  * **Sharded mode** (``axis_name`` given, inside ``shard_map``): the
    lanes are this device's contiguous share of each diagonal
    (``schedule.build_layout`` deals device r the lanes r·C … r·C + C − 1).
    Instead of updating X in place the engine adds the act-masked deltas
    into a zero buffer, which is all-reduced over the axis after each
    diagonal and added to X — exactly the per-device delta matrix and
    merge of the jnp sharded path (bitwise-equal, because both write the
    same ``where(act, new - old, 0)`` values into zeros, and the other
    devices add +0.0 to every cell, the diagonal's sets being
    conflict-free).

Both modes walk the bucket's diagonals in one loop, carrying
the dual slab and updating it in place. Per diagonal they read the
folded X row/column/carry slices in XLA, sweep them, and add the
act-masked deltas back in XLA. The band engine reads and writes X as
dense blocks at offsets fixed per diagonal (moved by the device's first
lane in sharded mode), skewed by a static shear; the tiled vector engine
gathers and scatters by the element index tables of
``ref.fused_bucket_pass_ref`` (the index engine). The engines differ only
in the sweep:

  * ``mode="tpu"`` (TPU production, the only engine compiled with
    ``interpret=False``): the sweep is a Pallas kernel (``_sweep_kernel``)
    over ``(T, block_c)`` lane tiles of the gathered slices, dual slab,
    gains and masks; grid ``(B, lane blocks)``. Step t reads and writes
    whole rows ``ref[pl.ds(t, 1), :]`` of lane-aligned blocks, so every
    VMEM access is tile-aligned. That is the shape Mosaic accepts: the
    in-kernel per-lane gathers of the earlier engine (windows at dynamic
    lane/sublane offsets, single-row dynamic stores, value
    ``dynamic_slice``) are all refused by the TPU compiler (DESIGN.md §10).
    Nothing of X lives in VMEM and no lane table lives in SMEM. The band
    engine's row blocks are transposed by a second small Pallas call.
  * ``mode="vector"`` (CPU / interpret only): the sweep is
    ``ref.fused_diag_sweep`` vmapped over B. With one lane block (every
    production bucket) the bucket program is plain XLA; with several it
    keeps a pallas grid of one diagonal per step (``_fused_kernel_vector``)
    in interpret mode.

VMEM budget (tpu mode, per grid step): 16 ``(T, block_c)`` tiles (11 in,
5 out), double-buffered by the grid pipeline — 32·T·block_c·4 bytes, so
12.6 MiB at T = 768, block_c = 128. The vector engine holds
B·n² floats and is CPU-only by construction.

Exactness note shared by both engines: every write outside a lane's
active cells adds an exact 0.0 (act-masked deltas; carry deltas guarded
by ``sizes > 0``), so overlapping blocks / wrapped padding indices only
ever add zeros — and X cells are never -0.0 (they start at +0.0 and only
accumulate sums), so zero-adds are bitwise no-ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
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


def _transpose_kernel(a_ref, o_ref):
    o_ref[...] = a_ref[...].T


def _transpose_tiles(a, *, interpret: bool):
    """(B, R, C) -> (B, C, R) as a Pallas call. The tpu engine transposes
    the band engine's row blocks here: an XLA transpose lets the TPU
    compiler lay the whole padded X out column-major around the row
    blocks' writes, two copies of X on every diagonal."""
    B, R, C = a.shape
    return pl.pallas_call(
        _transpose_kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec((None, R, C), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((None, C, R), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, C, R), a.dtype),
        interpret=interpret,
        name="band_transpose",
    )(a)


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


def _index_engine(lane, geo):
    """Gather and scatter of one diagonal by the element index tables
    ``J/iN/kN`` (the tiled vector engine)."""

    def gather(xv):
        return jax.vmap(lambda xb: _gather_diag(xb, lane, geo))(xv)

    def scatter(out, *args):
        one = lambda ob, *a: _scatter_diag(ob, lane, geo, *a)
        return jax.vmap(one)(out, *args)

    return gather, scatter


# ---------------------------------------------------------------------------
# Band engine (DESIGN.md §10): the gathers and scatters of a diagonal's
# lanes as dense blocks of X.
#
# Diagonal d holds the sets S_{x+c, z-c}, c < C_d, and its lane f holds set
# f (segment A) and set C_d-1-f (segment B). With r0 = x - s + C_d + 1,
# s = z - x, every operand of lane f is column f of a block of X read at a
# per-diagonal offset, "sheared" up by f rows:
#
#   row A  X[x+f, x+f+1+t]          block X[x : x+F, x+1 : x+1+T], transposed
#   row B  X[x+C_d-1-f, r0+f+t]     block X[x+C_d-F : x+C_d, r0 : r0+T+F],
#                                   rows flipped, transposed
#   col A  X[x+f+1+t, z-f]          block X[x : x+T+1, z-F+1 : z+1], columns
#                                   flipped; its row 0 is the carry X[x+f, z-f]
#   col B  X[r0+f+t, z-C_d+1+f]     block X[r0 : r0+T+F, z-C_d+1 : +F]
#
# and the B carry X[x+C_d-1-f, z-C_d+1+f] is row h = 2(s - C_d) of the
# sheared row-B block (h is the height of every paired lane). F is the
# bucket's lane count. A block row or column outside a lane's set holds
# other cells of X (or padding): the sweep reads them only at steps
# ``act`` masks, and the write-back adds an exact +0.0 there.
#
# A device that holds lanes f0 … f0+F-1 of the diagonal (sharded mode,
# f0 = rank·F) reads the same blocks moved f0 steps along their own
# diagonals: rows A and columns B down and right, row B up and right,
# column A down and left. The offsets come from the diagonal's first set
# (x, z), which the device need not hold, and f0.
# ---------------------------------------------------------------------------


def _band_pads(n: int, T: int, F: int):
    """(top, bottom, left, right) padding of X under which no block of any
    diagonal of a bucket of height T and F lanes leaves the padded matrix;
    in sharded mode F counts every device's lanes, and the blocks of each
    device's run of them stay inside too.

    ``lax.dynamic_slice`` clamps a start that would run off the end, which
    moves the whole window. The bounds follow from 0 <= x, z <= n-1,
    2 <= s <= min(n-1, T+1, 4F+1) (lane 0 is s-1 steps long, and a lane
    holds at most two sets) and C_d = floor(s/2) >= 1: the most negative
    start is r0 >= 1 - ceil(s/2) (segment B), or 1 - F for the row-B rows
    and 3 - F for the col-A columns; the B blocks, T+F long, end at most
    T+F-3 past n-1."""
    s = min(n - 1, T + 1, 4 * F + 1)
    half = (s + 1) // 2 - 1
    far = max(T + F - 3, F - 1, 0)
    return max(F - 1, half, 0), far, max(F - 3, half, 0), far


def _shear(a, sign: int):
    """Shift column f of ``a`` (..., R, F) along R by ``sign``·f rows,
    filling with zeros: ``sign = -1`` gives out[t, f] = a[t + f, f],
    ``+1`` gives out[t, f] = a[t - f, f]. A barrel of static shifts and
    selects, one per bit of F - 1."""
    f = jnp.arange(a.shape[-1])
    zero = jnp.zeros((), a.dtype)
    for b in range((a.shape[-1] - 1).bit_length()):
        k = sign << b
        cfg = [(0, 0, 0)] * a.ndim
        cfg[-2] = (k, -k, 0)
        a = jnp.where((f >> b) & 1 == 1, jax.lax.pad(a, zero, cfg), a)
    return a


def _band_blocks(x, z, T: int, F: int, f0=0):
    """The blocks of lanes f0 … f0+F-1 of the diagonal whose first set is
    S_{x,z}: name -> (row, column) of its corner in X, name -> its (rows,
    columns), and the row h of the sheared row-B block that holds the B
    carries."""
    s = z - x
    c = (s - 2) // 2 + 1
    r0 = x - s + c + 1
    at = {"row_a": (x, x + 1), "row_b": (x + c - F, r0),
          "col_a": (x, z - F + 1), "col_b": (r0, z - c + 1)}
    if not (isinstance(f0, int) and f0 == 0):  # a static 0 adds no op
        step = {"row_a": (1, 1), "row_b": (-1, 1),
                "col_a": (1, -1), "col_b": (1, 1)}
        at = {k: (r + step[k][0] * f0, col + step[k][1] * f0)
              for k, (r, col) in at.items()}
    size = {"row_a": (F, T), "row_b": (F, T + F),
            "col_a": (T + 1, F), "col_b": (T + F, F)}
    return at, size, 2 * (s - c)


def _band_engine(lane, segv, pads, transpose, first=None, f0=0):
    """Gather, scatter and clear of one diagonal's lanes as dense blocks
    of a padded batch ``xp`` (B, n + pads, n + pads); see the table above.
    Nothing here is indexed per element. ``pads`` is the (top, left)
    padding; ``transpose`` swaps the last two axes of a (B, R, C) array.
    The lanes are f0 … f0+F-1 of the diagonal whose first set (x, z) is
    ``first``; None reads it from lane 0 (f0 = 0)."""
    top, left = pads
    T, F = segv.shape
    i1, k1, s1, _, _, s2 = lane
    x, z = (i1[0], k1[0]) if first is None else (first[0], first[1])
    at, size, h = _band_blocks(x, z, T, F, f0)
    tr = transpose
    ident = lambda a: a
    # block -> its (R, F) frame (lane f in column f), and back
    to_frame = {
        "row_a": tr, "row_b": lambda a: tr(a[:, ::-1]),
        "col_a": lambda a: a[:, :, ::-1], "col_b": ident,
    }
    from_frame = {
        "row_a": tr, "row_b": lambda a: tr(a)[:, ::-1],
        "col_a": lambda a: a[:, :, ::-1], "col_b": ident,
    }

    def start(name):
        r, col = at[name]
        return (jnp.int32(0), r + top, col + left)

    def gather(xp):
        B = xp.shape[0]
        fr = {
            k: _shear(to_frame[k](jax.lax.dynamic_slice(
                xp, start(k), (B,) + size[k])), -1)
            for k in at
        }
        rowb = jnp.where(segv, fr["row_b"][:, :T], fr["row_a"])
        colb = jnp.where(segv, fr["col_b"][:, :T], fr["col_a"][:, 1:])
        xb = jax.lax.dynamic_index_in_dim(fr["row_b"], h, 1, keepdims=False)
        return rowb, colb, jnp.stack([fr["col_a"][:, 0], xb], axis=1)

    def scatter(xp, ab, rowb, colb, xikp, nrow, ncol, nxikp):
        live_a, live_b = ab & ~segv, ab & segv
        d = lambda new, old, m: jnp.where(m, new - old, 0)
        tail = lambda a: jnp.pad(a, ((0, 0), (0, F), (0, 0)))
        dxa = d(nxikp[:, 0], xikp[:, 0], s1 > 0)
        dxb = d(nxikp[:, 1], xikp[:, 1], s2 > 0)
        fr = {
            "row_a": d(nrow, rowb, live_a),
            # Row h of the B frame is past every lane's last step: only
            # the B carry lives there. (A single-set diagonal has no B
            # segment; its dxb is 0 wherever row h lands.)
            "row_b": jax.lax.dynamic_update_index_in_dim(
                tail(d(nrow, rowb, live_b)), dxb, h, 1),
            "col_a": jnp.concatenate(
                [dxa[:, None], d(ncol, colb, live_a)], axis=1),
            "col_b": tail(d(ncol, colb, live_b)),
        }
        # One block after another: blocks of a diagonal may overlap, and
        # outside its set each adds +0.0, so live cells end as the index
        # engine's scatter-add leaves them.
        for k in at:
            u = from_frame[k](_shear(fr[k], 1))
            blk = jax.lax.dynamic_slice(xp, start(k), u.shape)
            xp = jax.lax.dynamic_update_slice(xp, blk + u, start(k))
        return xp

    def clear(xp):
        """``xp`` with this diagonal's blocks set to zero."""
        for k in at:
            zero = jnp.zeros((xp.shape[0],) + size[k], xp.dtype)
            xp = jax.lax.dynamic_update_slice(xp, zero, start(k))
        return xp

    return gather, scatter, clear


def _diag_step(xv, out, engine, segv, yv, grow, gcol, gsel, dinv, actv,
               sweep):
    """One diagonal for B instances: the engine's gather, ``sweep``, the
    engine's scatter. ``xv`` is the gather source, ``out`` the scatter
    target (the same values in in-place mode; zeros in sharded mode)."""
    gather, scatter = engine[:2]
    with jax.named_scope("repro.bucket.gather"):
        rowb, colb, xikp = gather(xv)
    with jax.named_scope("repro.bucket.sweep"):
        nrow, ncol, nxikp, ny = sweep(
            rowb, colb, xikp, yv, grow, gcol, gsel, dinv, actv, segv
        )
    with jax.named_scope("repro.bucket.scatter"):
        out = scatter(out, actv, rowb, colb, xikp, nrow, ncol, nxikp)
    return out, ny


def _bucket_loop(x, yslab, lanes, g_row, g_col, g_sel, dinv, act, seg,
                 first, *, sweep, transpose, axis_name):
    """One loop over the bucket's diagonals, each a ``_diag_step`` on the
    whole batch through the band engine — the bucket program of both
    sweeps. The dual slab is carried and updated in place, one diagonal
    at a time, and X is read from a padded copy that the loop carries
    (padded once per bucket program, stripped at its end).

    In place (``axis_name`` None) each diagonal's deltas are added into
    that copy. In sharded mode they are added into a padded zero buffer
    that the loop carries too; its (n, n) interior is all-reduced over
    ``axis_name`` and added to the interior of X's copy, and the blocks
    just written are zeroed again. Per diagonal, the only whole-matrix
    traffic besides the all-reduce is the interior read out for it and
    X's interior read, added to and written in place: no padded matrix
    is copied."""
    n = x.shape[1]
    D, T, C = seg.shape
    at = lambda a, ax, d: jax.lax.dynamic_index_in_dim(
        a, d, ax, keepdims=False
    )
    f0, lanes_all = 0, C
    if axis_name is not None:
        f0 = jax.lax.axis_index(axis_name) * C
        lanes_all = jax.lax.axis_size(axis_name) * C
    top, bottom, left, right = _band_pads(n, T, lanes_all)
    with jax.named_scope("repro.bucket.gather"):
        x = jnp.pad(x, ((0, 0), (top, bottom), (left, right)))
    inner = lambda a: a[:, top:top + n, left:left + n]

    @jax.named_scope("repro.bucket")
    def diag(d, carry):
        xp, y = carry[0], carry[-1]
        segv = at(seg, 0, d) != 0
        lane = at(lanes, 1, d)
        engine = _band_engine(
            lane, segv, (top, left), transpose,
            None if first is None else at(first, 0, d), f0,
        )
        out, ny = _diag_step(
            xp, xp if axis_name is None else carry[1], engine,
            segv, at(y, 1, d), at(g_row, 1, d),
            at(g_col, 1, d), at(g_sel, 1, d), at(dinv, 1, d),
            # The barrier makes the diagonal's slice of ``act`` a value of
            # its own: without it the TPU compiler copies the whole
            # bucket's act slab to another layout on every diagonal.
            jax.lax.optimization_barrier(at(act, 1, d)) != 0, sweep,
        )
        ny = jax.lax.dynamic_update_index_in_dim(y, ny, d, 1)
        if axis_name is None:
            return out, ny
        # ``out`` holds this device's deltas. The sets are conflict-free,
        # so each cell's delta is non-zero on one device at most and the
        # all-reduce is an exact merge.
        with jax.named_scope("repro.shard.merge"):
            merged = jax.lax.psum(inner(out), axis_name)
            xp = jax.lax.dynamic_update_slice(
                xp, inner(xp) + merged, (0, top, left))
        return xp, engine[2](out), ny

    carry = (x, yslab) if axis_name is None else (
        x, jnp.zeros_like(x), yslab)
    carry = jax.lax.fori_loop(0, D, diag, carry)
    with jax.named_scope("repro.bucket.scatter"):
        nx = inner(carry[0])
    return nx, carry[-1]


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
    ox_ref,     # (B, n, n) working buffer: X
    oy_ref,     # (B, 1, 3, T, Cb)
    *,
    block_c: int,
    unroll: int,
):
    d = pl.program_id(1)
    cb = pl.program_id(2)

    @pl.when((d == 0) & (cb == 0))
    def _init_x():
        ox_ref[...] = x_ref[...]

    col0 = cb * block_c
    lane = jax.lax.dynamic_slice(
        lanes_ref[...], (jnp.int32(0), d, col0), (6, 1, block_c)
    ).reshape(6, block_c)
    xv = ox_ref[...]
    segv = seg_ref[0] != 0
    nxv, ny = _diag_step(
        xv, xv, _index_engine(lane, geom_ref[...][:, 0]), segv,
        y_ref[...][:, 0], grow_ref[...][:, 0], gcol_ref[...][:, 0],
        gsel_ref[...][:, 0], dinv_ref[...][:, 0], act_ref[...][:, 0] != 0,
        _vector_sweep(unroll),
    )
    ox_ref[...] = nxv
    oy_ref[...] = ny[:, None]


def _vector_tiled_pass(x, yslab, lanes, g_row, g_col, g_sel, dinv, act,
                       seg, geom, *, block_c, unroll):
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
        functools.partial(_fused_kernel_vector, block_c=bc, unroll=unroll),
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
    first=None,
    *,
    block_c: int = 128,
    interpret: bool = True,
    in_place: bool = False,
    mode: str = "vector",
    unroll: int = 4,
    axis_name: str | None = None,
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
        arrays (a sequence, or one stacked (3, D, T, C) array); read by
        the tiled vector engine alone.
      first: (D, 2) int32 — the first set (x, z) of each diagonal, which
        sharded mode needs (a device's lanes need not hold it); None
        reads it from lane 0.
      block_c: lane block of the sweep (tpu mode: C itself, or a multiple
        of 128 when C is wider).
      mode: "tpu" (Pallas sweep kernel; the TPU engine) or "vector"
        (interpret-only vmapped jnp sweep). Same contract, same results.
      unroll: unroll of the sequential-in-t sweep loop.
      in_place: alias the dual slab input→output inside the sweep kernel
        (enable under jit only, like the earlier generations).
      axis_name: sharded mode, inside ``shard_map``: the lanes are this
        device's contiguous share of each diagonal, lanes rank·C …
        rank·C + C − 1 along ``axis_name``, and each diagonal's deltas are
        all-reduced over it and added to X (the sharded solver's merge).
        None: one device, X updated in place.

    Returns (new_x, new_yslab) — (B, n, n) and (B, D, 3, T, C).
    """
    if mode not in ("tpu", "vector"):
        raise ValueError(f"unknown megakernel mode {mode!r}")
    if mode == "vector" and not interpret:
        raise ValueError(
            "the vector engine runs in interpret mode only; compiled "
            "kernels use mode='tpu'"
        )
    if axis_name is not None and first is None:
        raise ValueError("sharded mode needs each diagonal's first set")
    C = yslab.shape[-1]
    geom = tuple(g.astype(jnp.int32) for g in geom)
    operands = (x, yslab, lanes, g_row, g_col, g_sel, dinv, act, seg)
    # The tiled vector grid updates X in place, so the vector engine
    # sweeps sharded lanes as one block.
    tiled = mode == "vector" and block_c < C and axis_name is None
    # Counted once per trace of a bucket program: which engine it lowered to.
    obs.count("repro.bucket.engine." + ("index" if tiled else "band"))
    transpose = lambda a: jnp.swapaxes(a, 1, 2)
    if mode == "tpu":
        sweep = functools.partial(
            _sweep_tiles, block_c=block_c, interpret=interpret,
            unroll=unroll, alias=in_place,
        )
        transpose = functools.partial(_transpose_tiles, interpret=interpret)
    elif not tiled:
        # Single lane block: the vector engine runs as plain XLA — a
        # pallas grid of one step would add only whole-buffer copies
        # around the identical body.
        sweep = _vector_sweep(unroll)
    else:
        return _vector_tiled_pass(
            *operands, geom, block_c=block_c, unroll=unroll
        )
    return _bucket_loop(*operands, first, sweep=sweep, transpose=transpose,
                        axis_name=axis_name)
