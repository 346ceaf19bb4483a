"""Pallas TPU kernel: conflict-free diagonal sweep for metric projections.

TPU adaptation of the paper's tiled triplet assignment (§III.C): the sets
``S_{i,k}`` of one conflict-free diagonal are mapped to VPU *lanes* (last dim,
blocks of ``block_c``); the sequential middle-index loop runs as a
``fori_loop`` over the sublane dimension with the shared ``x_ik`` carried in
registers. Lanes are *folded* (core/schedule.py): each packs up to two sets
head-to-tail, with ``seg`` selecting which of the two ``x_ik`` carries is
live at step t — this evens out lane heights so the staged buffers carry
almost no padding. The buffers staged into VMEM are exactly the contiguous
row/column slices of X the paper's b×b×b cache cubes target — HBM→VMEM
blocking replaces L1/L2 cache blocking.

Grid: (num_c_blocks,). Block shapes: (T, block_c) for all (T, C) buffers and
(2, block_c) for the carries. VMEM footprint ≈ 13 · T · block_c · 4 bytes
(e.g. T=1024, block_c=128 → 6.5 MiB), within the ~16 MiB v5e VMEM budget; for
larger T the host splits the sweep (see ops.py).

With ``in_place=True`` the three dual blocks are aliased input→output
(``input_output_aliases``), so the schedule-native dual slabs are updated in
their own buffers rather than round-tripped as separate outputs.

``block_c`` is the tunable *tile size* — the analogue of the paper's Fig. 7
tile-size sweep, benchmarked in benchmarks/fig7_tilesize.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.metric_project.ref import triplet_visit

__all__ = ["sweep_pallas", "sweep_pallas_folded"]


def _sweep_kernel(
    rowb_ref,
    colb_ref,
    xikp_ref,
    y0_ref,
    y1_ref,
    y2_ref,
    wrow_ref,
    wcol_ref,
    wikp_ref,
    act_ref,
    seg_ref,
    orow_ref,
    ocol_ref,
    oxikp_ref,
    o0_ref,
    o1_ref,
    o2_ref,
    *,
    eps: float,
    T: int,
):
    dt = rowb_ref.dtype
    eps = jnp.asarray(eps, dt)
    iw_a = 1.0 / wikp_ref[0:1, :]  # (1, Cb)
    iw_b = 1.0 / wikp_ref[1:2, :]

    def body(t, carry):
        xa, xb = carry
        sl = (pl.ds(t, 1), slice(None))
        xij = rowb_ref[sl]
        xjk = colb_ref[sl]
        v0 = y0_ref[sl]
        v1 = y1_ref[sl]
        v2 = y2_ref[sl]
        act = act_ref[sl] != 0
        sg = seg_ref[sl] != 0
        iwij = 1.0 / wrow_ref[sl]
        iwjk = 1.0 / wcol_ref[sl]
        xc = jnp.where(sg, xb, xa)
        iw_ik = jnp.where(sg, iw_b, iw_a)
        nij, nik, njk, t0, t1, t2 = triplet_visit(
            xij, xc, xjk, v0, v1, v2, iwij, iw_ik, iwjk, eps
        )
        orow_ref[sl] = jnp.where(act, nij, xij)
        ocol_ref[sl] = jnp.where(act, njk, xjk)
        o0_ref[sl] = jnp.where(act, t0, v0)
        o1_ref[sl] = jnp.where(act, t1, v1)
        o2_ref[sl] = jnp.where(act, t2, v2)
        nik = jnp.where(act, nik, xc)
        return jnp.where(sg, xa, nik), jnp.where(sg, nik, xb)

    xa, xb = jax.lax.fori_loop(
        0, T, body, (xikp_ref[0:1, :], xikp_ref[1:2, :])
    )
    oxikp_ref[0:1, :] = xa
    oxikp_ref[1:2, :] = xb


def sweep_pallas_folded(
    rowb,
    colb,
    xikp,
    y0,
    y1,
    y2,
    w_row,
    w_col,
    w_ikp,
    active,
    seg,
    eps,
    *,
    block_c: int = 128,
    interpret: bool = True,
    in_place: bool = False,
):
    """Pallas folded diagonal sweep. Same contract as ref.sweep_ref_folded.

    Shapes: (T, C) buffers; (2, C) for xikp / w_ikp; (T, C) bool seg. C is
    padded to a multiple of ``block_c`` here; padding lanes carry
    active=False.

    ``in_place=True`` aliases the three dual inputs to the three dual outputs
    (``input_output_aliases``), so the kernel updates the dual blocks in
    their VMEM/HBM buffers instead of round-tripping through separate
    outputs — the schedule-native storage never needs the pre-sweep dual
    values again (DESIGN.md §3). Only enable under jit (XLA inserts copies if
    the donated inputs have other uses; eager callers would see their arrays
    deleted).
    """
    T, C = rowb.shape
    dt = rowb.dtype
    Cp = -(-C // block_c) * block_c

    def padc(a, fill):
        if a.shape[-1] == Cp:
            return a
        pad = [(0, 0)] * (a.ndim - 1) + [(0, Cp - C)]
        return jnp.pad(a, pad, constant_values=fill)

    rowb_, colb_ = padc(rowb, 0), padc(colb, 0)
    y0_, y1_, y2_ = padc(y0, 0), padc(y1, 0), padc(y2, 0)
    wrow_, wcol_ = padc(w_row, 1), padc(w_col, 1)
    xikp_ = padc(xikp, 0)
    wikp_ = padc(w_ikp, 1)
    act_ = padc(active.astype(jnp.int8), 0)
    seg_ = padc(seg.astype(jnp.int8), 0)

    tc_spec = pl.BlockSpec((T, block_c), lambda c: (0, c))
    p_spec = pl.BlockSpec((2, block_c), lambda c: (0, c))
    grid = (Cp // block_c,)
    kernel = functools.partial(_sweep_kernel, eps=float(eps), T=T)
    # Dual buffers y0/y1/y2 (inputs 3..5) alias outputs o0/o1/o2 (3..5):
    # their pre-sweep values are dead after the kernel, so the blocks are
    # overwritten in place rather than allocated as fresh outputs.
    aliases = {3: 3, 4: 4, 5: 5} if in_place else {}
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            tc_spec, tc_spec, p_spec, tc_spec, tc_spec, tc_spec,
            tc_spec, tc_spec, p_spec, tc_spec, tc_spec,
        ],
        out_specs=[tc_spec, tc_spec, p_spec, tc_spec, tc_spec, tc_spec],
        input_output_aliases=aliases,
        out_shape=[
            jax.ShapeDtypeStruct((T, Cp), dt),
            jax.ShapeDtypeStruct((T, Cp), dt),
            jax.ShapeDtypeStruct((2, Cp), dt),
            jax.ShapeDtypeStruct((T, Cp), dt),
            jax.ShapeDtypeStruct((T, Cp), dt),
            jax.ShapeDtypeStruct((T, Cp), dt),
        ],
        interpret=interpret,
    )(rowb_, colb_, xikp_, y0_, y1_, y2_, wrow_, wcol_, wikp_, act_, seg_)
    nrow, ncol, nxikp, n0, n1, n2 = out
    return (
        nrow[:, :C],
        ncol[:, :C],
        nxikp[:, :C],
        n0[:, :C],
        n1[:, :C],
        n2[:, :C],
    )


def sweep_pallas(
    rowb,
    colb,
    xik,
    y0,
    y1,
    y2,
    w_row,
    w_col,
    w_ik,
    active,
    eps,
    *,
    block_c: int = 128,
    interpret: bool = True,
    in_place: bool = False,
):
    """Unfolded Pallas diagonal sweep. Same contract as ref.sweep_ref:
    (T, C) buffers, (C,) xik / w_ik — a folded sweep with an empty B
    segment. Kept as the kernel's oracle-validated entry point."""
    xikp = jnp.stack([xik, jnp.zeros_like(xik)])
    w_ikp = jnp.stack([w_ik, jnp.ones_like(w_ik)])
    seg = jnp.zeros_like(active)
    nrow, ncol, nxikp, n0, n1, n2 = sweep_pallas_folded(
        rowb, colb, xikp, y0, y1, y2, w_row, w_col, w_ikp, active, seg, eps,
        block_c=block_c, interpret=interpret, in_place=in_place,
    )
    return nrow, ncol, nxikp[0], n0, n1, n2
