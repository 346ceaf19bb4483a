"""Conflict-free parallel execution schedule for metric constraints.

Implements the paper's triplet enumeration (Fig. 1/2): ordered triplets
``T = {(i, j, k) : 0 <= i < j < k < n}`` (0-based here) are grouped into sets

    S_{i,k} = {(i, j, k) : i < j < k},   nonempty iff k >= i + 2,

and the sets are swept along anti-diagonals of the (i, k) grid. Any two
triplets taken from *different* sets on the same diagonal share at most one
index, so their projection updates touch disjoint variables of X — they can be
executed simultaneously without locks (paper §III.A-B).

Two diagonal families cover the grid exactly once (paper Fig. 1):
  family 1: fix x = 0, z = n-1 .. 2:       sets S_{x+c, z-c}, c = 0..floor((z-x-2)/2)
  family 2: fix z = n-1, x = 1 .. n-3:     sets S_{x+c, z-c}, c = 0..floor((z-x-2)/2)

(The paper is 1-based; we use 0-based indices throughout.)

The schedule is *static*: it depends only on n, so it is precomputed in numpy
and baked into jitted solvers as constant index arrays.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

__all__ = [
    "BucketLayout",
    "Diagonal",
    "LANE_DEAL",
    "Schedule",
    "ScheduleLayout",
    "StageBucket",
    "build_layout",
    "build_schedule",
    "build_static_stage",
    "compose_slab_permutation",
    "dense_to_duals",
    "diagonal_list",
    "duals_to_dense",
    "enumerate_triplets",
    "folded_geometry_np",
    "device_assignment",
    "n_triplets",
    "slab_dims",
    "slab_valid_masks",
]


def n_triplets(n: int) -> int:
    """|T| = C(n, 3)."""
    return n * (n - 1) * (n - 2) // 6


@dataclasses.dataclass(frozen=True)
class Diagonal:
    """One anti-diagonal of S_{i,k} sets; all sets are mutually conflict-free.

    Attributes:
      i: (C,) smallest index of each set on the diagonal.
      k: (C,) largest index of each set (i + 2 <= k).
      sizes: (C,) number of middle indices j per set (= k - i - 1).
    """

    i: np.ndarray
    k: np.ndarray

    @property
    def sizes(self) -> np.ndarray:
        return self.k - self.i - 1

    @property
    def num_sets(self) -> int:
        return int(self.i.shape[0])

    @property
    def max_size(self) -> int:
        return int(self.sizes.max()) if self.num_sets else 0

    @property
    def num_triplets(self) -> int:
        return int(self.sizes.sum())


def diagonal_list(n: int) -> list[Diagonal]:
    """All diagonals of the two double loops in paper Fig. 1 (0-based)."""
    if n < 3:
        return []
    diags: list[Diagonal] = []

    def make(x: int, z: int) -> Diagonal:
        g = (z - x - 2) // 2
        c = np.arange(g + 1, dtype=np.int64)
        return Diagonal(i=x + c, k=z - c)

    # Family 1: x = 0, z = n-1 down to 2.
    for z in range(n - 1, 1, -1):
        if z - 0 >= 2:
            diags.append(make(0, z))
    # Family 2: z = n-1, x = 1 .. n-3.
    for x in range(1, n - 2):
        diags.append(make(x, n - 1))
    return diags


def enumerate_triplets(n: int) -> np.ndarray:
    """All triplets in schedule order, shape (C(n,3), 3). Test/debug helper."""
    rows = []
    for d in diagonal_list(n):
        for i, k in zip(d.i, d.k):
            for j in range(i + 1, k):
                rows.append((i, j, k))
    out = np.asarray(rows, dtype=np.int64).reshape(-1, 3)
    return out


# How ``build_layout`` deals a diagonal's lanes over devices. Dual slabs
# saved with procs > 1 are only valid under the deal that wrote them, so
# checkpoints record this name (launch/solve.py).
LANE_DEAL = "contiguous"


def device_assignment(num_lanes: int, lanes: int) -> np.ndarray:
    """Device of each folded lane of a diagonal: device r holds the
    contiguous run of lanes r·Cl … r·Cl + Cl − 1, Cl = ``lanes`` per
    device."""
    return np.arange(num_lanes, dtype=np.int64) // lanes


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Padded, array-form schedule for vectorized execution.

    All diagonals are stacked and padded to a common width so a single
    ``lax.scan`` can sweep them. ``bucket`` groups diagonals of similar length
    to bound padding waste (beyond-paper optimization; see EXPERIMENTS.md).

    Attributes:
      n: problem size.
      diag_i: (D, Cmax) int32, padded with -1.
      diag_k: (D, Cmax) int32, padded with -1.
      set_mask: (D, Cmax) bool, True where a real set exists.
      max_t: (D,) int32 — max j-steps needed on each diagonal.
      t_max: global max j-steps (int).
    """

    n: int
    diag_i: np.ndarray
    diag_k: np.ndarray
    set_mask: np.ndarray
    max_t: np.ndarray

    @property
    def num_diagonals(self) -> int:
        return int(self.diag_i.shape[0])

    @property
    def max_sets(self) -> int:
        return int(self.diag_i.shape[1])

    @property
    def t_max(self) -> int:
        return int(self.max_t.max()) if self.num_diagonals else 0


@functools.lru_cache(maxsize=32)
def build_schedule(n: int, pad_sets_to: int | None = None) -> Schedule:
    """Build the padded array schedule for size-n problems.

    Args:
      n: number of points.
      pad_sets_to: optionally round the set dimension up to a multiple
        (e.g. 128 for TPU lane alignment).
    """
    diags = diagonal_list(n)
    if not diags:
        z = np.zeros((0, 0), dtype=np.int64)
        return Schedule(n, z, z, z.astype(bool), np.zeros((0,), np.int64))
    cmax = max(d.num_sets for d in diags)
    if pad_sets_to:
        cmax = ((cmax + pad_sets_to - 1) // pad_sets_to) * pad_sets_to
    D = len(diags)
    diag_i = np.full((D, cmax), -1, dtype=np.int64)
    diag_k = np.full((D, cmax), -1, dtype=np.int64)
    set_mask = np.zeros((D, cmax), dtype=bool)
    max_t = np.zeros((D,), dtype=np.int64)
    for r, d in enumerate(diags):
        C = d.num_sets
        diag_i[r, :C] = d.i
        diag_k[r, :C] = d.k
        set_mask[r, :C] = True
        max_t[r] = d.max_size
    return Schedule(n, diag_i, diag_k, set_mask, max_t)


# --------------------------------------------------------------------------
# Schedule-native dual layout (DESIGN.md §3)
#
# Triangle duals never live in a dense (n, n, n) tensor inside the solvers.
# They are stored in "schedule layout": one slab per diagonal bucket, shaped
#
#     (procs, D, 3, T, Cl)
#
# where D diagonals are scanned in schedule order, T is the bucket's max
# lane height, Cl the per-device lane count, and axis 2 indexes the three
# constraints of a triplet (0: long (i,j) apex k, 1: long (i,k) apex j,
# 2: long (j,k) apex i). The slab slice for one diagonal is addressed by the
# ``lax.scan`` step index directly — no gather, no scatter. ``procs`` is the
# device count (1 for the single-device solver); lane f of a diagonal maps to
# (device f // Cl, slot f % Cl), so each device holds a contiguous run of the
# diagonal's lanes and its operands are columns of dense blocks of X at
# per-diagonal offsets (the band engine, DESIGN.md §10). Any partition of a
# diagonal's lanes is exact: its sets are mutually conflict-free, so each
# cell of X is updated on exactly one device and the merge adds +0.0 from
# the others. The paper's Fig. 3 round robin (set r to processor r mod p)
# is one such partition, chosen there for balance; folded lanes have
# near-uniform height, so contiguous runs balance as well.
#
# **Lane folding**: the sets of a diagonal have sizes s, s-2, s-4, ... — a
# rectangular (T, C) layout would waste ~half its area on the triangular
# profile. Since sets on one diagonal are mutually conflict-free, processing
# them in any interleaving is exact, so lane f packs TWO sets: segment A is
# set f (the f-th largest) for steps t < sizes_A, segment B is set C-1-f for
# the remaining steps. Paired sizes sum to a constant, so lanes have
# near-uniform height, slab area ≈ the true dual count 3·C(n, 3) (padding
# factor ~1.0–1.6 depending on bucketing vs the dense tensor's fixed ~2.1×),
# and per-lane work is balanced — strictly better than the unfolded Fig. 3
# deal on both memory and skew.
#
# ``BucketLayout`` carries precomputed flat conversion maps between this
# layout and the dense ``ytri[a, b, c]`` convention of the serial oracle
# (DESIGN.md §2), so solvers can import/export duals exactly.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Layout metadata for one contiguous bucket of diagonals.

    All work arrays are (procs, D, Cl) int32; i/k padded with -1, sizes
    with 0. Segment A of lane (dev, r, slot) is the set (i, k) visited for
    steps t in [0, sizes); segment B is the set (i2, k2) visited for steps
    t in [sizes, sizes + sizes2). Unpaired lanes have i2 = -1, sizes2 = 0.

    Attributes:
      diag_ids: (D,) global diagonal indices in schedule order.
      i, k, sizes: segment-A set per lane; ``sizes = k - i - 1``.
      i2, k2, sizes2: segment-B (folded partner) set per lane.
      T: max lane height (sizes + sizes2) over the bucket's diagonals.
      slab_shape: (procs, D, 3, T, Cl) — the dual slab for this bucket.
      slab_index: (M,) int64 flat indices into the slab, one per real dual.
      dense_index: 3×(M,) int64 arrays (a, b, c) — matching dense positions.
    """

    diag_ids: np.ndarray
    i: np.ndarray
    k: np.ndarray
    sizes: np.ndarray
    i2: np.ndarray
    k2: np.ndarray
    sizes2: np.ndarray
    T: int
    slab_shape: tuple[int, ...]
    slab_index: np.ndarray
    dense_index: tuple[np.ndarray, np.ndarray, np.ndarray]

    @property
    def procs(self) -> int:
        return int(self.slab_shape[0])

    @property
    def num_diagonals(self) -> int:
        return int(self.slab_shape[1])

    @property
    def lanes(self) -> int:
        return int(self.slab_shape[4])

    @property
    def slab_size(self) -> int:
        return int(np.prod(self.slab_shape))

    @property
    def first_sets(self) -> np.ndarray:
        """(D, 2) int32: each diagonal's first set (x, z), held by lane 0
        of device 0 (a device's own lanes need not hold it)."""
        return np.stack([self.i[0, :, 0], self.k[0, :, 0]], axis=-1)

    @property
    def num_duals(self) -> int:
        """Real (non-padding) dual entries in this bucket."""
        return int(self.slab_index.shape[0])


@dataclasses.dataclass(frozen=True)
class ScheduleLayout:
    """Full schedule-native dual layout: an ordered tuple of buckets.

    The buckets partition the diagonal list contiguously (schedule order is
    preserved), so sweeping bucket 0..B-1 visits constraints in exactly the
    serial oracle's "schedule" order. Total real duals = 3·C(n, 3).
    """

    n: int
    procs: int
    buckets: tuple[BucketLayout, ...]

    @property
    def num_duals(self) -> int:
        return sum(b.num_duals for b in self.buckets)

    def slab_shapes(self) -> list[tuple[int, ...]]:
        return [b.slab_shape for b in self.buckets]


def _fold(d: Diagonal):
    """Fold one diagonal: lane f = (set f, set C-1-f); the middle set of
    an odd diagonal rides alone. Paired sizes sum to a constant, so lane
    heights are near-uniform (see module comment)."""
    C = d.num_sets
    F = (C + 1) // 2
    cA = np.arange(F)
    cB = C - 1 - cA
    iA, kA = d.i[cA], d.k[cA]
    iB = np.where(cB > cA, d.i[cB], -1)
    kB = np.where(cB > cA, d.k[cB], -1)
    return iA, kA, iB, kB


def _bucket_dims(folds, procs: int, pad_sets_to: int | None):
    """(T, Cl) of one bucket's folded lanes."""
    T = max(
        int(((kA - iA - 1) + np.where(iB >= 0, kB - iB - 1, 0)).max())
        for iA, kA, iB, kB in folds
    )
    Cl = max(-(-len(f[0]) // procs) for f in folds)
    if pad_sets_to:
        Cl = ((Cl + pad_sets_to - 1) // pad_sets_to) * pad_sets_to
    return T, Cl


def slab_dims(
    n: int,
    num_buckets: int = 1,
    procs: int = 1,
    pad_sets_to: int | None = None,
) -> list[tuple[int, int, int]]:
    """``(D, T, Cl)`` of every bucket slab of ``build_layout(n, ...)``,
    without its conversion maps — cheap at any n (shape planning and
    compile checks)."""
    diags = diagonal_list(n)
    groups = np.array_split(np.arange(len(diags)), max(1, int(num_buckets)))
    return [
        (len(g),) + _bucket_dims(
            [_fold(diags[r]) for r in g], procs, pad_sets_to
        )
        for g in groups if len(g)
    ]


@functools.lru_cache(maxsize=32)
def build_layout(
    n: int,
    num_buckets: int = 1,
    procs: int = 1,
    pad_sets_to: int | None = None,
) -> ScheduleLayout:
    """Build the schedule-native dual layout for size-n problems.

    Args:
      n: number of points.
      num_buckets: contiguous diagonal buckets (bounds scan padding waste).
      procs: device count; each device holds a contiguous run of every
        diagonal's lanes (``device_assignment``).
      pad_sets_to: round the lane dimension up to a multiple (TPU alignment).
    """
    diags = diagonal_list(n)
    if not diags:
        return ScheduleLayout(n, procs, ())
    groups = np.array_split(np.arange(len(diags)), max(1, int(num_buckets)))
    buckets: list[BucketLayout] = []
    for g in groups:
        if len(g) == 0:
            continue
        D = len(g)
        folds = [_fold(diags[r]) for r in g]
        T, Cl = _bucket_dims(folds, procs, pad_sets_to)
        arrs = {
            name: np.full((procs, D, Cl), -1, dtype=np.int32)
            for name in ("i", "k", "i2", "k2")
        }
        for r, (iA, kA, iB, kB) in enumerate(folds):
            dev = device_assignment(len(iA), Cl)
            slot = np.arange(len(iA)) - dev * Cl
            arrs["i"][dev, r, slot] = iA
            arrs["k"][dev, r, slot] = kA
            arrs["i2"][dev, r, slot] = iB
            arrs["k2"][dev, r, slot] = kB
        s_arr = np.where(arrs["i"] >= 0, arrs["k"] - arrs["i"] - 1, 0).astype(np.int32)
        s2_arr = np.where(arrs["i2"] >= 0, arrs["k2"] - arrs["i2"] - 1, 0).astype(np.int32)
        slab_shape = (procs, D, 3, T, Cl)
        # Conversion maps: every real (dev, diag, t, lane) cell, three duals.
        shape4 = (procs, D, T, Cl)
        tt = np.broadcast_to(
            np.arange(T, dtype=np.int32)[None, None, :, None], shape4
        )
        s1b = np.broadcast_to(s_arr[:, :, None, :], shape4)
        s2b = np.broadcast_to(s2_arr[:, :, None, :], shape4)
        seg_entries = []
        for seg, (i_name, k_name) in enumerate((("i", "k"), ("i2", "k2"))):
            ib = np.broadcast_to(arrs[i_name][:, :, None, :], shape4)
            kb = np.broadcast_to(arrs[k_name][:, :, None, :], shape4)
            if seg == 0:
                valid = (ib >= 0) & (tt < s1b)
                toff = tt
            else:
                valid = (ib >= 0) & (tt >= s1b) & (tt < s1b + s2b)
                toff = tt - s1b
            dev, dg, tv, ln = (a.astype(np.int64) for a in np.nonzero(valid))
            iv = ib[valid].astype(np.int64)
            kv = kb[valid].astype(np.int64)
            jv = iv + 1 + toff[valid].astype(np.int64)
            seg_entries.append((dev, dg, tv, ln, iv, jv, kv))
        flat = []
        dense_a, dense_b, dense_c = [], [], []
        for dev, dg, tv, ln, iv, jv, kv in seg_entries:
            for m, (a, b, c) in enumerate(
                ((iv, jv, kv), (iv, kv, jv), (jv, kv, iv))
            ):
                flat.append(
                    np.ravel_multi_index(
                        (dev, dg, np.full_like(dev, m), tv, ln), slab_shape
                    )
                )
                dense_a.append(a)
                dense_b.append(b)
                dense_c.append(c)
        buckets.append(
            BucketLayout(
                diag_ids=np.asarray(g, dtype=np.int64),
                i=arrs["i"],
                k=arrs["k"],
                sizes=s_arr,
                i2=arrs["i2"],
                k2=arrs["k2"],
                sizes2=s2_arr,
                T=T,
                slab_shape=slab_shape,
                slab_index=np.concatenate(flat),
                dense_index=(
                    np.concatenate(dense_a),
                    np.concatenate(dense_b),
                    np.concatenate(dense_c),
                ),
            )
        )
    return ScheduleLayout(n, procs, tuple(buckets))


def duals_to_dense(layout: ScheduleLayout, slabs) -> np.ndarray:
    """Schedule-layout dual slabs → dense ``ytri[a, b, c]`` (DESIGN.md §2).

    ``slabs`` is one array per bucket; any shape that flattens to
    ``prod(bucket.slab_shape)`` is accepted (solvers may drop a unit procs
    axis). Returns float64 (n, n, n).
    """
    n = layout.n
    ytri = np.zeros((n, n, n), dtype=np.float64)
    for bl, slab in zip(layout.buckets, slabs):
        flat = np.asarray(slab, dtype=np.float64).reshape(-1)
        if flat.shape[0] != bl.slab_size:
            raise ValueError(
                f"slab has {flat.shape[0]} elements, layout expects {bl.slab_size}"
            )
        ytri[bl.dense_index] = flat[bl.slab_index]
    return ytri


def dense_to_duals(
    layout: ScheduleLayout, ytri: np.ndarray, dtype=np.float32
) -> list[np.ndarray]:
    """Dense ``ytri[a, b, c]`` → schedule-layout slabs (inverse of
    :func:`duals_to_dense`; padding cells are zero)."""
    out = []
    for bl in layout.buckets:
        flat = np.zeros(bl.slab_size, dtype=dtype)
        flat[bl.slab_index] = ytri[bl.dense_index].astype(dtype)
        out.append(flat.reshape(bl.slab_shape))
    return out


def slab_valid_masks(
    layout: ScheduleLayout, n_real: int | None = None
) -> list[np.ndarray]:
    """Per-bucket bool masks marking the real (non-padding) dual cells.

    Shape matches ``slab_shape``. Slab-native reductions (the device
    convergence engine's ``triangle_dual_stats``) mask with these: under
    fused execution (DESIGN.md §4) the padding cells of a dual slab carry
    don't-care values and must never enter a reduction.

    ``n_real`` makes the masks **ghost-aware** (DESIGN.md §8): on a
    ghost-padded problem the cells of every triangle set touching an
    index >= n_real are additionally dropped — those sets are masked out
    of the staged ``act`` slabs, so their dual cells also carry
    don't-care values under fused execution. A set ``S_{i,k}`` is ghost
    iff its largest index ``kN >= n_real`` (i < j < k), the same
    predicate the staging applies.
    """
    out = []
    for bl in layout.buckets:
        m = np.zeros(bl.slab_size, dtype=bool)
        m[bl.slab_index] = True
        m = m.reshape(bl.slab_shape)
        if n_real is not None:
            _, _, kN, _, _ = folded_geometry_np(
                bl.i, bl.k, bl.sizes, bl.i2, bl.k2, bl.sizes2, bl.T
            )  # (procs, D, T, Cl)
            m = m & (kN[:, :, None, :, :] < int(n_real))
        out.append(m)
    return out


@functools.lru_cache(maxsize=16)
def compose_slab_permutation(
    n: int, num_buckets: int, p_old: int, p_new: int
) -> tuple[np.ndarray, np.ndarray, int, int]:
    """Direct slab→slab permutation between two device counts.

    Composes the two layouts' dense conversion maps *symbolically*: every
    real dual has a unique dense key (a, b, c), so sorting both layouts'
    (key, flat slab position) tables by key aligns old and new positions
    one-to-one — the dense (n, n, n) tensor itself is never materialized
    (that round-trip survives only as the test oracle,
    ``elastic.reshard_duals_dense``).

    Returns ``(src, dst, old_size, new_size)``: flat positions into the
    bucket-concatenated old/new slab vectors such that
    ``new_flat[dst] = old_flat[src]`` (padding cells stay zero).
    """
    old = build_layout(n, num_buckets=num_buckets, procs=p_old)
    new = build_layout(n, num_buckets=num_buckets, procs=p_new)

    def flat_table(layout: ScheduleLayout):
        keys, pos, off = [], [], 0
        for bl in layout.buckets:
            a, b, c = bl.dense_index
            keys.append((a * n + b) * n + c)
            pos.append(bl.slab_index + off)
            off += bl.slab_size
        if not keys:
            return np.zeros(0, np.int64), np.zeros(0, np.int64), 0
        return np.concatenate(keys), np.concatenate(pos), off

    k_old, p_old_flat, size_old = flat_table(old)
    k_new, p_new_flat, size_new = flat_table(new)
    so = np.argsort(k_old, kind="stable")
    sn = np.argsort(k_new, kind="stable")
    if not np.array_equal(k_old[so], k_new[sn]):
        raise AssertionError("layouts enumerate different constraint sets")
    return p_old_flat[so], p_new_flat[sn], size_old, size_new


# --------------------------------------------------------------------------
# Static staging (DESIGN.md §4)
#
# Everything a pass touches besides X and the duals is a pure function of
# (n, num_buckets, procs) and the constant weight matrix W: the folded
# per-step geometry (J / iN / kN index tables), the active/seg masks, and
# the gathered weight slices w_row / w_col / w_ikp. Before fused-pass
# execution these were re-derived (or re-gathered from HBM) inside every
# ``lax.scan`` step of every pass — pure waste, since they never change.
# ``build_static_stage`` precomputes them once, in numpy, as per-bucket
# slabs laid out exactly like the dual slabs:
#
#     J, iN, kN        (procs, D, T, Cl) int32   per-step triplet indices
#     active, seg      (procs, D, T, Cl) bool    step masks
#     w_row, w_col     (procs, D, T, Cl) dtype   W[iN, J], W[J, kN]
#     w_ikp            (procs, D, 2, Cl) dtype   W[i, k] per segment
#
# The per-diagonal slice of each slab is addressed by the scan step index —
# the same zero-gather discipline as the dual storage (§3). The geometry
# must agree **bit-for-bit** with ``parallel_dykstra.folded_geometry`` (the
# jnp implementation used by data-dependent paths such as the sharded
# solver's packed delta exchange); ``folded_geometry_np`` is its numpy twin
# and tests/test_fused_pass.py pins the equivalence property.
# --------------------------------------------------------------------------


def folded_geometry_np(i1, k1, s1, i2, k2, s2, T: int):
    """Numpy twin of ``parallel_dykstra.folded_geometry``.

    Inputs are int arrays of shape (..., C) (any leading batch dims, e.g.
    (procs, D, Cl)); returns (J, iN, kN, active, seg) of shape (..., T, C)
    with int32/bool dtypes, bit-identical to the jnp implementation.
    """
    i1, k1, s1, i2, k2, s2 = (
        np.asarray(a, np.int32) for a in (i1, k1, s1, i2, k2, s2)
    )
    ax = i1.ndim - 1
    e = lambda a: np.expand_dims(a, ax)  # (..., 1, C)
    t = np.arange(T, dtype=np.int32).reshape((1,) * ax + (T, 1))
    seg = t >= e(s1)  # (..., T, C) — True in segment B
    tB = t - e(s1)
    J = np.where(seg, e(i2) + 1 + tB, e(i1) + 1 + t).astype(np.int32)
    shape = J.shape
    iN = np.where(seg, np.broadcast_to(e(i2), shape),
                  np.broadcast_to(e(i1), shape)).astype(np.int32)
    kN = np.where(seg, np.broadcast_to(e(k2), shape),
                  np.broadcast_to(e(k1), shape)).astype(np.int32)
    active = np.where(
        seg,
        (tB < e(s2)) & (e(i2) >= 0),
        (t < e(s1)) & (e(i1) >= 0),
    )
    return J, iN, kN, active, seg


@dataclasses.dataclass(frozen=True)
class StageBucket:
    """Precomputed static staging slabs for one bucket (DESIGN.md §4).

    All arrays carry the leading ``procs`` axis of the layout; the
    single-device solver drops it, the sharded solver shards it.

    Attributes:
      J, iN, kN: (procs, D, T, Cl) int32 — per-step middle index ``j`` and
        the segment-selected ``(i, k)`` of each folded lane.
      active: (procs, D, T, Cl) bool — True where a real triplet is visited.
      seg: (procs, D, T, Cl) bool — True while the lane sweeps segment B.
      w_row, w_col: (procs, D, T, Cl) — W[iN, J] / W[J, kN], out-of-bounds
        cells filled with 1.0 (matching ``x.at[].get(mode="fill")``).
      w_ikp: (procs, D, 2, Cl) — W[i, k] of segments A and B.
    """

    J: np.ndarray
    iN: np.ndarray
    kN: np.ndarray
    active: np.ndarray
    seg: np.ndarray
    w_row: np.ndarray
    w_col: np.ndarray
    w_ikp: np.ndarray


def build_static_stage(
    layout: ScheduleLayout, w: np.ndarray, dtype=np.float32
) -> list[StageBucket]:
    """Precompute the pass-invariant staging slabs for every bucket.

    Args:
      layout: the schedule-native dual layout (``build_layout``).
      w: (n, n) weight matrix of the problem.
      dtype: dtype of the staged weight slabs (the solver compute dtype).

    Unlike the legacy per-diagonal gathers (``w.at[idx].get(mode="fill")``,
    whose negative padding indices *wrap* into the zero lower triangle and
    poison masked lanes with ``1/w = inf``), every cell a **masked** step
    would read — padding lanes, out-of-range middle indices, lower-triangle
    wraps — is staged as 1.0, so no inf/nan from padding ever enters the
    fused pipeline. Active steps always read W verbatim (the geometry
    guarantees valid upper-triangle indices there), so X and every real
    dual are unaffected bit-for-bit — including problems whose real
    weights contain zeros, which keep the serial oracle's ``1/w = inf``
    semantics.
    """
    n = layout.n
    dtype = np.dtype(dtype)
    w = np.asarray(w, dtype)

    def gather(rows, cols, live, fill):
        """W[rows, cols] where ``live``; ``fill`` at masked cells."""
        fill = dtype.type(fill)
        r = np.clip(rows, 0, n - 1)
        c = np.clip(cols, 0, n - 1)
        return np.where(live, w[r, c], fill).astype(dtype)

    out = []
    for bl in layout.buckets:
        J, iN, kN, active, seg = folded_geometry_np(
            bl.i, bl.k, bl.sizes, bl.i2, bl.k2, bl.sizes2, bl.T
        )
        # A lane's (i, k) carry weight is live iff the segment exists.
        w_ikp = np.stack(
            [gather(bl.i, bl.k, bl.i >= 0, 1.0),
             gather(bl.i2, bl.k2, bl.i2 >= 0, 1.0)], axis=-2
        )  # (procs, D, 2, Cl)
        out.append(
            StageBucket(
                J=J,
                iN=iN,
                kN=kN,
                active=active,
                seg=seg,
                w_row=gather(iN, J, active, 1.0),
                w_col=gather(J, kN, active, 1.0),
                w_ikp=w_ikp,
            )
        )
    return out


def validate_conflict_free(d: Diagonal) -> bool:
    """Brute-force check: any two triplets from different sets of this diagonal
    share at most one index (paper §III.A). Used in tests."""
    for a in range(d.num_sets):
        for b in range(a + 1, d.num_sets):
            ia, ka = int(d.i[a]), int(d.k[a])
            ib, kb = int(d.i[b]), int(d.k[b])
            for ja in range(ia + 1, ka):
                for jb in range(ib + 1, kb):
                    shared = len({ia, ja, ka} & {ib, jb, kb})
                    if shared > 1:
                        return False
    return True
