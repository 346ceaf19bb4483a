"""Vectorized parallel Dykstra solver (single device).

TPU-native adaptation of the paper's parallel execution schedule: instead of
p threads sweeping the sets ``S_{i,k}`` of a diagonal, the *whole diagonal* is
vectorized — one lane per set — and the sequential middle-index loop becomes a
``lax.scan`` carrying ``x_ik``. The paper's conflict-freedom theorem
(any two triplets from different sets on a diagonal share at most one index)
guarantees every gather/scatter below touches disjoint cells across lanes, so
scatters are exact merges with ``unique_indices=True`` — the JAX analogue of
"no locks" (paper §III.A; DESIGN.md §3).

Data layout per diagonal ("schedule layout"): lanes are *folded* — lane c
packs up to two sets of the diagonal head-to-tail (DESIGN.md §3), segment A
``(i, k)`` for steps t < sizes, then partner segment B ``(i2, k2)``. The
touched entries of X are

    rowb[t, c] = x[i_c(t), j(t)]  (contiguous row slice of X — VMEM friendly)
    colb[t, c] = x[j(t),  k_c(t)] (contiguous column slice)
    xikp[s, c] = x[i, k]          (the sequential carry, one per segment)

Triangle duals are **schedule-native** (DESIGN.md §3): they live permanently
in per-bucket slabs ``(D, 3, T, C)`` addressed by the scan step index — the
slab slice for a diagonal is pure slicing, never a gather. Only the X
row/column/carry slices above are gathered, and those are contiguous. Dual
memory is exactly ``3·C(n, 3)`` floats plus bucket padding — there is no
dense (n, n, n) tensor anywhere in this solver. Use ``duals_to_dense`` /
``dense_to_duals`` to convert to the serial oracle's dense convention.

**Fused-pass execution** (DESIGN.md §4, the default): everything above that
never changes across passes — folded geometry, step masks, gathered weight
buffers — is precomputed once by ``core/schedule.py::build_static_stage``
into per-bucket slabs addressed by the scan step index, the per-diagonal
sweep is the staged ``fused_bucket_pass_ref`` (or, with ``use_kernel=True``,
one whole-bucket Pallas megakernel per bucket instead of one kernel launch
per diagonal), and ``run(passes=P)`` executes all P passes (pair/box steps
included) as a single jitted ``lax.scan`` with a periodic convergence probe
— a full solve is one device program, not ~2n·P of them.

``fused=False`` keeps the PR-1 path (per-diagonal geometry recompute +
weight re-gather, one host dispatch per pass) as a benchmark baseline.

Pair/box steps, host/device metrics, dual conversions and the
``run_until`` solve-to-tolerance runtime are inherited from
``core/engine.py::SolverRuntime`` (the device-resident convergence
engine, DESIGN.md §7) and shared with the sharded solver.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import metrics_device, schedule as sched
from repro.core.engine import SolverRuntime
from repro.core.problems import MetricQP

__all__ = ["ParallelState", "ParallelSolver", "folded_geometry"]


def folded_geometry(i1, k1, s1, i2, k2, s2, T: int):
    """(T, C) index/mask arrays for folded lanes (DESIGN.md §3).

    Lane c sweeps set (i1, k1) for steps t < s1 (segment A), then partner
    set (i2, k2) at local step t - s1 (segment B). All inputs are (C,)
    int32 with -1/-0 padding. Returns (J, iN, kN, active, seg) — the single
    source of the segment-selection math shared by both solvers; the
    conflict-free exactness argument requires every call site to agree on
    it bit-for-bit.
    """
    C = i1.shape[0]
    t_idx = jnp.arange(T, dtype=jnp.int32)
    seg = t_idx[:, None] >= s1[None, :]  # (T, C) — True in segment B
    tB = t_idx[:, None] - s1[None, :]
    J = jnp.where(seg, i2[None, :] + 1 + tB, i1[None, :] + 1 + t_idx[:, None])
    iN = jnp.where(seg, jnp.broadcast_to(i2[None, :], (T, C)),
                   jnp.broadcast_to(i1[None, :], (T, C)))
    kN = jnp.where(seg, jnp.broadcast_to(k2[None, :], (T, C)),
                   jnp.broadcast_to(k1[None, :], (T, C)))
    active = jnp.where(
        seg,
        (tB < s2[None, :]) & (i2[None, :] >= 0),
        (t_idx[:, None] < s1[None, :]) & (i1[None, :] >= 0),
    )
    return J, iN, kN, active, seg


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ParallelState:
    x: jax.Array  # (n, n) upper triangle
    f: jax.Array | None
    yd: list[jax.Array]  # per bucket: (D_b, 3, T_b, C_b) schedule-native duals
    ypair: jax.Array | None  # (2, n, n)
    ybox: jax.Array | None  # (2, n, n)
    passes: jax.Array  # scalar int32


def _gather(arr, idx_tuple, fill):
    return arr.at[idx_tuple].get(mode="fill", fill_value=fill)


def _scatter_add(arr, idx_tuple, delta):
    # Conflict-free by the paper's theorem; OOB (padding) rows are dropped.
    return arr.at[idx_tuple].add(delta, mode="drop", unique_indices=True)


class ParallelSolver(SolverRuntime):
    """Vectorized Dykstra for one MetricQP on a single device.

    Args:
      problem: the MetricQP instance.
      dtype: compute dtype (float32 default; float64 if x64 enabled).
      use_kernel: use the Pallas whole-bucket megakernel (interpret=True on
        CPU) instead of the pure-jnp fused reference. Requires
        ``fused=True``.
      bucket_diagonals: group diagonals into T-size buckets to cut padding
        waste (beyond-paper optimization; see EXPERIMENTS.md §Solver-perf).
      fused: fused-pass execution (DESIGN.md §4, default) — static staging
        slabs, whole-bucket sweeps, and a single multi-pass scan runner.
        False keeps the PR-1 per-diagonal/per-pass path as a baseline.
      probe_every: evaluate the runner's convergence probe every this many
        passes (``last_residuals`` holds -1.0 at skipped passes).
      sweep_unroll: unroll factor of the inner sequential-in-j scan
        (amortizes loop overhead; 4 is a good CPU/TPU default).
      n_real: live-point count when the problem is ghost-padded to a
        serving bucket (DESIGN.md §8): only indices < n_real are real.
        Every triangle touching a ghost index is masked out of the
        staged ``act`` slabs (a set S_{i,k} is ghost iff its largest
        index k >= n_real, so whole sets drop at once), the pair/box
        steps and the convergence engine run under the live-pair mask,
        and ghost cells of X/F/duals stay exactly at their init values —
        the padded solve IS the n_real solve on the padded schedule.
    """

    def __init__(
        self,
        problem: MetricQP,
        dtype=jnp.float32,
        use_kernel: bool = False,
        bucket_diagonals: int = 1,
        pad_sets_to: int | None = None,
        fused: bool = True,
        probe_every: int = 1,
        sweep_unroll: int = 4,
        n_real: int | None = None,
    ):
        self.p = problem
        self.n = problem.n
        self.n_real = self.n if n_real is None else int(n_real)
        if not 0 <= self.n_real <= self.n:
            raise ValueError(f"n_real={n_real} outside [0, {self.n}]")
        self.dtype = dtype
        self.use_kernel = use_kernel
        self.fused = fused
        self.probe_every = max(1, int(probe_every))
        self.sweep_unroll = max(1, int(sweep_unroll))
        self.bucket_diagonals = max(1, int(bucket_diagonals))
        if use_kernel and not fused:
            raise ValueError(
                "use_kernel=True requires fused=True: the gen-1 "
                "per-diagonal kernel is test-oracle only, so the legacy "
                "path has no kernel sweep."
            )
        with obs.span("repro.stage.layout"):
            self.layout = sched.build_layout(
                self.n,
                num_buckets=self.bucket_diagonals,
                procs=1,
                pad_sets_to=pad_sets_to,
            )
        self._w = jnp.asarray(problem.w, dtype)
        self._d = jnp.asarray(problem.d, dtype)
        self._wf = (
            jnp.asarray(problem.w_f, dtype) if problem.has_f else None
        )
        self._mask = metrics_device.live_pair_mask(
            self.n, self.n_real if self.n_real < self.n else None
        )
        self._buckets = self._stage_buckets()
        self._pass_fn = self._jit_staged(self._one_pass)

    def _stage_buckets(self) -> list[dict]:
        """Device-resident per-bucket work arrays (procs=1 → unit device
        axis dropped). Lane tables (i/k/s/...) drive the legacy path and
        the carry gathers; the staged geometry/mask/gain slabs
        (DESIGN.md §4) — everything the fused pass needs beyond X and the
        duals — are built only when fused execution is on (the legacy
        path re-derives them at runtime and must not pay their memory).
        Each bucket's slabs are built on the host (``repro.stage.slabs``)
        and put on the device (``repro.stage.put``) in turn, so the host
        holds one bucket's gains at a time; the last put span waits for
        every transfer."""
        with obs.span("repro.stage.put"):
            buckets = [
                dict(
                    i=jnp.asarray(bl.i[0], jnp.int32),
                    k=jnp.asarray(bl.k[0], jnp.int32),
                    s=jnp.asarray(bl.sizes[0], jnp.int32),
                    i2=jnp.asarray(bl.i2[0], jnp.int32),
                    k2=jnp.asarray(bl.k2[0], jnp.int32),
                    s2=jnp.asarray(bl.sizes2[0], jnp.int32),
                    T=bl.T,
                )
                for bl in self.layout.buckets
            ]
        if self.fused:
            npdt = np.dtype(self.dtype)
            one = npdt.type(1.0)
            epsc = npdt.type(self.p.eps)
            with obs.span("repro.stage.slabs"):
                stage = sched.build_static_stage(self.layout, self.p.w, npdt)
            for b, sb in zip(buckets, stage):
                with obs.span("repro.stage.slabs"):
                    # Ghost padding (DESIGN.md §8): a triplet is real iff
                    # its largest index kN < n_real, so the staged step
                    # mask drops every ghost set wholesale — ghost duals/X
                    # cells are simply never visited (the structural
                    # fixed-point argument).
                    act = sb.active[0]
                    if self.n_real < self.n:
                        act = act & (sb.kN[0] < self.n_real)
                    # Projection gains: g = (1/w)/eps, staged so the inner
                    # step never divides; dinv = 1/(sum of the triplet's
                    # three gains) makes theta a single multiply
                    # (ref.py::fused_step).
                    g_row = (one / sb.w_row[0]) / epsc
                    g_col = (one / sb.w_col[0]) / epsc
                    g_ikp = (one / sb.w_ikp[0]) / epsc  # (D, 2, Cl)
                    g_sel = np.where(
                        sb.seg[0], g_ikp[:, 1][:, None, :],
                        g_ikp[:, 0][:, None, :],
                    ).astype(npdt)
                    dinv = (one / (g_row + g_sel + g_col)).astype(npdt)
                with obs.span("repro.stage.put"):
                    b.update(
                        J=jnp.asarray(sb.J[0]),
                        iN=jnp.asarray(sb.iN[0]),
                        kN=jnp.asarray(sb.kN[0]),
                        act=jnp.asarray(act),
                        seg=jnp.asarray(sb.seg[0]),
                        g_row=jnp.asarray(g_row),
                        g_col=jnp.asarray(g_col),
                        g_sel=jnp.asarray(g_sel),
                        dinv=jnp.asarray(dinv),
                    )
        with obs.span("repro.stage.put"):
            jax.block_until_ready(buckets)
        return buckets

    def _staged_arrays(self) -> list[dict]:
        return [{k: v for k, v in b.items() if k != "T"}
                for b in self._buckets]

    @property
    def staged_buckets(self) -> list[dict]:
        """Public view of the per-bucket staged work arrays, in schedule
        order. Each dict carries the lane tables ``i/k/s/i2/k2/s2`` and
        ``T``; with ``fused=True`` also the DESIGN.md §4 staging slabs
        (``J/iN/kN/act/seg`` geometry + ``g_row/g_col/g_sel/dinv`` gains)
        in the exact contract ``ops.fused_bucket_pass`` consumes. External
        callers (benchmarks, tooling) use this instead of solver privates."""
        return self._buckets

    # ------------------------------------------------------------------ init
    def init_state(self) -> ParallelState:
        n, dt = self.n, self.dtype
        p = self.p
        return ParallelState(
            x=jnp.asarray(p.x0(), dt),
            f=jnp.asarray(p.f0(), dt) if p.has_f else None,
            yd=self._zero_duals(),
            ypair=jnp.zeros((2, n, n), dt) if p.has_f else None,
            ybox=jnp.zeros((2, n, n), dt) if p.box is not None else None,
            passes=jnp.zeros((), jnp.int32),
        )

    def _zero_duals(self) -> list[jax.Array]:
        # slab_shape is (1, D, 3, T, C); the solver stores (D, 3, T, C).
        return [
            jnp.zeros(bl.slab_shape[1:], self.dtype) for bl in self.layout.buckets
        ]

    # ----------------------------------------------------- engine hooks
    # Dual conversions, pair/box steps, metrics and run_until live on
    # SolverRuntime (core/engine.py); this solver only customizes device
    # placement and the kernel-backed violation probe.
    def _slab_state_shape(self, slab: np.ndarray) -> tuple[int, ...]:
        return slab.shape[1:]  # drop the unit procs axis

    def _triangle_violation(self, x):
        # Ghost triangles are masked inside the kernel (``n_live``), so
        # padded serve instances take the same probe as full solves.
        if self.use_kernel:
            from repro.kernels.metric_project import ops as kops

            return kops.triangle_violation(
                metrics_device.symmetrize(self._dprob.mask, x),
                n_live=None if self.n_real >= self.n else self.n_real,
            )
        return super()._triangle_violation(x)

    # ------------------------------------------------------------- one pass
    def _sweep_fn(self):
        # Legacy (fused=False) path only, which never runs a kernel.
        from repro.kernels.metric_project import ref as kref

        return kref.sweep_ref_slab

    def _diagonal_body(self, x, diag, T: int):
        """Legacy (``fused=False``) diagonal body: re-derives the folded
        geometry and re-gathers the weight slices on every diagonal of
        every pass. Kept as the PR-1 benchmark baseline; the fused path
        replaces all of this with static staging slabs."""
        i1, k1, s1 = diag["i"], diag["k"], diag["s"]
        i2, k2, s2 = diag["i2"], diag["k2"], diag["s2"]
        yslab = diag["y"]
        eps = float(self.p.eps)
        J, iN, kN, active, seg = folded_geometry(i1, k1, s1, i2, k2, s2, T)
        if self.n_real < self.n:  # ghost sets masked out (DESIGN.md §8)
            active = active & (kN < self.n_real)

        rowb = _gather(x, (iN, J), 0.0)
        colb = _gather(x, (J, kN), 0.0)
        xikp = jnp.stack(
            [_gather(x, (i1, k1), 0.0), _gather(x, (i2, k2), 0.0)]
        )
        w_row = _gather(self._w, (iN, J), 1.0)
        w_col = _gather(self._w, (J, kN), 1.0)
        w_ikp = jnp.stack(
            [_gather(self._w, (i1, k1), 1.0), _gather(self._w, (i2, k2), 1.0)]
        )

        sweep = self._sweep_fn()
        nrow, ncol, nxikp, new_yslab = sweep(
            rowb, colb, xikp, yslab, w_row, w_col, w_ikp, active, seg, eps
        )

        x = _scatter_add(x, (iN, J), jnp.where(active, nrow - rowb, 0))
        x = _scatter_add(x, (J, kN), jnp.where(active, ncol - colb, 0))
        x = _scatter_add(
            x, (i1, k1), jnp.where(s1 > 0, nxikp[0] - xikp[0], 0)
        )
        x = _scatter_add(
            x, (i2, k2), jnp.where(s2 > 0, nxikp[1] - xikp[1], 0)
        )
        return x, new_yslab

    def _triangle_sweeps(self, x, yd: list[jax.Array]):
        """All triangle constraints of one pass: one fused bucket program
        per bucket (default), or the legacy per-diagonal scan."""
        new_yd = []
        buckets = [
            {"T": b["T"]} | s
            for b, s in zip(self._buckets, self._staged_view())
        ]
        if self.fused and self.use_kernel:
            from repro.kernels.metric_project import ops as kops

            for b, yb in zip(buckets, yd):
                x, nyb = kops.fused_bucket_pass(
                    x, yb, b, unroll=self.sweep_unroll
                )
                new_yd.append(nyb)
        elif self.fused:
            from repro.kernels.metric_project import ref as kref

            for b, yb in zip(buckets, yd):
                x, nyb = kref.fused_bucket_pass_ref(
                    x, yb, b, unroll=self.sweep_unroll
                )
                new_yd.append(nyb)
        else:
            for b, yb in zip(buckets, yd):
                body = functools.partial(self._diagonal_body, T=b["T"])
                xs = {key: b[key] for key in ("i", "k", "s", "i2", "k2", "s2")}
                x, nyb = jax.lax.scan(body, x, xs | {"y": yb})
                new_yd.append(nyb)
        return x, new_yd

    def _one_pass(self, st: ParallelState) -> ParallelState:
        x, new_yd = self._triangle_sweeps(st.x, st.yd)
        f, ypair, ybox = st.f, st.ypair, st.ybox
        mask = self._mask
        if self.p.has_f:
            with jax.named_scope("repro.pass.pair"):
                x2, f2, ypair = self._pair_step(x, f, ypair)
                x = jnp.where(mask, x2, x)
                f = jnp.where(mask, f2, f)
                ypair = jnp.where(mask[None], ypair, 0)
        if self.p.box is not None:
            with jax.named_scope("repro.pass.box"):
                x2, ybox = self._box_step(x, ybox)
                x = jnp.where(mask, x2, x)
                ybox = jnp.where(mask[None], ybox, 0)
        return ParallelState(x, f, new_yd, ypair, ybox, st.passes + 1)

    # ------------------------------------------------------ multi-pass run
    # ``run(passes=P)`` — one jitted lax.scan over passes with the
    # periodic ||Δx||_inf probe — is inherited from SolverRuntime
    # (``_multi_pass_fn``); ``fused=False`` host-loops ``_pass_fn``.
