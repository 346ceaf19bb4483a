"""Multi-device parallel Dykstra via shard_map (the distributed solver).

Maps the paper's multithreaded execution model onto a TPU/CPU device mesh:

  * **Set assignment** (paper Fig. 3 deals the r-th set of a diagonal to
    processor ``r mod p``): here each device holds a contiguous run of a
    diagonal's folded lanes, device r lanes ``r·Cl … r·Cl + Cl − 1``
    (Cl = ceil(lanes/p) over the bucket), materialized as per-device work
    arrays of shape ``(p, D, Cl)`` so the shard_map simply splits axis 0.
    Any partition of a diagonal's lanes is exact, its sets being mutually
    conflict-free; the round robin was the paper's choice for balance,
    which folded lanes of near-uniform height give here too. A contiguous
    run makes a device's operands columns of dense blocks of X, which the
    kernel path reads and writes without element gathers (DESIGN.md §10).
  * **Per-device dual arrays** (paper §III.D): every triplet is visited by the
    same device in the same order each pass, so its three duals live in a
    *schedule-native* slab ``(p, D, 3, T, Cl)`` sharded on axis 0 — the exact
    analogue of the paper's per-processor arrays; duals never travel. The
    layout (and its dense conversion maps) is built centrally by
    ``core/schedule.py::build_layout`` and shared with the single-device
    solver (DESIGN.md §3).
  * **Shared-memory X → replicated X + exact delta merge**: each device holds
    a replica of X and updates only the entries of its own sets. Because the
    schedule is conflict-free, per-device deltas are supported on *disjoint*
    cells, so one ``psum`` per diagonal merges them exactly (not an average —
    this is why the paper's schedule parallelizes Dykstra where the
    averaging-based parallel Dykstra of Iusem & De Pierro fails).

The pair/box constraint families are O(n^2), conflict-free across pairs, and
executed replicated (identical on every device; no communication).

Collective cost: one (n, n) psum per diagonal, ~2n psums per pass. The
per-device compute is O(n^3 / p) — the solver becomes compute-bound once
n / p is large, which is the trillion-constraint regime the paper targets
(PERF.md §4-6 give the merge's share of a four-chip pass at n = 1024).
The merge is named for the trace: each diagonal's all-reduce (and, in
psum mode, the add of its sum to X) runs under the device scope
``repro.shard.merge``, and each traced bucket program adds the bytes it
all-reduces to the counter ``repro.shard.merge.bytes``.

**Fused-pass execution** (DESIGN.md §9, the default): the per-device sweep
consumes staged *projection gains* ``g = (1/w)/eps`` and ``dinv``
(`ref.fused_diag_sweep`, the same staged math as the single-device fused
path — no per-step division, no restore-selects, scan unroll), and
``run(passes=P)`` executes all P passes as ONE jitted ``lax.scan`` whose
body is the shard_map pass — one dispatch and one host sync for the whole
run instead of one per pass, with the periodic ``||Δx||_inf`` probe on
``last_residuals``. ``fused=False`` keeps the PR-1-style path (runtime
weight division in ``sweep_ref_slab``, one jitted dispatch per pass) as
the benchmark baseline.

Pair/box steps, host/device metrics, dual conversions and the ``run`` /
``run_until`` runtimes are inherited from
``core/engine.py::SolverRuntime`` (DESIGN.md §7/§9); this module only adds
the sharded specifics — a psum-max violation probe whose apex blocks are
dealt over the mesh axis, and sharded placement of imported dual slabs.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import metrics_device, schedule as sched
from repro.core.engine import SolverRuntime
from repro.core.parallel_dykstra import folded_geometry
from repro.core.problems import MetricQP

__all__ = ["ShardedSolver", "ShardedState"]

AXIS = "solver"


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ShardedState:
    x: jax.Array  # (n, n), replicated
    f: jax.Array | None  # (n, n), replicated
    yd: list[jax.Array]  # per bucket: (p, D_b, 3, T_b, Cl_b), sharded axis 0
    ypair: jax.Array | None  # (2, n, n), replicated
    ybox: jax.Array | None
    passes: jax.Array


class ShardedSolver(SolverRuntime):
    """Distributed Dykstra over a 1-D device mesh.

    Args:
      problem: MetricQP instance.
      mesh: a jax Mesh with a single axis named "solver" (built by
        launch/mesh.py for production; tests pass small host meshes).
      num_buckets: diagonal buckets (contiguous, order preserving).
      use_kernel: route the inner sweep through the Pallas kernel.
      fused: fused execution (DESIGN.md §9, default) — staged projection
        gains in the per-device sweep and the single-scan multi-pass
        runner. False keeps the legacy sweep + one dispatch per pass as
        the benchmark baseline.
      sweep_unroll: unroll factor of the inner sequential-in-j scan
        (fused path only).
      probe_every: evaluate the runner's convergence probe every this
        many passes (``last_residuals`` holds -1.0 at skipped passes).
      probe_block_c: lane block width of the kernel-backed violation
        probe (use_kernel=True; DESIGN.md §14). None = full width.
    """

    def __init__(
        self,
        problem: MetricQP,
        mesh: Mesh,
        dtype=jnp.float32,
        num_buckets: int = 4,
        use_kernel: bool = False,
        delta_mode: str = "psum",
        fused: bool = True,
        sweep_unroll: int = 4,
        probe_every: int = 1,
        probe_block_c: int | None = None,
    ):
        """delta_mode:
          "psum"   — paper-faithful shared-memory emulation: one (n, n)
                     delta all-reduce per diagonal.
          "packed" — beyond-paper (§Perf H3): all_gather only the TOUCHED
                     row/column segments in schedule layout — the payload is
                     the actual update support (~2·C·T values per diagonal)
                     instead of the full n² matrix.
        """
        assert mesh.axis_names == (AXIS,), mesh.axis_names
        assert delta_mode in ("psum", "packed"), delta_mode
        if use_kernel and delta_mode == "packed":
            raise ValueError(
                "use_kernel=True requires delta_mode='psum': the gen-3 "
                "megakernel emits the per-diagonal delta matrix directly "
                "(DESIGN.md §10); the packed compact exchange re-derives "
                "deltas host-side and has no kernel path."
            )
        if use_kernel and not fused:
            raise ValueError(
                "use_kernel=True requires fused=True: the gen-1 "
                "per-diagonal kernel is test-oracle only, so the legacy "
                "path has no kernel sweep."
            )
        self.p = problem
        self.n = problem.n
        self.mesh = mesh
        self.dtype = dtype
        self.nproc = mesh.devices.size
        self.use_kernel = use_kernel
        self.delta_mode = delta_mode
        self.fused = fused
        self.sweep_unroll = max(1, int(sweep_unroll))
        self.probe_every = max(1, int(probe_every))
        # Lane (column) block of the kernel-backed violation probe
        # (use_kernel=True): None keeps one full-width column block; at
        # n ≫ 10³ pick a finite width so the per-device probe's VMEM per
        # grid step stays bounded (DESIGN.md §14).
        self.probe_block_c = (
            None if probe_block_c is None else int(probe_block_c)
        )
        self.num_buckets = num_buckets
        # Schedule-native dual layout, shared with ParallelSolver and the
        # elastic re-sharder (DESIGN.md §3).
        with obs.span("repro.stage.layout"):
            self.layout = sched.build_layout(
                self.n, num_buckets=num_buckets, procs=self.nproc
            )
        self._w = jnp.asarray(problem.w, dtype)
        self._d = jnp.asarray(problem.d, dtype)
        self._wf = jnp.asarray(problem.w_f, dtype) if problem.has_f else None
        self._mask = jnp.triu(jnp.ones((self.n, self.n), bool), k=1)
        self._work_dev = self._stage_buckets()
        self._pass_fn = self._jit_staged(self._one_pass)

    def _stage_buckets(self) -> list[dict]:
        """Static staging (DESIGN.md §4): the lane tables, folded
        geometry, step masks and projection gains (or, with
        ``fused=False``, the gathered weight slabs) are pass-invariant,
        so they are built once and sharded on the device axis like the
        dual slabs; the per-device scan body does no index math and no
        weight gathers. In ``ParallelSolver``'s order: the lane tables
        are put first (``repro.stage.put``), then each bucket's slabs are
        built on the host (``repro.stage.slabs``) and put in turn, and
        the last put span waits for every transfer. Each array goes from
        the host straight to the devices, each device receiving only its
        own shard."""
        shard = NamedSharding(self.mesh, P(AXIS))
        put = lambda a: jax.device_put(a, shard)
        with obs.span("repro.stage.put"):
            work = [
                {key: put(getattr(bl, key))
                 for key in ("i", "k", "sizes", "i2", "k2", "sizes2")}
                | {"first": put(np.ascontiguousarray(np.broadcast_to(
                    bl.first_sets, (bl.procs, bl.num_diagonals, 2)))),
                   "T": bl.T}
                for bl in self.layout.buckets
            ]
        npdt = np.dtype(self.dtype)
        with obs.span("repro.stage.slabs"):
            stage = sched.build_static_stage(self.layout, self.p.w, npdt)
        for wk, sb in zip(work, stage):
            with obs.span("repro.stage.slabs"):
                slabs = {"J": sb.J, "iN": sb.iN, "kN": sb.kN,
                         "act": sb.active, "seg": sb.seg}
                if self._fused_sweep:
                    # Projection gains (DESIGN.md §4), staged with the
                    # procs axis — the exact expressions of
                    # ParallelSolver._stage_buckets, so the per-step math
                    # is shared bit-for-bit with the single-device fused
                    # path.
                    one = npdt.type(1.0)
                    epsc = npdt.type(self.p.eps)
                    g_row = (one / sb.w_row) / epsc
                    g_col = (one / sb.w_col) / epsc
                    g_ikp = (one / sb.w_ikp) / epsc  # (procs, D, 2, Cl)
                    g_sel = np.where(
                        sb.seg,
                        g_ikp[:, :, 1][:, :, None, :],
                        g_ikp[:, :, 0][:, :, None, :],
                    ).astype(npdt)
                    dinv = (one / (g_row + g_sel + g_col)).astype(npdt)
                    slabs |= {"g_row": g_row, "g_col": g_col,
                              "g_sel": g_sel, "dinv": dinv}
                else:
                    slabs |= {"w_row": sb.w_row, "w_col": sb.w_col,
                              "w_ikp": sb.w_ikp}
            with obs.span("repro.stage.put"):
                wk.update({key: put(a) for key, a in slabs.items()})
        with obs.span("repro.stage.put"):
            jax.block_until_ready(work)
        return work

    # ------------------------------------------------------------------ state
    def init_state(self) -> ShardedState:
        n, dt, prob = self.n, self.dtype, self.p
        shard = NamedSharding(self.mesh, P(AXIS))
        rep = NamedSharding(self.mesh, P())
        yd = [
            jax.device_put(jnp.zeros(bl.slab_shape, dt), shard)
            for bl in self.layout.buckets
        ]
        return ShardedState(
            x=jax.device_put(jnp.asarray(prob.x0(), dt), rep),
            f=jax.device_put(jnp.asarray(prob.f0(), dt), rep) if prob.has_f else None,
            yd=yd,
            ypair=jnp.zeros((2, n, n), dt) if prob.has_f else None,
            ybox=jnp.zeros((2, n, n), dt) if prob.box is not None else None,
            passes=jnp.zeros((), jnp.int32),
        )

    # ------------------------------------------------------------- the pass
    @property
    def _fused_sweep(self) -> bool:
        """True when the per-device sweep runs on staged projection gains —
        the jnp ``ref.fused_diag_sweep`` body, or the gen-3 megakernel in
        sharded mode when ``use_kernel`` (both consume the same
        staged gains; DESIGN.md §10). Only the legacy baseline
        (``fused=False``) keeps the runtime-weight slab contract."""
        return self.fused

    def _sweep_fn(self):
        # Legacy (fused=False) path only. The gen-1 per-diagonal kernel is
        # test-oracle-only since PR 6, so this is always the jnp sweep.
        from repro.kernels.metric_project import ref as kref

        return kref.sweep_ref_slab

    def _device_bucket(self, x, yd_b, work, T: int):
        """Runs on ONE device (inside shard_map): sweep its assigned folded
        lanes of every diagonal in this bucket, psum-merging X deltas per
        diagonal. ``work`` is the bucket's sharded work-array dict: lane
        tables plus the static staging slabs (geometry, masks, weights) —
        nothing is re-derived or re-gathered per diagonal."""
        eps = float(self.p.eps)
        fused = self._fused_sweep
        sweep = None if fused else self._sweep_fn()
        if fused and self.use_kernel:
            from repro.kernels.metric_project import ops as kops
        elif fused:
            from repro.kernels.metric_project import ref as kref
        # shard_map keeps the device axis with local extent 1 — drop it.
        yd_b = yd_b[0]
        work = {key: val[0] for key, val in work.items()}
        # Bytes this bucket program all-reduces: per diagonal, the (n, n)
        # delta matrix, or the packed (2T + 7, p, Cl) segments.
        D, Cl = yd_b.shape[0], yd_b.shape[-1]
        cells = (x.size if self.delta_mode == "psum"
                 else (2 * T + 7) * self.nproc * Cl)
        obs.count("repro.shard.merge.bytes", D * cells * x.dtype.itemsize)

        if fused and self.use_kernel:
            # Gen-3 megakernel over the whole bucket (DESIGN.md §10): the
            # band engine reads this device's lanes as dense blocks of a
            # padded replica of X, and each diagonal's act-masked delta
            # matrix is all-reduced and added to it inside the bucket
            # program — bitwise-equal to the scatter construction below.
            x, new_yd = kops.fused_bucket_pass_sharded(
                x, yd_b, work, AXIS, unroll=self.sweep_unroll
            )
            return x, new_yd[None]

        def diag_body(x, inp):
            w, yslab = inp  # per-diagonal slices of work arrays + dual slab
            i1, k1, s1 = w["i"], w["k"], w["sizes"]
            i2, k2, s2 = w["i2"], w["k2"], w["sizes2"]
            J, iN, kN = w["J"], w["iN"], w["kN"]
            active, seg = w["act"], w["seg"]
            get = lambda a, idx, fill: a.at[idx].get(mode="fill", fill_value=fill)
            rowb = get(x, (iN, J), 0.0)
            colb = get(x, (J, kN), 0.0)
            xikp = jnp.stack([get(x, (i1, k1), 0.0), get(x, (i2, k2), 0.0)])
            # per-device duals: schedule-native slab (paper §III.D) — pure
            # slicing, no gather/transpose, because this device always
            # re-visits the same slots in the same order.
            if fused:
                # staged-gain sweep (DESIGN.md §4/§9): masked outputs are
                # don't-care — deltas are act-masked below and the dual
                # conversion maps / valid masks skip padding cells.
                nrow, ncol, nxikp, new_yslab = kref.fused_diag_sweep(
                    rowb, colb, xikp, yslab, w["g_row"], w["g_col"],
                    w["g_sel"], w["dinv"], active, seg,
                    unroll=self.sweep_unroll,
                )
            else:
                nrow, ncol, nxikp, new_yslab = sweep(
                    rowb, colb, xikp, yslab, w["w_row"], w["w_col"],
                    w["w_ikp"], active, seg, eps
                )
            add = lambda a, idx, v: a.at[idx].add(
                v, mode="drop", unique_indices=True
            )
            d_row = jnp.where(active, nrow - rowb, 0)
            d_col = jnp.where(active, ncol - colb, 0)
            d_ik1 = jnp.where(s1 > 0, nxikp[0] - xikp[0], 0)
            d_ik2 = jnp.where(s2 > 0, nxikp[1] - xikp[1], 0)
            if self.delta_mode == "psum":
                delta = jnp.zeros_like(x)
                delta = add(delta, (iN, J), d_row)
                delta = add(delta, (J, kN), d_col)
                delta = add(delta, (i1, k1), d_ik1)
                delta = add(delta, (i2, k2), d_ik2)
                # conflict-free ⇒ exact merge (disjoint supports), no average
                with jax.named_scope("repro.shard.merge"):
                    x = x + jax.lax.psum(delta, AXIS)
            else:
                # §Perf H3: exchange only the TOUCHED segments in schedule
                # layout — payload per diagonal is p·(2·T·Cl + 7·Cl) floats
                # (the update support) instead of the n² matrix. Each device
                # owns a distinct slot of the compact buffer, so the psum is
                # an exact merge; conflict-freedom makes the post-merge
                # scatter exact too.
                T_, Cl_ = d_row.shape
                rank = jax.lax.axis_index(AXIS)
                p_ = self.nproc
                pack = jnp.zeros((2 * T_ + 7, p_, Cl_), d_row.dtype)
                asf = lambda a: a[None].astype(d_row.dtype)
                mine = jnp.concatenate(
                    [d_row, d_col, d_ik1[None], d_ik2[None],
                     asf(i1), asf(k1), asf(i2), asf(k2), asf(s1)], axis=0
                )  # (2T+7, Cl)
                pack = jax.lax.dynamic_update_slice(
                    pack, mine[:, None, :], (0, rank, 0)
                )
                with jax.named_scope("repro.shard.merge"):
                    # invariant, compact payload
                    pack = jax.lax.psum(pack, AXIS)
                # every device reconstructs all p lane groups: flatten the
                # (p, Cl) lane tables and reuse the shared folded geometry
                g_row = jnp.moveaxis(pack[:T_], 1, 0)        # (p, T, Cl)
                g_col = jnp.moveaxis(pack[T_:2 * T_], 1, 0)
                g_ik1 = pack[2 * T_]                         # (p, Cl)
                g_ik2 = pack[2 * T_ + 1]
                gint = lambda r: pack[2 * T_ + r].astype(jnp.int32).reshape(-1)
                gJ, gi, gk, _, _ = folded_geometry(
                    gint(2), gint(3), gint(6), gint(4), gint(5),
                    jnp.where(gint(4) >= 0, gint(5) - gint(4) - 1, 0), T_,
                )  # (T, p·Cl) each
                to3 = lambda a: jnp.moveaxis(a.reshape(T_, p_, Cl_), 1, 0)
                gi, gk, gJ = to3(gi), to3(gk), to3(gJ)       # (p, T, Cl)
                g_i1 = pack[2 * T_ + 2].astype(jnp.int32)
                g_k1 = pack[2 * T_ + 3].astype(jnp.int32)
                g_i2 = pack[2 * T_ + 4].astype(jnp.int32)
                g_k2 = pack[2 * T_ + 5].astype(jnp.int32)
                # padding lanes (i = -1) carry zero deltas; their indices may
                # alias real cells after clamping, so no unique_indices here
                gadd = lambda a, idx, v: a.at[idx].add(v, mode="drop")
                x = gadd(x, (gi, gJ), g_row)
                x = gadd(x, (gJ, gk), g_col)
                x = gadd(x, (g_i1, g_k1), g_ik1)
                x = gadd(x, (g_i2, g_k2), g_ik2)
            return x, new_yslab

        x, new_yd = jax.lax.scan(diag_body, x, (work, yd_b))
        return x, new_yd[None]  # restore the local device axis for out_specs

    def _staged_arrays(self) -> list[dict]:
        return [{k: v for k, v in w.items() if k != "T"}
                for w in self._work_dev]

    def _one_pass(self, st: ShardedState) -> ShardedState:
        x = st.x
        new_yd = []
        for b, work, arrays in zip(st.yd, self._work_dev,
                                   self._staged_view()):
            fn = functools.partial(self._device_bucket, T=work["T"])
            x, yb = jax.shard_map(
                fn,
                mesh=self.mesh,
                in_specs=(P(), P(AXIS), P(AXIS)),
                out_specs=(P(), P(AXIS)),
                # pallas_call has no replication rule; the per-diagonal psum
                # makes x replicated by construction.
                check_vma=not self.use_kernel,
            )(x, b, arrays)
            new_yd.append(yb)
        f, ypair, ybox = st.f, st.ypair, st.ybox
        mask = self._mask
        if self.p.has_f:
            x2, f2, ypair = self._pair_step(x, f, ypair)
            x = jnp.where(mask, x2, x)
            f = jnp.where(mask, f2, f)
            ypair = jnp.where(mask[None], ypair, 0)
        if self.p.box is not None:
            x2, ybox = self._box_step(x, ybox)
            x = jnp.where(mask, x2, x)
            ybox = jnp.where(mask[None], ybox, 0)
        return ShardedState(x, f, new_yd, ypair, ybox, st.passes + 1)

    # ----------------------------------------------------- engine hooks
    # Dual conversions, pair/box steps, metrics, the fused multi-pass
    # ``run`` and ``run_until`` live on SolverRuntime (core/engine.py);
    # this solver customizes device placement of imported slabs and
    # shards the violation probe.
    def _put_slab(self, slab: np.ndarray):
        shard = NamedSharding(self.mesh, P(AXIS))
        return jax.device_put(jnp.asarray(slab, self.dtype), shard)

    def _triangle_violation(self, x):
        """Apex slabs dealt over the mesh, partial maxima pmax-merged —
        the probe's compute scales O(n^3 / p) like the pass itself.
        ``use_kernel`` routes the lane-blocked Pallas slab kernel per
        device (DESIGN.md §14) — this was the last loud jnp fallback on
        the sharded hot path; the jnp apex-blocked reduction stays as the
        default/oracle route. Both are bitwise-equal (max is
        association-free) and both honor ghost padding via ``n_live``."""
        xs = metrics_device.symmetrize(self._dprob.mask, x)
        if self.use_kernel:
            return metrics_device.triangle_violation_sharded_kernel(
                xs, self.mesh, AXIS,
                block_c=self.probe_block_c, n_live=self._dprob.n_real,
            )
        return metrics_device.triangle_violation_sharded(
            xs, self.mesh, AXIS, n_live=self._dprob.n_real
        )
