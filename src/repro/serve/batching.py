"""Vmapped multi-instance solve engine (DESIGN.md §8).

``BatchedSolver`` runs B independent MetricQP instances of one shape
bucket as a *single* device program. The single-instance machinery is
reused wholesale — the staged fused pass (`ref.fused_bucket_pass_ref`),
the pair/box steps (`engine.pair_step` / `engine.box_step`), the stopping
metrics (`metrics_device`) — but where `ParallelSolver` bakes its problem
data into the trace as constants, the batched engine splits every
per-pass input into

  * **shared statics** (one copy per bucket shape, traced as constants):
    the schedule layout, folded geometry / step-mask / seg slabs, lane
    tables — pure functions of ``bucket_n`` alone;
  * **per-instance operands** (stacked with a leading B axis, passed as
    runtime arguments): ``(w, d, c)`` problem data, the staged projection
    gains derived from w on device, the live-pair mask and ghost count
    ``n_real`` — so a new batch of weight matrices NEVER recompiles.

``run_until`` is the batched twin of the engine's solve-to-tolerance
runtime: one jitted ``lax.while_loop`` whose body runs ``check_every``
vmapped passes and evaluates the per-instance stopping rule
(`engine.stop_converged`) as a (B,) vector on device. Converged instances
**freeze**: their slots are select-restored after every chunk (a no-op in
lock-step vmap execution), so stragglers keep sweeping while finished
instances hold their stopped state and pass counter — exactly the state a
standalone `ParallelSolver.run_until` of the same padded instance stops
at, pinned to 1e-10 by tests/test_serve.py.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, metrics_device, schedule as sched
from repro.core.problems import MetricQP
from repro.kernels.metric_project import ref as kref
from repro.serve.buckets import Family, family_of, pad_problem

__all__ = [
    "BatchedSolver",
    "BatchedState",
    "ContinuousBatcher",
    "InstanceBatch",
    "stack_instances",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BatchedState:
    """State of B stacked instances: leading axis of every leaf is the
    batch slot. ``passes`` is per instance (slots freeze independently)."""

    x: jax.Array  # (B, n, n)
    f: jax.Array | None
    yd: list[jax.Array]  # per bucket: (B, D, 3, T, Cl)
    ypair: jax.Array | None  # (B, 2, n, n)
    ybox: jax.Array | None
    passes: jax.Array  # (B,) int32


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class InstanceBatch:
    """Per-instance problem data, stacked: the runtime operands of the
    batched runner (a new batch never recompiles). ``n_real[b] = 0``
    marks an empty slot (all-ghost; converges at the first check)."""

    w: jax.Array  # (B, n, n)
    d: jax.Array
    c_x: jax.Array
    w_f: jax.Array | None
    c_f: jax.Array | None
    n_real: jax.Array  # (B,) int32

    @property
    def batch(self) -> int:
        return int(self.w.shape[0])


def stack_instances(
    problems: list[MetricQP | None],
    bucket_n: int,
    family: Family,
    dtype,
) -> InstanceBatch:
    """Ghost-pad each problem to ``bucket_n`` and stack the batch.

    ``None`` entries become empty slots (n_real = 0, inert data). Every
    real problem must match ``family`` — eps/has_f/box are compile-time
    constants of the batched program.
    """
    n = bucket_n
    zeros = np.zeros((n, n), np.float64)
    ones = np.ones((n, n), np.float64)
    ws, ds, cxs, wfs, cfs, n_real = [], [], [], [], [], []
    for p in problems:
        if p is None:
            ws.append(ones)
            ds.append(zeros)
            cxs.append(zeros)
            wfs.append(ones)
            cfs.append(zeros)
            n_real.append(0)
            continue
        got = family_of(p, dtype)
        if got != family:
            raise ValueError(
                f"instance family {got} does not match batch family {family}"
            )
        pp = pad_problem(p, bucket_n)
        ws.append(pp.w)
        ds.append(pp.d)
        cxs.append(pp.c_x)
        wfs.append(pp.w_f if pp.w_f is not None else ones)
        cfs.append(pp.c_f if pp.c_f is not None else zeros)
        n_real.append(p.n)
    stack = lambda xs: jnp.asarray(np.stack(xs), dtype)
    return InstanceBatch(
        w=stack(ws),
        d=stack(ds),
        c_x=stack(cxs),
        w_f=stack(wfs) if family.has_f else None,
        c_f=stack(cfs) if family.has_f else None,
        n_real=jnp.asarray(np.asarray(n_real, np.int32)),
    )


def _freeze(done, old, new):
    """Select-restore frozen slots across a whole state pytree."""

    def sel(a, b):
        if a is None:
            return None
        d = done.reshape(done.shape + (1,) * (a.ndim - 1))
        return jnp.where(d, a, b)

    return jax.tree_util.tree_map(sel, old, new)


class BatchedSolver:
    """Vmapped fused-pass Dykstra for one (bucket_n, batch, family) slot
    of the serving ladder (see module docstring and DESIGN.md §8).

    Args:
      bucket_n: canonical padded instance size of this bucket.
      batch: number of instance slots B.
      family: problem family (eps/has_f/box/dtype) — the compile key.
      num_buckets: diagonal buckets of the schedule (same knob as
        ``ParallelSolver.bucket_diagonals``).
      sweep_unroll: inner-scan unroll of the fused sweep.
      use_kernel: route the triangle sweeps through the gen-3 Pallas
        megakernel — the whole (B, ...) bucket runs as one bucket program
        per pass (DESIGN.md §10), bitwise-equal per instance
        to the vmapped jnp fused reference. Gains/masks stay runtime
        operands either way, so new batches never recompile.
    """

    def __init__(
        self,
        bucket_n: int,
        batch: int,
        family: Family,
        num_buckets: int = 6,
        sweep_unroll: int = 4,
        use_kernel: bool = False,
    ):
        self.bucket_n = self.n = int(bucket_n)
        self.batch = int(batch)
        self.family = family
        self.dtype = jnp.dtype(family.dtype)
        self.sweep_unroll = max(1, int(sweep_unroll))
        self.use_kernel = bool(use_kernel)
        self.num_buckets = max(1, int(num_buckets))
        self.layout = sched.build_layout(
            self.n, num_buckets=self.num_buckets, procs=1
        )
        # Shared statics: lane tables + folded geometry/masks (weight
        # slabs of the ones-stage are discarded — weights are operands).
        stage = sched.build_static_stage(
            self.layout, np.ones((self.n, self.n)), np.dtype(self.dtype)
        )
        self._geo = [
            dict(
                i=jnp.asarray(bl.i[0], jnp.int32),
                k=jnp.asarray(bl.k[0], jnp.int32),
                s=jnp.asarray(bl.sizes[0], jnp.int32),
                i2=jnp.asarray(bl.i2[0], jnp.int32),
                k2=jnp.asarray(bl.k2[0], jnp.int32),
                s2=jnp.asarray(bl.sizes2[0], jnp.int32),
                J=jnp.asarray(sb.J[0]),
                iN=jnp.asarray(sb.iN[0]),
                kN=jnp.asarray(sb.kN[0]),
                seg=jnp.asarray(sb.seg[0]),
            )
            for bl, sb in zip(self.layout.buckets, stage)
        ]
        self._act0 = [jnp.asarray(sb.active[0]) for sb in stage]
        self._runner_cache: dict = {}
        self._fn_cache: dict = {}
        #: (B, R) chunk-boundary ||Δx||_inf trajectories of the last
        #: run_until (oldest first per row, -1.0 where fewer chunks ran).
        self.last_residuals = None

    # ------------------------------------------------------------ plumbing
    @property
    def _wide_dtype(self):
        if jax.config.jax_enable_x64 and self.dtype != jnp.float64:
            return jnp.float64
        return self.dtype

    def stack(self, problems: list[MetricQP | None]) -> InstanceBatch:
        """Pad + stack a list of instances into this solver's slots."""
        if len(problems) > self.batch:
            raise ValueError(f"{len(problems)} instances > batch {self.batch}")
        problems = list(problems) + [None] * (self.batch - len(problems))
        return stack_instances(problems, self.n, self.family, self.dtype)

    def _init_expr(self, inst: InstanceBatch) -> BatchedState:
        """The init-state expression (traceable; shared by ``init_state``
        and the jitted slot-refill merge, so a refilled slot restarts
        from bitwise the state a fresh drain-mode batch would give it)."""
        mask_all = jnp.triu(jnp.ones((self.n, self.n), bool), k=1)
        eps = self.family.eps
        x0 = jnp.where(mask_all, -inst.c_x / (eps * inst.w), 0.0)
        f0 = None
        if self.family.has_f:
            f0 = jnp.where(mask_all, -inst.c_f / (eps * inst.w_f), 0.0)
        B, n, dt = self.batch, self.n, self.dtype
        return BatchedState(
            x=x0.astype(dt),
            f=None if f0 is None else f0.astype(dt),
            yd=[
                jnp.zeros((B,) + bl.slab_shape[1:], dt)
                for bl in self.layout.buckets
            ],
            ypair=(
                jnp.zeros((B, 2, n, n), dt)
                if self.family.has_f else None
            ),
            ybox=(
                jnp.zeros((B, 2, n, n), dt)
                if self.family.box is not None else None
            ),
            passes=jnp.zeros((self.batch,), jnp.int32),
        )

    def init_state(self, inst: InstanceBatch) -> BatchedState:
        fn = self._fn_cache.get("init")
        if fn is None:
            fn = self._fn_cache["init"] = jax.jit(self._init_expr)
        return fn(inst)

    # ------------------------------------------------- per-instance pieces
    def _aux_one(self, w, n_real):
        """Staged per-instance operands: projection gains gathered from
        this instance's W on device, ghost-masked step masks, live-pair
        mask. Mirrors ``ParallelSolver._stage_buckets`` expression-for-
        expression so batched == standalone bit-for-bit."""
        dt = self.dtype
        one = jnp.asarray(1.0, dt)
        eps = jnp.asarray(self.family.eps, dt)
        gains = []
        for geo, act0 in zip(self._geo, self._act0):
            gather = lambda r, c: w.at[r, c].get(mode="fill", fill_value=1.0)
            w_row = jnp.where(act0, gather(geo["iN"], geo["J"]), one)
            w_col = jnp.where(act0, gather(geo["J"], geo["kN"]), one)
            w_ikp = jnp.stack(
                [
                    jnp.where(geo["i"] >= 0, gather(geo["i"], geo["k"]), one),
                    jnp.where(geo["i2"] >= 0, gather(geo["i2"], geo["k2"]), one),
                ],
                axis=1,
            )  # (D, 2, Cl)
            g_row = (one / w_row) / eps
            g_col = (one / w_col) / eps
            g_ikp = (one / w_ikp) / eps
            g_sel = jnp.where(
                geo["seg"], g_ikp[:, 1][:, None, :], g_ikp[:, 0][:, None, :]
            )
            dinv = one / (g_row + g_sel + g_col)
            gains.append(
                dict(
                    act=act0 & (geo["kN"] < n_real),
                    g_row=g_row,
                    g_col=g_col,
                    g_sel=g_sel,
                    dinv=dinv,
                )
            )
        return dict(
            gains=gains,
            mask=metrics_device.live_pair_mask(self.n, n_real),
        )

    def _pairbox_one(self, x, f, ypair, ybox, inst1, aux):
        """Pair/box projections of one instance under its live-pair mask
        (shared by the vmapped-ref and kernel batch passes)."""
        mask = aux["mask"]
        eps = self.family.eps
        if self.family.has_f:
            x2, f2, ypair = engine.pair_step(
                x, f, ypair, w=inst1.w, wf=inst1.w_f, d=inst1.d, eps=eps
            )
            x = jnp.where(mask, x2, x)
            f = jnp.where(mask, f2, f)
            ypair = jnp.where(mask[None], ypair, 0)
        if self.family.box is not None:
            lo, hi = self.family.box
            x2, ybox = engine.box_step(
                x, ybox, w=inst1.w, lo=lo, hi=hi, eps=eps
            )
            x = jnp.where(mask, x2, x)
            ybox = jnp.where(mask[None], ybox, 0)
        return x, f, ypair, ybox

    def _pass_one(self, st, inst1, aux):
        """One fused pass of a single instance (vmapped by the runner)."""
        x, yd = st.x, st.yd
        new_yd = []
        for geo, g, yb in zip(self._geo, aux["gains"], yd):
            x, nyb = kref.fused_bucket_pass_ref(
                x, yb, geo | g, unroll=self.sweep_unroll
            )
            new_yd.append(nyb)
        x, f, ypair, ybox = self._pairbox_one(
            x, st.f, st.ypair, st.ybox, inst1, aux
        )
        return BatchedState(x, f, new_yd, ypair, ybox, st.passes + 1)

    def _pass_batch(self, st, inst, aux):
        """One fused pass of the WHOLE batch: per bucket, one gen-3
        megakernel call covers all B instances (the leading instance grid
        axis of DESIGN.md §10) — bitwise-equal to ``vmap(_pass_one)``.
        ``aux`` is the vmapped ``_aux_one`` output (leading B axis on
        every gain/mask leaf)."""
        from repro.kernels.metric_project import ops as kops

        x, yd = st.x, st.yd
        new_yd = []
        for geo, g, yb in zip(self._geo, aux["gains"], yd):
            x, nyb = kops.fused_bucket_pass_batched(
                x, yb, geo, g, unroll=self.sweep_unroll
            )
            new_yd.append(nyb)
        x, f, ypair, ybox = jax.vmap(self._pairbox_one)(
            x, st.f, st.ypair, st.ybox, inst, aux
        )
        return BatchedState(x, f, new_yd, ypair, ybox, st.passes + 1)

    def _dprob_one(self, inst1, mask, n_real, dtype):
        up = lambda a: None if a is None else a.astype(dtype)
        return metrics_device.DeviceProblem(
            n=self.n,
            eps=self.family.eps,
            has_f=self.family.has_f,
            box=self.family.box,
            mask=mask,
            d=up(inst1.d),
            w=up(inst1.w),
            c_x=up(inst1.c_x),
            w_f=up(inst1.w_f),
            c_f=up(inst1.c_f),
            n_real=n_real,
        )

    def _probe_one(self, st, inst1, aux, n_real):
        """(viol, gap, obj) of one instance in the wide dtype — the same
        reductions as ``SolverRuntime._stopping_pair`` and
        ``_wide_objective``."""
        wd = self._wide_dtype
        dp = self._dprob_one(inst1, aux["mask"], n_real, wd)
        up = lambda a: None if a is None else a.astype(wd)
        x, f = up(st.x), up(st.f)
        viol = metrics_device.max_violation(dp, x, f)
        gap = metrics_device.duality_gap(dp, x, f, up(st.ypair), up(st.ybox))
        obj = metrics_device.qp_objective(dp, x, f)
        return viol, gap, obj

    # ------------------------------------------------------------ runners
    def _loop_pieces(self, check_every: int, stop_rule: str, res_hist: int):
        """Build the chunk loop's ``(cond, body)`` closure factory.

        ``make(inst, tol, max_passes)`` returns the predicate and body of
        ONE convergence-check chunk over an ``engine.ChunkCarry`` — the
        exact while_loop pieces ``run_until`` jits, also exposed one
        body-application at a time through ``_chunk_fn`` for the
        continuous-batching serve loop (DESIGN.md §12). Sharing the
        closure is what makes continuous-mode chunk boundaries bitwise
        identical to drain-mode ones.
        """

        def make(inst, tol, max_passes):
                dt = self._wide_dtype
                aux = jax.vmap(self._aux_one)(inst.w, inst.n_real)

                def chunk_guarded(st1, inst1, aux1):
                    # Exact host k = min(chunk, remaining) semantics for a
                    # partial final chunk — the engine's per-pass guard.
                    # Under vmap the cond lowers to a select that
                    # materializes BOTH branches' state every pass (~4x a
                    # plain pass), so the runner only takes this chunk
                    # when some live slot would overshoot max_passes.
                    def guarded(s):
                        return jax.lax.cond(
                            s.passes < max_passes,
                            lambda q: self._pass_one(q, inst1, aux1),
                            lambda q: q,
                            s,
                        )

                    s2, _ = jax.lax.scan(
                        lambda c, _: (guarded(c), None),
                        st1, None, length=check_every,
                    )
                    return s2

                def chunk_plain(st1, inst1, aux1):
                    s2, _ = jax.lax.scan(
                        lambda c, _: (self._pass_one(c, inst1, aux1), None),
                        st1, None, length=check_every,
                    )
                    return s2

                def kchunk_plain(st1):
                    s2, _ = jax.lax.scan(
                        lambda c, _: (self._pass_batch(c, inst, aux), None),
                        st1, None, length=check_every,
                    )
                    return s2

                def kchunk_guarded(st1):
                    # Batch-level twin of chunk_guarded: the vmapped
                    # per-instance cond lowers to a per-slot select, so
                    # freezing at-limit slots after a full batch pass is
                    # bit-identical.
                    def step(c, _):
                        c2 = self._pass_batch(c, inst, aux)
                        return _freeze(c.passes >= max_passes, c, c2), None

                    s2, _ = jax.lax.scan(
                        step, st1, None, length=check_every
                    )
                    return s2

                if self.use_kernel:
                    run_plain, run_guarded = kchunk_plain, kchunk_guarded
                else:
                    vchunk_guarded = jax.vmap(chunk_guarded)
                    vchunk_plain = jax.vmap(chunk_plain)
                    run_plain = lambda q: vchunk_plain(q, inst, aux)
                    run_guarded = lambda q: vchunk_guarded(q, inst, aux)
                vprobe = jax.vmap(self._probe_one)

                def cond(carry):
                    return jnp.any(
                        ~carry.done & (carry.state.passes < max_passes)
                    )

                def body(carry):
                    # carry's obj is the previous check's objective — the
                    # plateau rule's progress baseline.
                    s, done = carry.state, carry.done
                    viol_p, gap_p, obj_prev = carry.viol, carry.gap, carry.obj
                    resbuf, k, div = carry.resbuf, carry.k, carry.div
                    # Scalar predicate -> a true XLA branch: the fast
                    # unguarded chunk whenever no live slot can cross
                    # max_passes inside it (frozen slots are restored by
                    # the select below, so their overshoot is harmless).
                    safe = jnp.all(
                        done | (s.passes + check_every <= max_passes)
                    )
                    s2 = jax.lax.cond(safe, run_plain, run_guarded, s)
                    s2 = _freeze(done, s, s2)
                    # (B, R) ring buffer of the chunk-boundary ||Δx||_inf
                    # probe — the solo runtime's residual trajectory, one
                    # row per instance. A slot records only the chunks it
                    # was live for (its write cursor freezes with it), so
                    # row i IS the trajectory solo run_until would export
                    # for instance i.
                    B = self.batch
                    res = jnp.max(
                        jnp.abs(s2.x - s.x).reshape(B, -1), axis=1
                    ).astype(dt)
                    viol, gap, obj = vprobe(s2, inst, aux, inst.n_real)
                    viol, gap, obj = (
                        viol.astype(dt), gap.astype(dt), obj.astype(dt)
                    )
                    # Per-slot divergence guard (the solo engine's,
                    # vectorized): a slot whose probe goes non-finite is
                    # restored to its last finite chunk boundary and
                    # frozen — a NaN-poisoned instance stops costing
                    # passes after one chunk while healthy slots keep
                    # sweeping. In fault-free runs every select below is
                    # an identity, preserving batched==solo bitwise
                    # parity.
                    bad = (~done) & ~(
                        jnp.isfinite(res)
                        & jnp.isfinite(viol)
                        & jnp.isfinite(gap)
                    )
                    s2 = _freeze(bad, s, s2)
                    viol = jnp.where(bad, viol_p, viol)
                    gap = jnp.where(bad, gap_p, gap)
                    obj = jnp.where(bad, obj_prev, obj)
                    live = (~done) & (s.passes < max_passes)
                    slot = jax.lax.broadcasted_iota(
                        jnp.int32, (B, res_hist), 1
                    )
                    write = live[:, None] & (
                        slot == (k % res_hist)[:, None]
                    )
                    rec = jnp.where(bad, jnp.asarray(jnp.inf, dt), res)
                    resbuf = jnp.where(write, rec[:, None], resbuf)
                    k = k + live.astype(jnp.int32)
                    div = div | bad
                    done = done | bad | engine.stop_converged(
                        stop_rule, tol, viol, gap, obj, obj_prev
                    )
                    return engine.ChunkCarry(
                        s2, done, viol, gap, obj, resbuf, k, div
                    )

                return cond, body

        return make

    def _until_fn(self, check_every: int, stop_rule: str,
                  res_hist: int = 16):
        key = (check_every, stop_rule, res_hist)
        fn = self._runner_cache.get(key)
        if fn is None:
            make = self._loop_pieces(check_every, stop_rule, res_hist)

            def runner(st, inst, tol, max_passes):
                cond, body = make(inst, tol, max_passes)
                carry = engine.init_chunk_carry(
                    st, self.batch, res_hist, self._wide_dtype
                )
                return jax.lax.while_loop(cond, body, carry)

            fn = self._runner_cache[key] = jax.jit(runner)
        return fn

    def _chunk_fn(self, check_every: int, stop_rule: str,
                  res_hist: int = 16):
        """One body-application of the chunk loop, jitted: the
        continuous-batching stepper. Identity when no slot is live (the
        while_loop's exit condition), otherwise exactly one chunk —
        ``check_every`` passes + probe + freeze/divergence/stop updates —
        so interleaving refills at chunk boundaries never perturbs
        co-resident slots (each slot's trajectory depends only on its own
        operands under the vmapped/kernel pass)."""
        key = ("chunk", check_every, stop_rule, res_hist)
        fn = self._fn_cache.get(key)
        if fn is None:
            make = self._loop_pieces(check_every, stop_rule, res_hist)

            def step(carry, inst, tol, max_passes):
                cond, body = make(inst, tol, max_passes)
                return jax.lax.cond(
                    cond(carry), body, lambda c: c, carry
                )

            fn = self._fn_cache[key] = jax.jit(step)
        return fn

    def start_carry(self, inst: InstanceBatch, state=None,
                    residual_history: int = 16) -> engine.ChunkCarry:
        """Fresh chunk-loop carry over ``state`` (default: the batch's
        init state) — the continuous loop's entry point."""
        st = state if state is not None else self.init_state(inst)
        return engine.init_chunk_carry(
            st, self.batch, max(1, int(residual_history)), self._wide_dtype
        )

    def _refill_fn(self):
        """Jitted slot refill: merge ``new_inst`` rows into ``inst`` and
        reset the carry's state/bookkeeping at ``mask`` rows — the new
        slots restart from exactly the init state + fresh carry drain
        mode would give them, while untouched rows pass through bitwise
        (every select is an identity off-mask). Operands only — a refill
        NEVER recompiles."""
        fn = self._fn_cache.get("refill")
        if fn is None:

            def refill(carry, inst, new_inst, mask):
                inst2 = jax.tree_util.tree_map(
                    lambda old, new: jnp.where(
                        mask.reshape(mask.shape + (1,) * (new.ndim - 1)),
                        new, old,
                    ),
                    inst, new_inst,
                )
                st0 = self._init_expr(inst2)
                st = _freeze(mask, st0, carry.state)
                dt = self._wide_dtype
                inf = jnp.asarray(jnp.inf, dt)
                sel = lambda a, b: jnp.where(mask, a, b)
                return engine.ChunkCarry(
                    state=st,
                    done=sel(jnp.zeros_like(carry.done), carry.done),
                    viol=sel(inf, carry.viol),
                    gap=sel(inf, carry.gap),
                    obj=sel(inf, carry.obj),
                    resbuf=jnp.where(
                        mask[:, None], jnp.asarray(-1.0, dt), carry.resbuf
                    ),
                    k=sel(jnp.zeros_like(carry.k), carry.k),
                    div=sel(jnp.zeros_like(carry.div), carry.div),
                ), inst2

            fn = self._fn_cache["refill"] = jax.jit(refill)
        return fn

    def dual_stats(self, st: BatchedState, inst: InstanceBatch) -> dict:
        """Per-instance triangle dual stats (min/max/l1/active count),
        reduced slab-native under **ghost-aware** valid masks: the
        structural padding mask of the shared layout AND'd with each
        instance's ``kN < n_real`` set predicate (a traced per-instance
        scalar — one compiled program serves every batch). Ghost and
        padding cells hold don't-care values under fused execution and
        never enter the reductions. Returns length-B numpy arrays, keys
        as ``metrics_device.triangle_dual_stats``."""
        fn = self._fn_cache.get("dual_stats")
        if fn is None:
            valid0 = [
                jnp.asarray(m[0])
                for m in sched.slab_valid_masks(self.layout)
            ]

            def one(yd1, n_real):
                masks = [
                    v & (geo["kN"][:, None, :, :] < n_real)
                    for v, geo in zip(valid0, self._geo)
                ]
                return metrics_device.triangle_dual_stats(yd1, masks)

            fn = self._fn_cache["dual_stats"] = jax.jit(jax.vmap(one))
        out = jax.device_get(fn(st.yd, inst.n_real))
        return {k: np.asarray(v) for k, v in out.items()}

    def _objectives_fn(self):
        fn = self._fn_cache.get("objectives")
        if fn is None:

            def obj_one(st, inst1, n_real):
                mask = metrics_device.live_pair_mask(self.n, n_real)
                dp = self._dprob_one(inst1, mask, n_real, self._wide_dtype)
                up = lambda a: None if a is None else a.astype(self._wide_dtype)
                return (
                    metrics_device.qp_objective(dp, up(st.x), up(st.f)),
                    metrics_device.lp_objective(dp, up(st.x)),
                )

            fn = self._fn_cache["objectives"] = jax.jit(
                jax.vmap(obj_one)
            )
        return fn

    def run_until(
        self,
        inst: InstanceBatch,
        state: BatchedState | None = None,
        *,
        tol: float = 1e-4,
        max_passes: int = 100,
        check_every: int = 10,
        stop_rule: str = "absolute",
        residual_history: int = 16,
    ):
        """Solve all B instances to tolerance inside ONE jitted
        while_loop with per-instance device-side stopping (see module
        docstring). Semantics per instance are exactly
        ``SolverRuntime.run_until`` — same chunking, same cumulative
        ``max_passes`` guard, same ``stop_rule`` decision — evaluated as
        (B,) vectors; converged slots freeze while stragglers sweep.

        Returns ``(state, info)`` where every info value is a length-B
        numpy array (``passes``, ``converged``, ``diverged``,
        ``max_violation``, ``duality_gap``, ``qp_objective``,
        ``lp_objective``), plus
        ``residuals`` — the (B, R) chunk-boundary ``||Δx||_inf``
        trajectory ring buffer (R = ``residual_history``): row i holds
        the most recent R chunk residuals of instance i oldest-first
        (-1.0 where fewer chunks ran — a slot's cursor freezes with it),
        exactly the trajectory the solo runtime exports; mirrored to
        ``self.last_residuals``.

        A slot whose residual probe goes non-finite trips the per-slot
        divergence guard: it is restored to its last finite chunk
        boundary and frozen (``diverged[b] = True``, ``converged[b] =
        False``) while healthy slots keep sweeping — one poisoned
        instance never costs the batch its remaining passes.
        """
        if stop_rule not in engine.STOP_RULES:
            raise ValueError(
                f"unknown stop_rule {stop_rule!r}; "
                f"expected one of {engine.STOP_RULES}"
            )
        st = state if state is not None else self.init_state(inst)
        check_every = max(1, int(check_every))
        residual_history = max(1, int(residual_history))
        fn = self._until_fn(check_every, stop_rule, residual_history)
        out = fn(st, inst, float(tol), int(max_passes))
        st, done, viol, gap, obj, resbuf, kcnt, div = (
            out.state, out.done, out.viol, out.gap, out.obj,
            out.resbuf, out.k, out.div,
        )
        div = np.asarray(jax.device_get(div), bool)
        viol, gap, obj = (
            np.asarray(jax.device_get(v), np.float64) for v in (viol, gap, obj)
        )
        qp, lp = (
            np.asarray(jax.device_get(v), np.float64)
            for v in self._objectives_fn()(st, inst, inst.n_real)
        )
        if not np.all(np.isfinite(viol)):
            # no chunk ran (some slot already at/over max_passes), or a
            # slot diverged on its very first chunk (its carried pair is
            # still inf): probe once so callers get a real stopping
            # vector — NaN for slots whose restored state is itself
            # poisoned, which stop_converged below treats as False.
            probe = self._fn_cache.get("probe")
            if probe is None:
                probe = self._fn_cache["probe"] = jax.jit(
                    jax.vmap(self._probe_one)
                )
            aux = jax.vmap(self._aux_one)(inst.w, inst.n_real)
            viol, gap, obj = (
                np.asarray(jax.device_get(v), np.float64)
                for v in probe(st, inst, aux, inst.n_real)
            )
        with np.errstate(invalid="ignore"):
            converged = (
                np.asarray(
                    engine.stop_converged(
                        stop_rule, float(tol), viol, gap, obj,
                        np.full_like(obj, np.inf),
                    )
                )
                | np.asarray(jax.device_get(done))
            ) & ~div
        resbuf = np.asarray(jax.device_get(resbuf), np.float64)
        kcnt = np.asarray(jax.device_get(kcnt), np.int64)
        residuals = np.array(
            [
                row if k <= residual_history
                else np.roll(row, -(k % residual_history))
                for row, k in zip(resbuf, kcnt)
            ]
        )
        self.last_residuals = residuals
        info = {
            "passes": np.asarray(jax.device_get(st.passes), np.int64),
            "converged": np.asarray(converged, bool),
            "diverged": div,
            "max_violation": viol,
            "duality_gap": gap,
            "qp_objective": qp,
            "lp_objective": lp,
            "stop_rule": stop_rule,
            "residuals": residuals,
        }
        return st, info


class ContinuousBatcher:
    """Slot-level continuous batching over one ``BatchedSolver``
    (DESIGN.md §12): a long-lived chunk-loop carry whose slots retire and
    refill independently at chunk boundaries, instead of a whole batch
    waiting for its slowest instance.

    The loop contract is the drain-mode one taken apart: ``step()`` is
    one body-application of the SAME jitted chunk closure ``run_until``
    while_loops (``BatchedSolver._chunk_fn``); ``harvest()`` pops slots
    the while_loop's exit condition would have released
    (``engine.chunk_terminal``) and reproduces ``run_until``'s host
    epilogue per slot; ``admit()`` resets freed slots to exactly the init
    state + fresh carry a drain-mode batch would give the new instance
    (``BatchedSolver._refill_fn`` — weights are runtime operands, so a
    refill never recompiles). Because each slot's trajectory depends only
    on its own operands under the vmapped/kernel pass, and a slot's
    stopping checks land at multiples of ``check_every`` from its OWN
    pass 0, every instance's harvested ``x``/``passes`` are bitwise what
    the same instance gets in a drain-mode batch — the mixed-age
    extension of the §8 batched==solo pin, pinned by
    tests/test_continuous.py.

    Host-side bookkeeping only lives here (which tag occupies which
    slot); all math is the solver's. Not thread-safe: one owner (the
    scheduler's per-bucket worker) drives it.
    """

    def __init__(
        self,
        solver: BatchedSolver,
        *,
        tol: float = 1e-4,
        max_passes: int = 100,
        check_every: int = 10,
        stop_rule: str = "absolute",
        residual_history: int = 16,
    ):
        if stop_rule not in engine.STOP_RULES:
            raise ValueError(
                f"unknown stop_rule {stop_rule!r}; "
                f"expected one of {engine.STOP_RULES}"
            )
        self.solver = solver
        self.tol = float(tol)
        self.max_passes = int(max_passes)
        self.check_every = max(1, int(check_every))
        self.stop_rule = stop_rule
        self.residual_history = max(1, int(residual_history))
        #: slot -> tag of the occupying instance (None = free).
        self.tags: list = [None] * solver.batch
        self._n_real: dict = {}  # tag -> native n of its problem
        self.inst = solver.stack([])
        self.carry = solver.start_carry(
            self.inst, residual_history=self.residual_history
        )
        self.chunks_run = 0
        self.refills = 0
        #: sum over chunks of occupied slots — occupancy numerator.
        self.occupied_chunks = 0

    # ---------------------------------------------------------- occupancy
    def free_slots(self) -> list[int]:
        return [b for b, t in enumerate(self.tags) if t is None]

    @property
    def occupied(self) -> int:
        return sum(t is not None for t in self.tags)

    @property
    def live(self) -> bool:
        return self.occupied > 0

    # ------------------------------------------------------------- refill
    def admit(self, assignments: list) -> None:
        """Fill freed slots: ``assignments`` is ``[(slot, problem, tag)]``
        (each slot currently free). One jitted refill merges every row at
        once; co-resident rows pass through bitwise."""
        if not assignments:
            return
        B = self.solver.batch
        probs: list = [None] * B
        mask = np.zeros((B,), bool)
        for slot, problem, tag in assignments:
            if self.tags[slot] is not None:
                raise ValueError(f"slot {slot} is occupied by {self.tags[slot]!r}")
            probs[slot] = problem
            mask[slot] = True
        new_inst = stack_instances(
            probs, self.solver.n, self.solver.family, self.solver.dtype
        )
        self.carry, self.inst = self.solver._refill_fn()(
            self.carry, self.inst, new_inst, jnp.asarray(mask)
        )
        for slot, problem, tag in assignments:
            self.tags[slot] = tag
            self._n_real[tag] = problem.n
            self.refills += 1

    # --------------------------------------------------------------- step
    def step(self) -> None:
        """Advance the live slots one convergence chunk (identity when
        no slot is live — the while_loop's exit condition)."""
        fn = self.solver._chunk_fn(
            self.check_every, self.stop_rule, self.residual_history
        )
        self.carry = fn(
            self.carry, self.inst, self.tol, self.max_passes
        )
        self.chunks_run += 1
        self.occupied_chunks += self.occupied

    # ------------------------------------------------------------ harvest
    def harvest(self) -> list:
        """Pop every occupied terminal slot: returns
        ``[(slot, tag, x_row, f_row, info)]`` with ``info`` exactly the
        per-instance ``run_until`` report (passes / converged / diverged /
        stopping pair / objectives / residual trajectory). Freed slots
        are immediately admittable."""
        c = self.carry
        done = np.asarray(jax.device_get(c.done), bool)
        passes = np.asarray(jax.device_get(c.state.passes), np.int64)
        term = np.asarray(
            engine.chunk_terminal(done, passes, self.max_passes), bool
        )
        slots = [
            b for b, t in enumerate(self.tags)
            if t is not None and term[b]
        ]
        if not slots:
            return []
        st, inst, solver = c.state, self.inst, self.solver
        x = np.asarray(jax.device_get(st.x))
        f = None if st.f is None else np.asarray(jax.device_get(st.f))
        div = np.asarray(jax.device_get(c.div), bool)
        viol, gap, obj = (
            np.asarray(jax.device_get(v), np.float64)
            for v in (c.viol, c.gap, c.obj)
        )
        qp, lp = (
            np.asarray(jax.device_get(v), np.float64)
            for v in solver._objectives_fn()(st, inst, inst.n_real)
        )
        if not np.all(np.isfinite(viol[slots])):
            # drain-mode's epilogue fallback, per slot: a slot that never
            # completed a finite chunk (diverged on its first, or
            # max_passes=0) still gets a real stopping probe — NaN when
            # the restored state is itself poisoned, which the stop rule
            # treats as not-converged.
            probe = solver._fn_cache.get("probe")
            if probe is None:
                probe = solver._fn_cache["probe"] = jax.jit(
                    jax.vmap(solver._probe_one)
                )
            aux = jax.vmap(solver._aux_one)(inst.w, inst.n_real)
            pv, pg, po = (
                np.asarray(jax.device_get(v), np.float64)
                for v in probe(st, inst, aux, inst.n_real)
            )
            bad = ~np.isfinite(viol)
            viol = np.where(bad, pv, viol)
            gap = np.where(bad, pg, gap)
            obj = np.where(bad, po, obj)
        resbuf = np.asarray(jax.device_get(c.resbuf), np.float64)
        kcnt = np.asarray(jax.device_get(c.k), np.int64)
        R = self.residual_history
        out = []
        for b in slots:
            tag = self.tags[b]
            n = self._n_real.pop(tag)
            conv = bool(
                engine.harvest_converged(
                    self.stop_rule, self.tol,
                    viol[b: b + 1], gap[b: b + 1], obj[b: b + 1],
                    done[b: b + 1], div[b: b + 1],
                )[0]
            )
            row = resbuf[b]
            residuals = (
                row if kcnt[b] <= R else np.roll(row, -(kcnt[b] % R))
            )
            info = {
                "passes": int(passes[b]),
                "converged": conv,
                "diverged": bool(div[b]),
                "max_violation": float(viol[b]),
                "duality_gap": float(gap[b]),
                "qp_objective": float(qp[b]),
                "lp_objective": float(lp[b]),
                "stop_rule": self.stop_rule,
                "residuals": residuals,
                "n": n,
            }
            out.append((
                b, tag, x[b],
                None if f is None else f[b],
                info,
            ))
            self.tags[b] = None
        # Park the freed rows: a slot harvested at the pass cap has
        # done=False, passes==max_passes, and if it stays empty (queue
        # drained) it would flip the chunk loop's ``safe`` predicate and
        # route EVERY later chunk through the guarded per-pass-cond body
        # (~4x a plain chunk). Latching done=True freezes the row (same
        # freeze a converged slot gets — bitwise inert for co-residents)
        # and keeps the plain path; refill resets done at re-admitted
        # rows, so a parked slot is indistinguishable from a fresh one.
        park = self.solver._fn_cache.get("park")
        if park is None:
            park = self.solver._fn_cache["park"] = jax.jit(jnp.logical_or)
        freed = np.zeros((self.solver.batch,), bool)
        freed[slots] = True
        self.carry = dataclasses.replace(
            self.carry, done=park(self.carry.done, jnp.asarray(freed))
        )
        return out
