"""Shared solver runtime: device-resident convergence engine (DESIGN.md §7).

`SolverRuntime` is the mixin both vectorized Dykstra solvers
(`ParallelSolver`, `ShardedSolver`) inherit. It owns every surface the two
previously duplicated — the pair/box constraint steps, the host metrics
report, the dense dual conversion — and adds the device-resident
convergence engine:

  * ``device_metrics(state)``  — the full (QP/LP objective, duality gap,
    max violation, optional slab-native dual stats) report as one jitted
    device program; nothing densifies, nothing loops on the host.
  * ``run_until(state, tol, max_passes, check_every)`` — a full
    solve-to-tolerance as a single jitted ``lax.while_loop``: each
    iteration runs ``check_every`` fused passes (a ``lax.scan`` over the
    subclass's ``_one_pass``) and evaluates the paper's stopping pair
    (max violation, |duality gap|) *on device*. The host is not consulted
    until the loop exits — zero host syncs per chunk, versus the one
    dispatch + one full host metrics report per chunk of the PR-2 loop.
  * ``run(state, passes)`` — the fused multi-pass runner (DESIGN.md §4/§9):
    all P passes as ONE jitted ``lax.scan`` over ``_one_pass`` with the
    periodic ``||Δx||_inf`` probe, shared verbatim by the single-device
    and the sharded solver (the scan body simply contains the subclass's
    shard_map pass when sharded). ``fused=False`` subclasses fall back to
    one jitted dispatch per pass — the benchmark baseline.

Subclass contract: provide ``p`` (MetricQP), ``n``, ``dtype``, ``layout``,
``_w``/``_d``/``_wf``/``_mask`` device constants, ``init_state()`` and
``_one_pass(state) -> state``; optionally ``fused`` / ``probe_every`` /
``_pass_fn`` (the runner knobs — defaults True / 1 / a fresh jit of
``_one_pass``), ``_staged_arrays`` (the staged device arrays the pass
reads through ``_staged_view``), and overrides for ``_triangle_violation``
(the sharded solver routes it through a psum-max, the kernel solver
through the Pallas apex-block kernel) and ``_put_slab`` (device placement
of imported dual slabs).

The float64 numpy path in `core/convergence.py` stays as the oracle the
engine is property-tested against (tests/test_engine.py, 1e-10).
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import metrics_device, schedule as sched

__all__ = [
    "STOP_RULES",
    "ChunkCarry",
    "SolverRuntime",
    "box_step",
    "chunk_terminal",
    "harvest_converged",
    "init_chunk_carry",
    "pair_step",
    "stop_converged",
]

#: Stopping rules for ``run_until`` (and the batched serve engine, which
#: applies the same rule per instance — DESIGN.md §8):
#:   absolute — the paper's pair: viol < tol and |gap| < tol.
#:   rel_gap  — viol < tol and |gap| <= tol * (1 + |qp objective|); the
#:              scale-free variant production workloads want when the
#:              objective magnitude varies across instances.
#:   plateau  — viol < tol and the qp objective moved less than
#:              tol * (1 + |obj|) since the previous convergence check:
#:              feasible and no longer making progress.
STOP_RULES = ("absolute", "rel_gap", "plateau")


def stop_converged(rule: str, tol, viol, gap, obj, prev_obj):
    """Elementwise convergence decision for one stop rule.

    All operands may be scalars (run_until) or (B,) arrays (the batched
    engine) — the expression is elementwise either way. ``prev_obj`` is
    the objective at the previous check (inf on the first: every rule
    then returns False, since viol is also still inf).
    """
    feas = viol < tol
    if rule == "absolute":
        return feas & (jnp.abs(gap) < tol)
    if rule == "rel_gap":
        return feas & (jnp.abs(gap) <= tol * (1.0 + jnp.abs(obj)))
    if rule == "plateau":
        return feas & (jnp.abs(obj - prev_obj) <= tol * (1.0 + jnp.abs(obj)))
    raise ValueError(f"unknown stop_rule {rule!r}; expected one of {STOP_RULES}")


# ------------------------------------------------------------------------
# Chunked-resume carry: the loop-invariant state of ONE convergence-check
# chunk, as a pytree. ``run_until`` (solo and batched) threads exactly this
# carry through its jitted ``lax.while_loop``; the continuous-batching
# serve loop (DESIGN.md §12) instead holds a live ``ChunkCarry`` across
# host round-trips and advances it one body-application at a time — the
# SAME body closure the while_loop runs, so a chunk boundary reached by
# the continuous loop is bitwise the chunk boundary drain-mode reaches.
# ------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ChunkCarry:
    """Everything a convergence chunk needs from the previous boundary.

    ``state`` is the subclass solver state (solo SolveState or serve
    BatchedState); every other leaf is per-instance — scalar in the solo
    runtime, length-B in the batched one. ``viol``/``gap``/``obj`` carry
    the previous check's stopping probe (inf before the first: the
    plateau baseline and the divergence guard's restore values),
    ``resbuf``/``k`` the chunk-boundary ``||Δx||_inf`` ring buffer and
    its per-instance write cursor, ``div`` the divergence-guard latch.
    """

    state: object
    done: jax.Array
    viol: jax.Array
    gap: jax.Array
    obj: jax.Array
    resbuf: jax.Array
    k: jax.Array
    div: jax.Array


def init_chunk_carry(state, batch: int, res_hist: int, dtype) -> ChunkCarry:
    """Fresh carry for a (B,)-instance chunk loop (B=1 collapses to the
    solo runtime's shape)."""
    inf = jnp.full((batch,), jnp.inf, dtype)
    return ChunkCarry(
        state=state,
        done=jnp.zeros((batch,), bool),
        viol=inf,
        gap=inf,
        obj=inf,
        resbuf=jnp.full((batch, res_hist), -1.0, dtype),
        k=jnp.zeros((batch,), jnp.int32),
        div=jnp.zeros((batch,), bool),
    )


def chunk_terminal(done, passes, max_passes):
    """Per-instance terminal predicate of the chunk loop — exactly the
    negation of the while_loop's live set, so a slot the continuous loop
    harvests is a slot drain-mode's loop would have exited for."""
    return done | (passes >= max_passes)


def harvest_converged(rule: str, tol, viol, gap, obj, done, div):
    """The ``converged`` vector ``run_until`` reports for a finished
    carry (host-side epilogue, numpy in / numpy out): the stop rule
    re-evaluated on the final probe OR the device-side ``done`` latch,
    never a diverged slot. Matches the batched ``run_until`` epilogue
    bit for bit so continuous-mode harvests agree with drain mode."""
    with np.errstate(invalid="ignore"):
        conv = np.asarray(
            stop_converged(
                rule, float(tol), viol, gap, obj, np.full_like(obj, np.inf)
            )
        )
    return (conv | np.asarray(done, bool)) & ~np.asarray(div, bool)


# ------------------------------------------------------------------------
# Pair/box constraint steps as pure functions. The runtime methods below
# close these over the solver's device constants; the batched serve engine
# (repro/serve/batching.py) instead vmaps them with per-instance (w, wf, d)
# operands — which is why the problem data are explicit arguments, not
# attributes.
# ------------------------------------------------------------------------
def pair_step(x, f, ypair, *, w, wf, d, eps):
    """Both pair constraints, all pairs at once (conflict-free family)."""
    iw_x, iw_f = 1.0 / w, 1.0 / wf
    denom = iw_x + iw_f
    # x - f <= d
    xv = x + ypair[0] * iw_x / eps
    fv = f - ypair[0] * iw_f / eps
    theta = eps * jnp.maximum(xv - fv - d, 0.0) / denom
    x = xv - theta * iw_x / eps
    f = fv + theta * iw_f / eps
    y0 = theta
    # -x - f <= -d
    xv = x - ypair[1] * iw_x / eps
    fv = f - ypair[1] * iw_f / eps
    theta = eps * jnp.maximum(d - xv - fv, 0.0) / denom
    x = xv + theta * iw_x / eps
    f = fv + theta * iw_f / eps
    return x, f, jnp.stack([y0, theta])


def box_step(x, ybox, *, w, lo, hi, eps):
    iw_x = 1.0 / w
    xv = x + ybox[0] * iw_x / eps
    theta_hi = eps * jnp.maximum(xv - hi, 0.0) / iw_x
    x = xv - theta_hi * iw_x / eps
    xv = x - ybox[1] * iw_x / eps
    theta_lo = eps * jnp.maximum(lo - xv, 0.0) / iw_x
    x = xv + theta_lo * iw_x / eps
    return x, jnp.stack([theta_hi, theta_lo])


class _HostView:
    """Host float64 snapshot of a solver state, in the shape
    ``convergence.report`` expects."""

    def __init__(self, st):
        asnp = lambda a: None if a is None else np.asarray(a, np.float64)
        self.x = asnp(st.x)
        self.f = asnp(st.f)
        self.ypair = asnp(st.ypair)
        self.ybox = asnp(st.ybox)
        self.passes = int(st.passes)


class SolverRuntime:
    """Runtime shared by the vectorized solvers (see module docstring)."""

    #: per-pass ``||x_{p+1} - x_p||_inf`` trajectory of the last fused
    #: ``run`` / the chunk-boundary trajectory of the last ``run_until``
    #: (-1.0 at passes the periodic probe skipped).
    last_residuals = None

    # ------------------------------------------------------ device constants
    @property
    def _n_real(self) -> int | None:
        """Live-point count when the problem is ghost-padded (DESIGN.md
        §8); None (all live) unless the subclass sets ``n_real``."""
        nr = getattr(self, "n_real", None)
        return None if nr is None or nr >= self.n else int(nr)

    @functools.cached_property
    def _dprob(self) -> metrics_device.DeviceProblem:
        return metrics_device.DeviceProblem.from_qp(
            self.p, self.dtype, n_real=self._n_real
        )

    @functools.cached_property
    def _dprob_wide(self) -> metrics_device.DeviceProblem:
        """Float64 twin of the constants for the stopping decision, when
        the process allows it (x64). With x64 off this is the compute
        dtype — the stopping pair then inherits that dtype's reduction
        noise (~1e-3 relative at f32/n≈100), so pick ``tol`` above it or
        enable x64 for tight tolerances."""
        if jax.config.jax_enable_x64 and self.dtype != jnp.float64:
            return metrics_device.DeviceProblem.from_qp(
                self.p, jnp.float64, n_real=self._n_real
            )
        return self._dprob

    @functools.cached_property
    def _slab_valid(self) -> list[jax.Array]:
        # Ghost-aware on padded problems (DESIGN.md §8): ghost sets are
        # never visited, so under fused execution their slab cells hold
        # don't-care values just like schedule padding — both are masked.
        return [
            jnp.asarray(m)
            for m in sched.slab_valid_masks(self.layout, self._n_real)
        ]

    # ---------------------------------------------------- staged operands
    # Pass-invariant device arrays ``_one_pass`` reads besides the state
    # (staged geometry, gains, masks). An array a traced function closes
    # over is embedded in the program as a constant (gigabytes of HLO at
    # n ~ 10^3), so the jitted runners take them as an operand instead.
    _staged_tracer = None

    def _staged_arrays(self):
        """Subclass hook: the staged array pytree (None: nothing staged)."""
        return None

    def _staged_view(self):
        """The staged arrays as traced code must read them: the operand
        inside a ``_jit_staged`` program, the arrays themselves outside."""
        view = self._staged_tracer
        return self._staged_arrays() if view is None else view

    def _jit_staged(self, fn):
        """``jax.jit(fn)``, with the staged arrays passed as an operand.
        The returned call's ``lower(*args)`` lowers the same program."""

        def traced(staged, *args):
            outer, self._staged_tracer = self._staged_tracer, staged
            try:
                return fn(*args)
            finally:
                self._staged_tracer = outer

        jitted = jax.jit(traced)

        def call(*args):
            return jitted(self._staged_arrays(), *args)

        call.lower = lambda *args: jitted.lower(self._staged_arrays(), *args)
        return call

    @functools.cached_property
    def _engine_cache(self) -> dict:
        return {"report": {}, "until": {}, "probe": None}

    def _ensure_constants(self):
        """Materialize the cached device constants eagerly. Must run
        before any engine jit: a cached_property first touched *inside* a
        trace would capture (and leak) tracers instead of constants."""
        self._dprob, self._dprob_wide, self._slab_valid

    # ------------------------------------------- pair/box constraint families
    # O(n^2), conflict-free across pairs, executed replicated — identical in
    # both solvers. The math lives in the module-level pure functions
    # (vmap-safe; the batched serve engine calls them with per-instance
    # operands); these methods just close them over the device constants.
    def _pair_step(self, x, f, ypair):
        return pair_step(
            x, f, ypair, w=self._w, wf=self._wf, d=self._d,
            eps=float(self.p.eps),
        )

    def _box_step(self, x, ybox):
        lo, hi = self.p.box
        return box_step(
            x, ybox, w=self._w, lo=lo, hi=hi, eps=float(self.p.eps)
        )

    # --------------------------------------------------- dual conversions
    # Dense (n, n, n) is the *interchange* format only (DESIGN.md §2):
    # these are host-side diagnostics/test boundaries, never on any solve
    # or metrics hot path.
    def duals_to_dense(self, st) -> np.ndarray:
        """Schedule-native duals → dense ``ytri[a, b, c]`` (DESIGN.md §2).
        Diagnostics/tests only — the engine never calls this."""
        return sched.duals_to_dense(self.layout, st.yd)

    def _put_slab(self, slab: np.ndarray):
        """Device placement of one imported dual slab (subclass hook)."""
        return jnp.asarray(slab, self.dtype)

    def dense_to_duals(self, ytri: np.ndarray) -> list[jax.Array]:
        """Dense ``ytri`` → state slabs (e.g. to resume from the oracle)."""
        slabs = sched.dense_to_duals(self.layout, ytri, np.float64)
        return [self._put_slab(s.reshape(self._slab_state_shape(s))) for s in slabs]

    def _slab_state_shape(self, slab: np.ndarray) -> tuple[int, ...]:
        """Shape a converted slab takes inside the state pytree (the
        single-device solver drops the unit procs axis)."""
        return slab.shape

    # ----------------------------------------------------- device metrics
    def _triangle_violation(self, x):
        """Triangle-family max violation on device (subclasses override:
        psum-max when sharded, Pallas kernel when use_kernel).
        ``n_live`` masks ghost-apex triangles on padded problems — ghost
        x cells are 0, so an unmasked ghost apex would report the false
        slack x_ab - 0 - 0."""
        return metrics_device.triangle_violation(
            metrics_device.symmetrize(self._dprob.mask, x),
            n_live=self._dprob.n_real,
        )

    def _stopping_pair(self, st):
        """The paper's stopping pair (max violation, duality gap), traced
        on device — the while_loop probe and the metrics report share it.
        Reduced in float64 whenever x64 is enabled (the host loop's
        decision precision); see ``_dprob_wide`` for the f32 caveat."""
        dp = self._dprob_wide
        wd = dp.w.dtype
        up = lambda a: None if a is None else a.astype(wd)
        x, f = up(st.x), up(st.f)
        viol = metrics_device.max_violation(
            dp, x, f, tri=self._triangle_violation(x)
        )
        gap = metrics_device.duality_gap(dp, x, f, up(st.ypair), up(st.ybox))
        return viol, gap

    def _device_report(self, st, include_duals: bool):
        dp = self._dprob
        viol, gap = self._stopping_pair(st)
        out = {
            "passes": st.passes,
            "qp_objective": metrics_device.qp_objective(dp, st.x, st.f),
            "lp_objective": metrics_device.lp_objective(dp, st.x),
            "duality_gap": gap,
            "max_violation": viol,
        }
        if include_duals:
            out.update(
                metrics_device.triangle_dual_stats(st.yd, self._slab_valid)
            )
        return out

    def device_metrics(self, st, include_duals: bool = False) -> dict:
        """Full metrics bundle computed on device (one jitted program, one
        host sync). Same keys/semantics as the host ``metrics``; dual
        stats are reduced slab-native when requested — on ghost-padded
        problems under the ghost-aware valid masks, so they cover exactly
        the real (< n_real) triangle duals."""
        self._ensure_constants()
        cache = self._engine_cache["report"]
        key = bool(include_duals)
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = jax.jit(
                functools.partial(self._device_report, include_duals=key)
            )
        out = jax.device_get(fn(st))
        ints = ("passes", "active_constraints")
        return {k: (int(v) if k in ints else float(v)) for k, v in out.items()}

    def metrics(self, st, include_duals: bool = False) -> dict:
        """Host float64 oracle report (core/convergence.py). The device
        engine (``device_metrics``) is property-tested against this."""
        if self._n_real is not None:
            raise NotImplementedError(
                "the host oracle has no ghost-padding support; use "
                "device_metrics on padded solvers (DESIGN.md §8)"
            )
        from repro.core import convergence

        ytri = self.duals_to_dense(st) if include_duals else None
        return convergence.report(self.p, _HostView(st), ytri=ytri)

    def _wide_objective(self, st):
        """QP objective in the stopping-decision dtype (rel_gap/plateau
        operand; also the plateau rule's progress signal)."""
        dp = self._dprob_wide
        wd = dp.w.dtype
        up = lambda a: None if a is None else a.astype(wd)
        return metrics_device.qp_objective(dp, up(st.x), up(st.f))

    # ------------------------------------------------------ solve runtime
    def _multi_pass_fn(self, passes: int):
        """Jitted P-pass runner: a single ``lax.scan`` over passes (the
        subclass ``_one_pass``, pair/box steps included) — one dispatch
        and one host sync for the whole run. Emits the per-pass residual
        ``||x_{p+1} - x_p||_inf`` wherever the periodic probe fires
        (every ``probe_every`` passes; -1 elsewhere), the cheap
        convergence signal callers poll without leaving the device
        program. Shared by the single-device and sharded solvers
        (DESIGN.md §4/§9); cached per pass count."""
        cache = self._engine_cache.setdefault("runner", {})
        fn = cache.get(passes)
        if fn is None:
            probe = max(1, int(getattr(self, "probe_every", 1)))

            def multi(st):
                def body(carry, p):
                    st2 = self._one_pass(carry)
                    dt = st2.x.dtype
                    if probe == 1:
                        res = jnp.max(jnp.abs(st2.x - carry.x)).astype(dt)
                    else:
                        # lax.cond so skipped passes pay nothing for the
                        # O(n^2) reduction, not just discard its value.
                        res = jax.lax.cond(
                            (p + 1) % probe == 0,
                            lambda a, b: jnp.max(jnp.abs(a - b)).astype(dt),
                            lambda a, b: jnp.asarray(-1.0, dt),
                            st2.x, carry.x,
                        )
                    return st2, res

                return jax.lax.scan(
                    body, st, jnp.arange(passes, dtype=jnp.int32)
                )

            fn = cache[passes] = self._jit_staged(multi)
        return fn

    def run(self, state=None, passes: int = 1):
        """Run ``passes`` passes. With ``fused`` (the default) all P
        passes execute as one compiled program via ``_multi_pass_fn`` and
        the probe trajectory lands on ``last_residuals``; ``fused=False``
        host-loops one jitted dispatch per pass (benchmark baseline).
        Contract (pinned by tests): the P-pass scan produces bit-identical
        state to P single-pass runs; ``run(st, 0)`` is the identity."""
        st = state if state is not None else self.init_state()
        if passes <= 0:
            return st
        if not getattr(self, "fused", True):
            for _ in range(passes):
                st = self._pass_fn(st)
            return st
        st, self.last_residuals = self._multi_pass_fn(passes)(st)
        return st

    def _until_fn(self, check_every: int, stop_rule: str, res_hist: int):
        self._ensure_constants()
        cache = self._engine_cache["until"]
        key = (check_every, stop_rule, res_hist)
        fn = cache.get(key)
        if fn is None:

            def runner(st, tol, max_passes):
                # carry the stopping pair in its own (wide) dtype so the
                # on-device decision keeps the probe's full precision
                dt = self._dprob_wide.w.dtype

                def guarded(s):
                    # Per-pass cumulative cap: the final chunk runs only
                    # its real remainder (host k = min(chunk, remaining)
                    # semantics) with ONE compiled program per
                    # check_every — no specialized remainder runner.
                    return jax.lax.cond(
                        s.passes < max_passes, self._one_pass, lambda q: q, s
                    )

                def chunk(s):
                    s2, _ = jax.lax.scan(
                        lambda c, _: (guarded(c), None),
                        s, None, length=check_every,
                    )
                    return s2

                def cond(carry):
                    s, viol, gap, obj, prev_obj, _, _, div = carry
                    conv = stop_converged(stop_rule, tol, viol, gap, obj,
                                          prev_obj)
                    return (~div) & (~conv) & (s.passes < max_passes)

                def body(carry):
                    s, viol_p, gap_p, obj_prev, _, resbuf, k, div = carry
                    s2 = chunk(s)
                    with jax.named_scope("repro.chunk.probe"):
                        viol, gap = self._stopping_pair(s2)
                    with jax.named_scope("repro.chunk.objective"):
                        obj = self._wide_objective(s2)
                    res = jnp.max(jnp.abs(s2.x - s.x)).astype(dt)
                    # Divergence guard: isfinite of the residual probe is
                    # folded into the stopping decision — a NaN/Inf chunk
                    # flips ``div`` (the loop exits), restores the last
                    # finite chunk boundary, and keeps that boundary's
                    # stopping pair. Same device program, zero extra host
                    # syncs — versus scanning NaNs for the remaining
                    # max_passes and reporting garbage.
                    finite = (
                        jnp.isfinite(res)
                        & jnp.isfinite(viol)
                        & jnp.isfinite(gap)
                    )
                    sel = lambda a, b: jnp.where(finite, a, b)
                    s2 = jax.tree.map(sel, s2, s)
                    viol = sel(viol.astype(dt), viol_p)
                    gap = sel(gap.astype(dt), gap_p)
                    obj = sel(obj.astype(dt), obj_prev)
                    # ring buffer of the periodic ||Δx||_inf probe, one
                    # entry per executed chunk (ROADMAP: the fused
                    # runner's residual trajectory, threaded through the
                    # while_loop); a diverged chunk records inf.
                    resbuf = jax.lax.dynamic_update_index_in_dim(
                        resbuf, sel(res, jnp.asarray(jnp.inf, dt)),
                        k % res_hist, 0,
                    )
                    return (s2, viol, gap, obj, obj_prev, resbuf, k + 1,
                            div | ~finite)

                inf = jnp.asarray(jnp.inf, dt)
                resbuf0 = jnp.full((res_hist,), -1.0, dt)
                k0 = jnp.zeros((), jnp.int32)
                div0 = jnp.zeros((), bool)
                return jax.lax.while_loop(
                    cond, body, (st, inf, inf, inf, inf, resbuf0, k0, div0)
                )

            fn = cache[key] = self._jit_staged(runner)
        return fn

    def _probe_fn(self):
        self._ensure_constants()
        fn = self._engine_cache["probe"]
        if fn is None:
            fn = self._engine_cache["probe"] = jax.jit(self._stopping_pair)
        return fn

    def _objectives_fn(self):
        """Cached jit of the O(n^2) objectives alone — run_until reports
        them in info without re-running the O(n^3) violation reduction."""
        self._ensure_constants()
        fn = self._engine_cache.get("objectives")
        if fn is None:
            dp = self._dprob

            def obj(st):
                return (
                    metrics_device.qp_objective(dp, st.x, st.f),
                    metrics_device.lp_objective(dp, st.x),
                )

            fn = self._engine_cache["objectives"] = jax.jit(obj)
        return fn

    def _apply_entry_faults(self, faults, st):
        """Poll the ``chunk`` fault site once per ``run_until`` call (the
        host-visible chunk/window boundary). ``nan_poison`` poisons the
        live iterate — the on-device divergence guard must then stop the
        loop; ``straggler`` sleeps a deterministic beat. Duck-typed: any
        object with ``poll(site)`` works (serve.faults.FaultInjector)."""
        for spec in faults.poll("chunk"):
            if spec.kind == "nan_poison":
                st = dataclasses.replace(st, x=st.x * jnp.nan)
            elif spec.kind == "straggler":
                time.sleep(float(spec.payload.get("seconds", 0.001)))
        return st

    def run_until(
        self,
        state=None,
        *,
        tol: float = 1e-4,
        max_passes: int = 100,
        check_every: int = 10,
        stop_rule: str = "absolute",
        residual_history: int = 16,
        faults=None,
    ):
        """Solve to tolerance: run passes in chunks of ``check_every``
        until the ``stop_rule`` fires or the *cumulative* pass counter
        reaches ``max_passes``. Rules (module ``STOP_RULES``): the
        default ``absolute`` is the paper's pair (viol, |gap|) < tol;
        ``rel_gap`` scales the gap test by the objective magnitude;
        ``plateau`` stops when feasible and the objective stalls between
        checks. Every rule evaluates on device inside the loop.

        The whole chunk loop is one jitted ``lax.while_loop`` with an
        on-device stopping test — a solve is a single device program with
        zero host syncs per chunk (the PR-2 launcher paid one dispatch
        plus a full host-numpy metrics report per chunk). ``max_passes``
        is cumulative so resumed states (checkpoints) compose; inside the
        chunk scan every pass is guarded by the cumulative cap, so a
        final partial chunk runs exactly ``max_passes - passes`` real
        passes — the host loop's ``k = min(chunk, remaining)`` schedule
        pass-for-pass, without compiling a remainder-specialized runner.

        Returns ``(state, info)`` with info keys ``passes`` (cumulative),
        ``converged``, ``diverged``, ``max_violation``, ``duality_gap``,
        ``qp_objective``, ``lp_objective``, ``stop_rule`` and
        ``residuals`` — the chunk-boundary ``||Δx||_inf`` trajectory (the
        most recent ``residual_history`` chunks, oldest first), carried
        through the while_loop as a ring buffer and mirrored to
        ``self.last_residuals``. The stopping pair comes from the loop's
        own final probe and the objectives from one extra O(n^2) program,
        so callers never need a second full metrics pass.

        A non-finite residual probe (NaN poison, numerical blow-up) trips
        the on-device divergence guard: the loop exits at the first bad
        chunk with ``info["diverged"] = True`` and the state restored to
        the last finite chunk boundary, instead of scanning NaNs until
        ``max_passes``. ``faults`` (optional, duck-typed
        ``serve.faults.FaultInjector``) is polled once at entry — the
        ``chunk`` injection site (DESIGN.md §11).
        """
        st = state if state is not None else self.init_state()
        if faults is not None:
            st = self._apply_entry_faults(faults, st)
        check_every = max(1, int(check_every))
        residual_history = max(1, int(residual_history))
        if stop_rule not in STOP_RULES:
            raise ValueError(
                f"unknown stop_rule {stop_rule!r}; expected one of {STOP_RULES}"
            )
        max_passes = int(max_passes)
        tol = float(tol)

        syncs = 0

        def get(tree):
            # one host round trip: a read of device values
            nonlocal syncs
            syncs += 1
            return jax.device_get(tree)

        def launch(program, *args):
            # one host round trip: a program dispatched from the host (the
            # stop decision's few one-op programs and their read count as
            # one)
            nonlocal syncs
            syncs += 1
            return program(*args)

        def host(pair):
            v, g = get(pair)
            return float(v), float(g)

        with obs.span("repro.run_until"):
            with obs.span("repro.run_until.launch"):
                fn = self._until_fn(check_every, stop_rule, residual_history)
                st, viol, gap, obj, prev_obj, resbuf, k, div = launch(
                    fn, st, tol, max_passes
                )
            with obs.span("repro.run_until.wait"):
                viol, gap = host((viol, gap))
            with obs.span("repro.run_until.harvest"):
                obj, prev_obj = host((obj, prev_obj))
                k = int(get(k))
                diverged = bool(get(div))
                resbuf = np.asarray(get(resbuf), np.float64)
                residuals = (
                    resbuf[:k] if k <= residual_history
                    else np.roll(resbuf, -(k % residual_history))
                )
                self.last_residuals = residuals
                qp, lp = (float(v) for v in
                          get(launch(self._objectives_fn(), st)))
                if not np.isfinite(viol):
                    # no chunk ran (state already at/over max_passes), or
                    # the guard tripped on the very first chunk: probe the
                    # returned state once so the caller still gets a real
                    # stopping pair.
                    viol, gap = host(launch(self._probe_fn(), st))
                    obj = qp
                converged = not diverged and bool(launch(
                    stop_converged, stop_rule, tol, viol, gap, obj, prev_obj
                ))
                passes = int(get(st.passes))
        obs.count("repro.run_until.calls")
        obs.count("repro.run_until.syncs", syncs)
        info = {
            "passes": passes,
            "converged": converged,
            "diverged": diverged,
            "max_violation": viol,
            "duality_gap": gap,
            "qp_objective": qp,
            "lp_objective": lp,
            "stop_rule": stop_rule,
            "residuals": residuals,
        }
        return st, info
