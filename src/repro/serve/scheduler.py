"""Async micro-batching / continuous-batching request scheduler for the
batched solve service (DESIGN.md §8/§9/§12).

``submit`` never blocks on a solve: it validates, routes, and returns a
``ServeFuture`` immediately (the future compares and hashes as its tag,
so tag-keyed code keeps working). Background worker threads — one per
(bucket_n, family) slot, plus one for the above-ladder sharded route —
own every dispatch. ``results()`` / ``drain()`` are the sync points: they
wait for all in-flight work to land, then return the result map (queued
partial batches in drain mode are NOT in flight — only ``drain()`` or a
``poll()`` past the deadline flushes them, the pre-async contract).

Two dispatch modes for the bucketed route:

  * ``mode="drain"`` — classic micro-batching: requests queue per
    (bucket_n, family); a full batch (or an aged partial) is handed to
    that slot's worker as one ``run_until`` job; the whole batch lands
    when its slowest instance stops.
  * ``mode="continuous"`` — slot-level continuous batching
    (DESIGN.md §12): the worker owns a long-lived
    ``batching.ContinuousBatcher`` and loops chunk by chunk; at every
    chunk boundary it retires converged/diverged slots and refills the
    freed slots from its queue (weights are runtime operands — a refill
    never recompiles), so a mixed-age batch keeps every slot busy
    instead of waiting for the batch's slowest instance. Per-slot
    freeze semantics make each instance's result bitwise identical to
    its drain-mode solve.

**Above-ladder instances** (n larger than the top rung) do not batch:
they are handed to the dedicated sharded worker, which solves each at
its NATIVE n with ``ShardedSolver.run_until`` on the solver mesh
(DESIGN.md §9) — same stop rule, same result/certificate plumbing,
results flagged ``route="sharded"``, delivered through the same future.

The scheduler owns a ``SolverCache``: the first batch of a
(bucket_n, batch, family) slot compiles the batched runner, every later
batch reuses it. ``warmup(family)`` pre-compiles the runner for every
configured ladder rung up front (in continuous mode, the chunk stepper
and the refill merge too), so the first real batch of a prewarmed slot
dispatches warm. ``stats()`` reports the cache hit rate, warm/cold
dispatch counts, summed solve time, slot occupancy, per-bucket
queue-depth high-water marks, and refill/chunk counters — the numbers
the serve benchmark and CI smoke legs grep for.

**Fault tolerance** (DESIGN.md §11). ``submit`` never raises for a
solvable request and every accepted request reaches exactly one terminal
result; every fault site now fires under the worker that owns the
dispatch:

  * intake validation (`buckets.validate_problem`) rejects poison
    (non-finite data, non-positive weights, bad shapes) into an
    immediate dead-letter result instead of a queue slot;
  * duplicate tags — user-supplied, or an auto tag colliding with a
    still-pending one — raise ``ValueError`` at submit, the one case
    that IS a caller bug: silently overwriting ``_results`` loses a
    previous request's answer;
  * each dispatch attempt runs under retry with capped exponential
    backoff (transient failures heal); in drain mode a group that keeps
    failing is bisected to isolate the poison instance; in continuous
    mode the admission of each request is its own retry unit, so a
    poison admission dead-letters alone without any bisection;
  * a slot the batched engine flags ``diverged`` (NaN probe — the
    on-device guard froze it at its last finite iterate) dead-letters
    with ``error="diverged"`` rather than masquerading as a solve — in
    continuous mode it retires at the next chunk boundary while its
    co-resident slots keep sweeping unperturbed;
  * an optional ``faults`` injector (`serve.faults.FaultInjector`) is
    polled once per dispatch *attempt* — under the worker thread — the
    deterministic chaos source the end-to-end tests replay from a seed.
"""

from __future__ import annotations

import dataclasses
import queue as queue_mod
import threading
import time
from typing import Any, Callable

import numpy as np

from repro.core.problems import MetricQP
from repro.serve import buckets as bk

__all__ = ["BatchScheduler", "ServeFuture", "SolveRequest"]

#: Sentinel handed to a worker queue to stop the thread (close()).
_SHUTDOWN = object()


class ServeFuture:
    """Handle for one submitted request, resolved with the request's
    terminal result dict (solved OR dead-letter — exactly one of the
    two, the §11 invariant).

    Compares and hashes as its ``tag``, so code written against the old
    tag-returning ``submit`` — ``results()[submit(p)]``, set/dict
    membership — keeps working unchanged.
    """

    __slots__ = ("tag", "_event", "_result")

    def __init__(self, tag):
        self.tag = tag
        self._event = threading.Event()
        self._result = None

    def _resolve(self, result: dict) -> None:
        self._result = result
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> dict:
        """Block until the terminal result lands (or ``timeout``)."""
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.tag!r} not finished within {timeout}s"
            )
        return self._result

    def __eq__(self, other):
        if isinstance(other, ServeFuture):
            return other.tag == self.tag
        return other == self.tag

    def __hash__(self):
        return hash(self.tag)

    def __repr__(self):
        return f"ServeFuture({self.tag!r}, done={self.done()})"


@dataclasses.dataclass
class SolveRequest:
    """One queued instance. ``tag`` is the caller's correlation key;
    ``in_flight`` flips when the request is handed to a worker (the
    ``results()`` barrier counts exactly these)."""

    problem: MetricQP
    tag: Any = None
    t_submit: float = 0.0
    bucket_n: int = 0
    in_flight: bool = False


class BatchScheduler:
    """Async collect-up-to-B-or-deadline micro-batcher with an optional
    continuous-batching dispatch loop (see module docstring).

    Args:
      ladder: bucket sizes (sorted ascending is not required).
      batch: instance slots per batched solve.
      mode: ``"drain"`` (whole-batch dispatch) or ``"continuous"``
        (slot-level refill at chunk boundaries, DESIGN.md §12).
      deadline_s: drain mode — max age of the oldest queued request
        before a partial batch is dispatched anyway (0 = only ``drain``
        flushes partials). Continuous mode admits requests to free slots
        immediately, so the deadline never applies.
      cache: shared ``SolverCache`` (one per process is the right scope;
        pass your own to share compiled runners across schedulers).
      dtype: compute dtype of the batched solvers.
      sharded_mesh: mesh for the above-ladder sharded route (default: a
        1-D 'solver' mesh over every visible device, built lazily on the
        first big instance).
      sharded_num_buckets: diagonal buckets of the sharded solvers.
      prewarm: optionally a ``Family`` — ``warmup(prewarm)`` runs at
        construction, compiling the configured ladder before traffic.
      use_kernel: route BOTH dispatch paths through the gen-3 Pallas
        megakernel (DESIGN.md §10) — the batched route via
        ``BatchedSolver(use_kernel=True)``, the above-ladder route via
        ``ShardedSolver(use_kernel=True)``. Ignored when ``cache`` is
        passed explicitly (the cache's own solver kwargs win on the
        batched route).
      max_retries: dispatch attempts beyond the first before a group is
        bisected / an admission dead-letters (transient-failure budget).
      backoff_s / backoff_cap_s: initial / maximum retry backoff; the
        delay doubles per retry and is served by ``sleep`` (injectable —
        tests pass a recording stub, so retry tests take zero wall
        time).
      faults: optional ``serve.faults.FaultInjector`` polled once per
        dispatch attempt (the ``dispatch`` injection site), under the
        worker thread that owns the dispatch.
      solve_kwargs: forwarded to ``run_until`` on both routes (tol,
        max_passes, check_every, stop_rule, residual_history).
    """

    def __init__(
        self,
        ladder=bk.DEFAULT_LADDER,
        batch: int = 8,
        deadline_s: float = 0.0,
        cache: bk.SolverCache | None = None,
        dtype=np.float32,
        clock: Callable[[], float] = time.monotonic,
        sharded_mesh=None,
        sharded_num_buckets: int = 6,
        prewarm: bk.Family | None = None,
        use_kernel: bool = False,
        max_retries: int = 2,
        backoff_s: float = 0.05,
        backoff_cap_s: float = 1.0,
        sleep: Callable[[float], None] = time.sleep,
        faults=None,
        mode: str = "drain",
        **solve_kwargs,
    ):
        if mode not in ("drain", "continuous"):
            raise ValueError(
                f"unknown mode {mode!r}; expected 'drain' or 'continuous'"
            )
        self.ladder = tuple(ladder)
        self.batch = int(batch)
        self.mode = mode
        self.deadline_s = float(deadline_s)
        self.use_kernel = bool(use_kernel)
        self.cache = (
            cache
            if cache is not None
            else bk.SolverCache(use_kernel=self.use_kernel)
        )
        self.dtype = dtype
        self.clock = clock
        self.solve_kwargs = solve_kwargs
        self.sharded_num_buckets = int(sharded_num_buckets)
        self._mesh = sharded_mesh
        self.max_retries = max(0, int(max_retries))
        self.backoff_s = float(backoff_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self._sleep = sleep
        self.faults = faults
        # All mutable scheduler state below is guarded by _lock; _flush
        # is notified whenever an in-flight request reaches its terminal
        # result (the results()/stats() barrier).
        self._lock = threading.RLock()
        self._flush = threading.Condition(self._lock)
        self._in_flight = 0
        self._queues: dict[tuple[int, bk.Family], list[SolveRequest]] = {}
        self._results: dict[Any, dict] = {}
        self._futures: dict[Any, ServeFuture] = {}
        self._pending_tags: set = set()
        self._seq = 0
        self._instances_done = 0
        self._batches_run = 0
        self._slots_run = 0
        self._solve_time = 0.0
        self._sharded_done = 0
        self._sharded_time = 0.0
        self._retries = 0
        self._dead_letters = 0
        self._validation_rejects = 0
        self._queue_hwm: dict = {}
        # per-bucket dual-sparsity accumulator: bucket_n -> [sum of
        # active-constraint fractions, slots sampled] (DESIGN.md §13 —
        # the signal the sparsifier acts on, surfaced per landed slot).
        self._dual_sparsity: dict = {}
        self._refills = 0
        self._chunks_run = 0
        self._occupied_chunks = 0
        # worker threads: one per (bucket_n, family) slot + one sharded,
        # created lazily on the first request that routes to them.
        self._workers: dict = {}
        self._sharded_q = None
        self._closed = False
        # compile-warmth bookkeeping: a dispatch is "warm" when its
        # (bucket_n, batch, family) runner was compiled before it —
        # by warmup() or by an earlier batch of the same slot.
        self._compiled: set = set()
        self._prewarmed: set = set()
        self._warm_dispatches = 0
        self._cold_dispatches = 0
        if prewarm is not None:
            self.warmup(prewarm)

    # ------------------------------------------------------------- intake
    def submit(self, problem: MetricQP, tag: Any = None) -> ServeFuture:
        """Queue one instance; returns its ``ServeFuture`` immediately —
        never blocks on a solve, bucketed or sharded. The future (which
        compares as its tag) resolves with the terminal result.

        A duplicate tag (still pending, or already holding a result)
        raises ``ValueError`` — accepting it would silently overwrite
        the earlier request's result. Everything else terminates in a
        result: invalid problem data dead-letter at intake
        (``route="failed"``, ``error="validation"``), solver failures
        dead-letter after retry/bisection under the worker — ``submit``
        itself never raises past intake."""
        with self._lock:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            if tag is None:
                tag, self._seq = f"req-{self._seq}", self._seq + 1
            if tag in self._pending_tags or tag in self._results:
                raise ValueError(
                    f"duplicate tag {tag!r}: a request with this tag is "
                    "already pending or has an unclaimed result"
                )
            fut = self._futures[tag] = ServeFuture(tag)
            req = SolveRequest(
                problem=problem,
                tag=tag,
                t_submit=self.clock(),
                bucket_n=problem.n,
            )
            try:
                bk.validate_problem(problem)
            except bk.ValidationError as e:
                self._validation_rejects += 1
                self._dead_letter(req, "validation", e)
                return fut
            bucket_n = bk.route_for(problem.n, self.ladder)
            self._pending_tags.add(tag)
            if bucket_n is None:
                self._hand_to_sharded(req)
                return fut
            req.bucket_n = bucket_n
            key = (req.bucket_n, bk.family_of(problem, self.dtype))
            if self.mode == "continuous":
                self._hand_to_continuous(key, req)
                return fut
            self._queues.setdefault(key, []).append(req)
            self._note_depth(req.bucket_n, len(self._queues[key]))
            if len(self._queues[key]) >= self.batch:
                self._dispatch(key)
        return fut

    def future(self, tag) -> ServeFuture:
        """The future of a submitted request (KeyError if unknown)."""
        with self._lock:
            return self._futures[tag]

    def _note_depth(self, bucket, depth: int) -> None:
        if depth > self._queue_hwm.get(bucket, 0):
            self._queue_hwm[bucket] = depth

    # ------------------------------------------------------------- warmup
    def warmup(self, family: bk.Family, buckets=None) -> dict:
        """Pre-compile the batched runner for every ladder rung of one
        problem family (DESIGN.md §8): an all-empty batch is pushed
        through the REAL ``run_until`` with ``max_passes=0`` — the jitted
        while_loop compiles fully and exits at pass 0 — under exactly the
        solve kwargs real dispatches use, so the compile-cache key
        matches by construction. In continuous mode the chunk stepper
        and the refill merge compile too (one empty chunk + one
        empty-mask refill through the real jitted programs). Later real
        batches of these slots dispatch warm. Returns
        ``{bucket_n: seconds}``.
        """
        from repro.serve.batching import ContinuousBatcher

        timings = {}
        for bucket_n in sorted(set(int(b) for b in (buckets or self.ladder))):
            t0 = self.clock()
            with self._lock:
                solver = self.cache.get(bucket_n, self.batch, family)
            solver.run_until(
                solver.stack([]), **{**self.solve_kwargs, "max_passes": 0}
            )
            if self.mode == "continuous":
                import jax.numpy as jnp

                cb = ContinuousBatcher(solver, **self.solve_kwargs)
                cb.step()  # compiles the chunk stepper (both cond arms)
                solver._refill_fn()(
                    cb.carry, cb.inst, solver.stack([]),
                    jnp.asarray(np.zeros((self.batch,), bool)),
                )
            key = (bucket_n, self.batch, family)
            with self._lock:
                self._compiled.add(key)
                self._prewarmed.add(key)
            timings[bucket_n] = self.clock() - t0
        return timings

    @property
    def pending(self) -> int:
        """Requests accepted but not yet handed to a solve — drain-mode
        queue depth plus continuous-mode not-yet-admitted depth. In-
        flight work is NOT pending (it no longer needs poll/drain)."""
        with self._lock:
            n = sum(len(q) for q in self._queues.values())
            for w in self._workers.values():
                if w.get("kind") == "continuous":
                    n += w["queue"].qsize()
            return n

    def poll(self) -> None:
        """Drain mode: dispatch every bucket whose oldest request is past
        deadline. With ``deadline_s == 0`` partial batches wait for
        ``drain()`` (the documented contract); only full buckets dispatch
        eagerly. Continuous mode admits eagerly — poll is a no-op."""
        if self.deadline_s <= 0 or self.mode == "continuous":
            return
        with self._lock:
            now = self.clock()
            for key, q in list(self._queues.items()):
                if q and now - q[0].t_submit >= self.deadline_s:
                    self._dispatch(key)

    def drain(self) -> dict[Any, dict]:
        """Flush all partial batches, wait for every in-flight request to
        land, and return every finished result."""
        with self._lock:
            for key in list(self._queues):
                while self._queues.get(key):
                    self._dispatch(key)
        return self.results()

    def results(self) -> dict[Any, dict]:
        """Terminal results so far, as ``{tag: result}`` — a sync point:
        waits for all in-flight work to land first (queued partials in
        drain mode are not in flight; ``drain()`` flushes those)."""
        with self._flush:
            self._flush.wait_for(lambda: self._in_flight == 0)
            return dict(self._results)

    def close(self) -> None:
        """Stop every worker thread (idempotent; in-flight work finishes
        first — workers see the sentinel after their current item)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            workers = list(self._workers.values())
        for w in workers:
            w["queue"].put(_SHUTDOWN)
        for w in workers:
            w["thread"].join(timeout=60.0)

    # ------------------------------------------------------- fault handling
    def _dead_letter(self, req: SolveRequest, error: str, exc: Exception):
        """Terminal failure result: the request's tag still resolves, with
        a typed error instead of an iterate (DESIGN.md §11)."""
        now = self.clock()
        result = {
            "x": None,
            "x_pad": None,
            "f": None,
            "n": req.problem.n,
            "bucket_n": req.bucket_n,
            "route": "failed",
            "error": error,
            "error_type": type(exc).__name__,
            "error_detail": str(exc),
            "passes": 0,
            "converged": False,
            "wait_s": max(0.0, now - req.t_submit),
            "solve_s": 0.0,
            "latency_s": max(0.0, now - req.t_submit),
        }
        with self._flush:
            self._dead_letters += 1
            self._finish(req, result)

    def _finish(self, req: SolveRequest, result: dict) -> None:
        """Land one terminal result (caller holds the lock): resolve the
        future, retire the tag, release the results() barrier."""
        self._results[req.tag] = result
        self._pending_tags.discard(req.tag)
        fut = self._futures.get(req.tag)
        if fut is not None:
            fut._resolve(result)
        if req.in_flight:
            req.in_flight = False
            self._in_flight -= 1
            self._flush.notify_all()

    def _poll_faults(self, reqs: list[SolveRequest]) -> dict:
        """Poll the ``dispatch`` injection site once per solve attempt —
        from the worker thread that owns the dispatch. Raises
        ``InjectedFault`` for a due dispatch_error (the retry loop then
        eats it like any real dispatch exception); returns a
        {tag: poisoned_problem} override map for due nan_poison specs —
        corruption past the intake gate, which must surface as a
        per-slot divergence, never as a batch loss."""
        if self.faults is None:
            return {}
        from repro.serve import faults as flt

        overrides: dict = {}
        tags = [r.tag for r in reqs]
        for spec in self.faults.poll("dispatch", tags=tags):
            if spec.kind == "dispatch_error":
                raise flt.InjectedFault(f"injected dispatch error ({spec.spec_str()})")
            if spec.kind == "nan_poison":
                tag = spec.payload.get("tag", tags[0])
                for r in reqs:
                    if r.tag == tag:
                        overrides[tag] = flt.poison_problem(r.problem)
            elif spec.kind == "straggler":
                self._sleep(float(spec.payload.get("seconds", 0.001)))
        return overrides

    def _with_retries(self, attempt: Callable[[], Any]) -> Any:
        """Run one dispatch attempt under retry with capped exponential
        backoff. Each retry re-polls the fault site (transient injected
        faults heal exactly like transient real ones)."""
        delay = self.backoff_s
        failures = 0
        while True:
            try:
                return attempt()
            except Exception:
                failures += 1
                if failures > self.max_retries:
                    raise
                with self._lock:
                    self._retries += 1
                self._sleep(min(delay, self.backoff_cap_s))
                delay *= 2.0

    # ------------------------------------------------------ worker plumbing
    def _spawn_worker(self, name: str, kind: str, target, key=None) -> dict:
        q: queue_mod.Queue = queue_mod.Queue()
        worker = {"queue": q, "kind": kind, "key": key}
        t = threading.Thread(
            target=target, args=(q,), name=name, daemon=True
        )
        worker["thread"] = t
        t.start()
        return worker

    def _hand_to_sharded(self, req: SolveRequest) -> None:
        """Hand an above-ladder request to the background sharded worker
        (caller holds the lock) — the caller never blocks on the solve."""
        if "sharded" not in self._workers:
            self._workers["sharded"] = self._spawn_worker(
                "serve-sharded", "sharded", self._sharded_worker
            )
        req.in_flight = True
        self._in_flight += 1
        w = self._workers["sharded"]
        w["queue"].put(req)
        self._note_depth("sharded", w["queue"].qsize())

    def _hand_to_continuous(self, key, req: SolveRequest) -> None:
        """Hand a bucket request to its slot's continuous worker (caller
        holds the lock)."""
        if key not in self._workers:
            self._workers[key] = self._spawn_worker(
                f"serve-cont-{key[0]}", "continuous",
                lambda q, k=key: self._continuous_worker(k, q), key=key,
            )
        req.in_flight = True
        self._in_flight += 1
        w = self._workers[key]
        w["queue"].put(req)
        self._note_depth(key[0], w["queue"].qsize())

    # ------------------------------------------------- drain-mode dispatch
    def _dispatch(self, key) -> None:
        """Pop a batch off one bucket queue and hand it to the slot's
        worker (caller holds the lock)."""
        bucket_n, family = key
        q = self._queues.get(key, [])
        reqs, self._queues[key] = q[: self.batch], q[self.batch:]
        if not reqs:
            return
        ckey = (bucket_n, self.batch, family)
        if ckey in self._compiled:
            self._warm_dispatches += 1
        else:
            self._cold_dispatches += 1
            self._compiled.add(ckey)
        if key not in self._workers:
            self._workers[key] = self._spawn_worker(
                f"serve-batch-{bucket_n}", "drain",
                lambda jq, k=key: self._batch_worker(k, jq), key=key,
            )
        for r in reqs:
            r.in_flight = True
        self._in_flight += len(reqs)
        self._workers[key]["queue"].put(reqs)

    def _batch_worker(self, key, jobs: queue_mod.Queue) -> None:
        """Drain-mode worker loop for one (bucket_n, family) slot: each
        job is one request group, solved with the retry/bisect/dead-
        letter ladder. A worker crash never strands a request — the
        catch-all dead-letters the whole group (terminal-result
        invariant)."""
        bucket_n, family = key
        while True:
            item = jobs.get()
            if item is _SHUTDOWN:
                return
            reqs = item
            try:
                with self._lock:
                    solver = self.cache.get(bucket_n, self.batch, family)
                self._solve_group(solver, bucket_n, reqs)
            except BaseException as e:  # defensive: never strand a tag
                for r in reqs:
                    if r.tag not in self._results:
                        self._dead_letter(r, "dispatch", e)

    def _solve_group(self, solver, bucket_n: int, reqs: list[SolveRequest]):
        """Solve a request group with retry; on persistent failure bisect
        to isolate the poison instance — healthy halves land normally,
        the failing singleton dead-letters. Worst case (one bad instance
        in a batch of B) costs O(log B) extra sub-batch solves, each a
        warm dispatch of the already-compiled runner."""
        try:
            out = self._with_retries(
                lambda: self._attempt_batch(solver, reqs)
            )
        except Exception as e:
            if len(reqs) == 1:
                self._dead_letter(
                    reqs[0],
                    "injected" if type(e).__name__ == "InjectedFault"
                    else "dispatch",
                    e,
                )
                return
            mid = len(reqs) // 2
            self._solve_group(solver, bucket_n, reqs[:mid])
            self._solve_group(solver, bucket_n, reqs[mid:])
            return
        self._land_batch(bucket_n, reqs, *out)

    def _attempt_batch(self, solver, reqs: list[SolveRequest]):
        """One solve attempt of a request group (the retry unit)."""
        overrides = self._poll_faults(reqs)
        inst = solver.stack(
            [overrides.get(r.tag, r.problem) for r in reqs]
        )
        t0 = self.clock()
        state, info = solver.run_until(inst, **self.solve_kwargs)
        x = np.asarray(state.x)  # one host copy; also blocks for the timing
        dt = self.clock() - t0
        dstats = solver.dual_stats(state, inst)
        return state, info, x, t0, dt, dstats

    @staticmethod
    def _dual_fraction(active_count: float, n: int) -> float:
        """Active-constraint fraction of one slot: nonzero triangle duals
        over the instance's 3·C(n, 3) real constraints (n < 3 has none)."""
        total = n * (n - 1) * (n - 2) // 2  # 3 * C(n, 3)
        return float(active_count) / total if total else 0.0

    def _record_dual_sparsity(self, bucket_n: int, fracs) -> None:
        acc = self._dual_sparsity.setdefault(bucket_n, [0.0, 0])
        for f in fracs:
            acc[0] += f
            acc[1] += 1

    def _land_batch(self, bucket_n, reqs, state, info, x, t0, dt,
                    dstats) -> None:
        f = None if state.f is None else np.asarray(state.f)
        diverged = info.get("diverged")
        with self._flush:
            self._solve_time += dt
            self._batches_run += 1
            self._slots_run += self.batch
            self._record_dual_sparsity(bucket_n, [
                self._dual_fraction(
                    dstats["active_constraints"][i], r.problem.n
                )
                for i, r in enumerate(reqs)
            ])
            for i, r in enumerate(reqs):
                if diverged is not None and bool(diverged[i]):
                    # the on-device guard froze this slot at its last
                    # finite iterate; its result is a typed failure, not
                    # a solve.
                    self._dead_letters += 1
                    self._finish(r, self._diverged_result(r, bucket_n))
                    continue
                n = r.problem.n
                self._instances_done += 1
                now = self.clock()
                self._finish(r, {
                    "x": x[i, :n, :n],
                    "x_pad": x[i],  # padded iterate (ghost-aware rounding)
                    "f": None if f is None else f[i, :n, :n],
                    "n": n,
                    "bucket_n": bucket_n,
                    "route": "batch",
                    "passes": int(info["passes"][i]),
                    "converged": bool(info["converged"][i]),
                    "max_violation": float(info["max_violation"][i]),
                    "duality_gap": float(info["duality_gap"][i]),
                    "lp_objective": float(info["lp_objective"][i]),
                    "qp_objective": float(info["qp_objective"][i]),
                    "wait_s": max(0.0, t0 - r.t_submit),
                    "solve_s": dt,
                    "latency_s": max(0.0, now - r.t_submit),
                })

    def _diverged_result(self, req: SolveRequest, bucket_n: int) -> dict:
        now = self.clock()
        exc = ArithmeticError(
            "residual probe went non-finite; slot frozen at "
            "its last finite iterate by the divergence guard"
        )
        return {
            "x": None,
            "x_pad": None,
            "f": None,
            "n": req.problem.n,
            "bucket_n": bucket_n,
            "route": "failed",
            "error": "diverged",
            "error_type": type(exc).__name__,
            "error_detail": str(exc),
            "passes": 0,
            "converged": False,
            "wait_s": max(0.0, now - req.t_submit),
            "solve_s": 0.0,
            "latency_s": max(0.0, now - req.t_submit),
        }

    # ------------------------------------------- continuous-mode dispatch
    def _continuous_worker(self, key, q: queue_mod.Queue) -> None:
        """Continuous-batching worker loop for one (bucket_n, family)
        slot (DESIGN.md §12): a long-lived ``ContinuousBatcher`` sweeps a
        mixed-age batch chunk by chunk; freed slots refill from the queue
        at every chunk boundary. Admission is the per-request fault/retry
        unit; a chunk that keeps failing dead-letters the live slots and
        restarts the batcher with a fresh carry (terminal-result
        invariant)."""
        from repro.serve.batching import ContinuousBatcher

        bucket_n, family = key
        with self._lock:
            solver = self.cache.get(bucket_n, self.batch, family)
            ckey = (bucket_n, self.batch, family)
            if ckey in self._compiled:
                self._warm_dispatches += 1
            else:
                self._cold_dispatches += 1
                self._compiled.add(ckey)
        batcher = ContinuousBatcher(solver, **self.solve_kwargs)
        live_reqs: dict = {}  # tag -> (SolveRequest, t_admit)
        item = None
        while True:
            if item is None and not batcher.live:
                item = q.get()  # idle: block for traffic (or shutdown)
            if item is _SHUTDOWN:
                return
            # ---- refill every free slot from the queue
            assignments = []
            free = batcher.free_slots()
            while free:
                if item is None:
                    try:
                        item = q.get_nowait()
                    except queue_mod.Empty:
                        break
                    if item is _SHUTDOWN:
                        break
                req, item = item, None
                problem = self._admit_request(req)
                if problem is None:
                    continue  # dead-lettered at admission
                slot = free.pop(0)
                assignments.append((slot, problem, req.tag))
                live_reqs[req.tag] = (req, self.clock())
            try:
                batcher.admit(assignments)
                if not batcher.live:
                    continue
                # ---- one convergence chunk + harvest, under retry
                t0 = self.clock()
                self._with_retries(batcher.step)
                dt = self.clock() - t0
                with self._lock:
                    self._solve_time += dt
                    self._chunks_run += 1
                    self._occupied_chunks += batcher.occupied
                    self._refills = max(self._refills, 0)
                harvested = batcher.harvest()
            except BaseException as e:
                # the chunk itself keeps failing (device error, injected
                # chaos past the retry budget): every live slot dead-
                # letters and the batcher restarts with a fresh carry.
                for tag in list(batcher.tags):
                    if tag is None or tag not in live_reqs:
                        continue
                    req, _ = live_reqs.pop(tag)
                    self._dead_letter(
                        req,
                        "injected" if type(e).__name__ == "InjectedFault"
                        else "dispatch",
                        e,
                    )
                batcher = ContinuousBatcher(solver, **self.solve_kwargs)
                continue
            with self._lock:
                self._refills += len(assignments)
            if harvested:
                dstats = batcher.solver.dual_stats(
                    batcher.carry.state, batcher.inst
                )
                with self._flush:
                    self._record_dual_sparsity(bucket_n, [
                        self._dual_fraction(
                            dstats["active_constraints"][slot], info["n"]
                        )
                        for slot, _, _, _, info in harvested
                    ])
            for slot, tag, x_row, f_row, info in harvested:
                req, t_admit = live_reqs.pop(tag)
                self._land_slot(req, bucket_n, x_row, f_row, info, t_admit)

    def _admit_request(self, req: SolveRequest):
        """Admission gate of one request into a freed slot: the dispatch
        fault site + retry ladder, per request (continuous mode's retry
        unit — no bisection needed, a poison admission fails alone).
        Returns the (possibly nan_poison-overridden) problem, or None
        after dead-lettering."""

        def attempt():
            overrides = self._poll_faults([req])
            return overrides.get(req.tag, req.problem)

        try:
            return self._with_retries(attempt)
        except Exception as e:
            self._dead_letter(
                req,
                "injected" if type(e).__name__ == "InjectedFault"
                else "dispatch",
                e,
            )
            return None

    def _land_slot(self, req, bucket_n, x_row, f_row, info, t_admit):
        """Land one harvested slot (continuous mode): same result dict as
        a drain-mode batch slot, with per-request wait/solve split at its
        own admission time."""
        if info["diverged"]:
            with self._flush:
                self._dead_letters += 1
                self._finish(req, self._diverged_result(req, bucket_n))
            return
        n = req.problem.n
        now = self.clock()
        with self._flush:
            self._instances_done += 1
            self._slots_run += 1
            self._finish(req, {
                "x": x_row[:n, :n],
                "x_pad": x_row,
                "f": None if f_row is None else f_row[:n, :n],
                "n": n,
                "bucket_n": bucket_n,
                "route": "batch",
                "passes": info["passes"],
                "converged": info["converged"],
                "max_violation": info["max_violation"],
                "duality_gap": info["duality_gap"],
                "lp_objective": info["lp_objective"],
                "qp_objective": info["qp_objective"],
                "wait_s": max(0.0, t_admit - req.t_submit),
                "solve_s": max(0.0, now - t_admit),
                "latency_s": max(0.0, now - req.t_submit),
            })

    # -------------------------------------------------- sharded dispatch
    def _sharded_worker(self, jobs: queue_mod.Queue) -> None:
        """Above-ladder worker loop: one request per job, solved at its
        NATIVE n with ``ShardedSolver.run_until`` (DESIGN.md §9) — the
        route that used to block the caller inside ``submit`` and now
        runs behind the same future plumbing as every bucket slot."""
        while True:
            item = jobs.get()
            if item is _SHUTDOWN:
                return
            try:
                self._dispatch_sharded(item)
            except BaseException as e:  # defensive: never strand a tag
                if item.tag not in self._results:
                    self._dead_letter(item, "dispatch", e)

    def _dispatch_sharded(self, req: SolveRequest) -> None:
        """Solve one above-ladder instance (in the sharded worker): same
        stop rule and info/certificate plumbing as a batch slot, no ghost
        padding (``x_pad`` is the native iterate, ``bucket_n = n``, so
        the pipeline's ghost-aware device rounding degrades to plain
        device rounding). Same failure contract too: retry with backoff,
        then a dead-letter result; a diverged solve dead-letters."""
        from repro.core.sharded_dykstra import ShardedSolver

        def attempt():
            overrides = self._poll_faults([req])
            solver = ShardedSolver(
                overrides.get(req.tag, req.problem), self._solver_mesh(),
                dtype=self.dtype,
                num_buckets=self.sharded_num_buckets,
                use_kernel=self.use_kernel,
            )
            t0 = self.clock()
            state, info = solver.run_until(**self._sharded_kwargs())
            x = np.asarray(state.x)  # host copy; also blocks for the timing
            return state, info, x, t0

        try:
            state, info, x, t0 = self._with_retries(attempt)
        except Exception as e:
            self._dead_letter(
                req,
                "injected" if type(e).__name__ == "InjectedFault"
                else "dispatch",
                e,
            )
            return
        if info.get("diverged"):
            self._dead_letter(
                req, "diverged",
                ArithmeticError(
                    "residual probe went non-finite; sharded solve "
                    "stopped at its last finite chunk boundary"
                ),
            )
            return
        dt = self.clock() - t0
        n = req.problem.n
        now = self.clock()
        with self._flush:
            self._solve_time += dt
            self._sharded_time += dt
            self._sharded_done += 1
            self._instances_done += 1
            self._finish(req, {
                "x": x,
                "x_pad": x,
                "f": None if state.f is None else np.asarray(state.f),
                "n": n,
                "bucket_n": n,
                "route": "sharded",
                "passes": int(info["passes"]),
                "converged": bool(info["converged"]),
                "max_violation": float(info["max_violation"]),
                "duality_gap": float(info["duality_gap"]),
                "lp_objective": float(info["lp_objective"]),
                "qp_objective": float(info["qp_objective"]),
                "wait_s": max(0.0, t0 - req.t_submit),
                "solve_s": dt,
                "latency_s": max(0.0, now - req.t_submit),
            })

    def _sharded_kwargs(self) -> dict:
        """run_until kwargs for the sharded route — the batched solver's
        residual_history knob does not exist there."""
        kw = dict(self.solve_kwargs)
        kw.pop("residual_history", None)
        return kw

    def _solver_mesh(self):
        with self._lock:
            if self._mesh is None:
                from repro.launch import mesh as mesh_lib

                self._mesh = mesh_lib.make_solver_mesh()
            return self._mesh

    # -------------------------------------------------------------- stats
    def stats(self) -> dict:
        """Occupancy / queue / compile-cache / warmth counters. A sync
        point like ``results()``: waits for in-flight work, so the numbers
        describe completed dispatches.

        ``occupancy`` is mode-dependent: drain mode reports real
        instances per dispatched batch slot (how full the batches were);
        continuous mode reports occupied slots per swept chunk slot (how
        busy the long-lived batch stayed under refill — the sustained-
        load benchmark's headline). ``queue_depth_hwm`` is the per-bucket
        high-water mark of the waiting queue depth (key "sharded" for the
        above-ladder queue); ``refills`` counts slot admissions by the
        continuous loop, ``chunks_run`` its chunk steps.
        ``dual_sparsity`` maps bucket_n → mean active-constraint fraction
        (``BatchedSolver.dual_stats`` nonzero triangle duals over the
        instance's 3·C(n,3)) across landed slots — the signal the
        Project-and-Forget sparsifier acts on (DESIGN.md §13), and a
        capacity-planning proxy for how constrained a bucket's traffic
        runs."""
        with self._flush:
            self._flush.wait_for(lambda: self._in_flight == 0)
            if self.mode == "continuous":
                occupancy = (
                    self._occupied_chunks / (self._chunks_run * self.batch)
                    if self._chunks_run else 0.0
                )
            else:
                occupancy = (
                    (self._instances_done - self._sharded_done)
                    / self._slots_run
                    if self._slots_run else 0.0
                )
            return {
                "mode": self.mode,
                "instances_done": self._instances_done,
                "batches_run": self._batches_run,
                "pending": self.pending,
                "occupancy": occupancy,
                "solve_time_s": self._solve_time,
                "sharded_done": self._sharded_done,
                "sharded_time_s": self._sharded_time,
                "queue_depth_hwm": dict(self._queue_hwm),
                "dual_sparsity": {
                    b: acc[0] / acc[1]
                    for b, acc in sorted(self._dual_sparsity.items())
                    if acc[1]
                },
                "refills": self._refills,
                "chunks_run": self._chunks_run,
                "compile_cache": self.cache.stats(),
                "prewarm": {
                    "buckets": len(self._prewarmed),
                    "warm_dispatches": self._warm_dispatches,
                    "cold_dispatches": self._cold_dispatches,
                },
                "faults": {
                    "retries": self._retries,
                    "dead_letters": self._dead_letters,
                    "validation_rejects": self._validation_rejects,
                    "injected_fired": (
                        len(self.faults.fired)
                        if self.faults is not None else 0
                    ),
                },
            }
