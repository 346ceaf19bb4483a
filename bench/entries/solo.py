"""One instance solved alone: ``ParallelSolver.run_until`` in back-to-back
chunks of ``check_every`` passes, with a tolerance it cannot reach.

Set-up builds the solver once and drives it through its first
``check_chunks`` chunks with the window's own call, each resuming from the
state the last returned; the window then continues that same solver. The
check compares the state after those chunks (X, F, every pair, box and
triangle dual, the stopping pair the last probe reported) with the
reference.
"""

from __future__ import annotations

import gc

import numpy as np

from bench import check, graphs, reference


def build_problem(cfg: dict, seed: int):
    """(dissim, weights) of the configuration's instance for ``seed``."""
    g = cfg["graph"]
    adj = graphs.make_graph(g["kind"], g["n"], seed, **g.get("params", {}))
    return graphs.signed_instance(adj, **cfg["signing"])


def _host(st) -> dict:
    out = {k: np.asarray(getattr(st, k)) for k in ("x", "f", "ypair", "ybox")}
    out["yd"] = [np.asarray(a) for a in st.yd]
    return out


def run(cfg: dict, traffic: dict, seed: int, seconds: float, tools) -> dict:
    import jax.numpy as jnp

    from repro.core import problems, schedule
    from repro.core.parallel_dykstra import ParallelSolver

    dissim, w = build_problem(cfg, seed)
    n, eps = dissim.shape[0], float(cfg["eps"])
    prob = problems.correlation_clustering_lp(dissim, w, eps=eps)
    t0 = tools.clock()
    solver = ParallelSolver(prob, dtype=jnp.float32, **cfg["solver"])
    staging_s = tools.clock() - t0
    ce, tol = int(traffic["check_every"]), float(traffic["tol"])

    def chunk(st):
        with tools.span("bench.chunk"):
            return solver.run_until(st, tol=tol, max_passes=int(st.passes) + ce,
                                    check_every=ce)

    st = solver.init_state()
    for _ in range(int(traffic["check_chunks"])):
        st, info = chunk(st)
    first = _host(st)
    first["pair"] = (info["max_violation"], info["duality_gap"])
    first["passes"] = info["passes"]
    layout = solver.layout

    deadline = tools.open_window() + seconds
    chunks = diverged = traced = 0
    last = 0.0

    def step():
        nonlocal st, info, chunks, diverged, last
        t = tools.clock()
        st, info = chunk(st)
        last = tools.clock() - t
        chunks += 1
        diverged += bool(info["diverged"])

    if tools.trace:
        with tools.profile():
            for _ in range(int(traffic["trace_chunks"])):
                step()
        traced = chunks * ce
    while chunks == 0 or tools.clock() + last <= deadline:
        step()
    tools.close_window()
    passes = chunks * ce

    counters = {"staging_s": staging_s, "passes_traced": traced, "n": n}
    if tools.trace:
        probe = solver._probe_fn()
        probe(st)[0].block_until_ready()
        calls = int(traffic["probe_calls"])
        t = tools.clock()
        for _ in range(calls):
            probe(st)[0].block_until_ready()
        counters["probe_ms"] = (tools.clock() - t) / calls * 1e3
    tools.read_memory()
    del solver, st, prob
    gc.collect()

    # ---- the check, once the window has closed and the program is freed
    t = tools.clock()
    dense = schedule.duals_to_dense(layout, first.pop("yd"))
    del layout
    first["tri"] = lambda d, t, c, i, j, k: np.stack(
        [dense[i, j, k], dense[i, k, j], dense[j, k, i]])
    values = compare(cfg, w, dissim, first, jnp.float32)
    return {
        "end_to_end": {
            "pass_ms": tools.window_s * 1e3 / passes,
            "hbm_peak_gb": tools.memory_peak / 1e9,
        },
        "attempted": chunks,
        "failed": diverged,
        "counters": counters,
        "values": values,
        "notes": [f"window: {chunks} chunks of {ce} passes in "
                  f"{tools.window_s:.3f} s; staging {staging_s:.3f} s; "
                  f"last chunk {last:.3f} s; check {tools.clock() - t:.3f} s"],
    }


def reference_chunk(w, dissim, eps: float, passes: int, dtype) -> dict:
    """What the timed path holds after ``passes`` passes, computed by the
    reference at ``dtype``: X, F, pair and box duals, the triangle duals
    ``tri(d, t, c, i, j, k)`` of each diagonal's live cells (see
    ``reference.triangle_cells``) and the stopping pair."""
    import jax.numpy as jnp

    ref = reference.solve(w, dissim, eps, passes, dtype)
    out = {k: np.asarray(ref[k].astype(jnp.float32))
           for k in ("x", "f", "ypair", "ybox")}
    y = np.asarray(ref.pop("y"))
    del ref
    out["tri"] = lambda d, t, c, i, j, k: y[d][:, t, c].astype(np.float32)
    out["pair"] = reference.stopping_pair(out, w, dissim, eps)
    out["passes"] = passes
    return out


def triangle_dual_gap(got, ref, n: int) -> float:
    """max |got - ref| over every triangle dual, in units of the
    reference's max |dual|; ``got`` and ``ref`` map a diagonal's live cells
    to their (3, cells) duals."""
    gap = scale = 0.0
    for d in range(len(reference.diagonals(n))):
        cells = reference.triangle_cells(n, d)
        if not cells[0].size:
            continue
        r = np.asarray(ref(d, *cells), np.float64)
        g = np.asarray(got(d, *cells), np.float64)
        gap = max(gap, float(np.max(np.abs(g - r))))
        scale = max(scale, float(np.max(np.abs(r))))
    if not np.isfinite(gap):
        return float("inf")
    return gap / scale if scale > 0 else gap


def compare(cfg: dict, w, dissim, got: dict, dtype) -> dict:
    """The check's numbers: ``got`` (the state after the set-up chunks)
    against the reference at ``dtype``."""
    n, eps = w.shape[0], float(cfg["eps"])
    floor = float(cfg["check"]["pair_floor"])
    ref = reference_chunk(w, dissim, eps, got["passes"], dtype)
    live = np.triu(np.ones((n, n), bool), 1)
    duals = lambda s: np.concatenate([s["ypair"], s["ybox"]])
    return {
        "x_gap": check.rel_max_gap(got["x"], ref["x"], live, 1.0),
        "f_gap": check.rel_max_gap(got["f"], ref["f"], live, 1.0),
        "dual_gap": check.rel_max_gap(duals(got), duals(ref), live),
        "tri_dual_gap": triangle_dual_gap(got["tri"], ref["tri"], n),
        "pair_gap": check.pair_gap(got["pair"], ref["pair"], floor),
    }
