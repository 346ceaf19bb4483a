"""One instance solved over the cell's chips: ``ShardedSolver.run_until`` in
back-to-back chunks of ``check_every`` passes, with a tolerance it cannot
reach, on a one-axis solver mesh (``launch.mesh.make_solver_mesh``): X
replicated on every chip, the dual slabs dealt over the chips, and one
all-reduce per diagonal merging the per-chip deltas.

Set-up, window and check follow ``bench/entries/solo.py``: the solver is
built once and driven through its first ``check_chunks`` chunks with the
window's own call, each resuming from the state the last returned; the
window continues that same solver; the check compares the state after the
set-up chunks (X, F, every pair, box and triangle dual, the stopping pair)
with ``bench/reference.py``. Three things differ:

  * the reference depends on the instance alone, so it runs on the host's
    CPU in a thread from the start, beside staging, loading and the
    window; the check waits for it after the window;
  * the triangle duals are read diagonal by diagonal from the gathered
    slabs through the layout's lane tables (DESIGN.md §3), with no dense
    n^3 array;
  * a traced run reduces its own trace for the merge: the device time of
    the all-reduces that carry (n, n) deltas, and the part of it during
    which no other op runs on that device, handed over as counters.
"""

from __future__ import annotations

import collections
import concurrent.futures
import gc
import re

import numpy as np

from bench import check, harness, trace_reduce
from bench.entries import solo

def is_merge(name: str) -> bool:
    """An all-reduce whose result is one array of rank 2 or more: a
    diagonal's delta matrix, not the probe's scalar maxima. A v5e runs
    the merge as one synchronous ``all-reduce`` op per diagonal."""
    if " = " not in name or trace_reduce.opcode(name) != "all-reduce":
        return False
    rhs = name.split(" = ", 1)[1]
    return re.match(r"[a-z]\w*\[\d+(,\d+)+\]", rhs) is not None


def merge_seconds(device_ops, host_spans):
    """(device seconds of the merge ops, seconds of them during which no
    other leaf op runs on that device), each averaged over the devices,
    inside the ``bench.window`` span; None without a window or a merge op.
    Ops are ``(name, start_ns, duration_ns, device)`` tuples, spans
    ``(name, start_ns, duration_ns)``, as ``trace_reduce.from_xplane``
    gives them."""
    windows = [(s, s + d) for n, s, d in host_spans
               if n == trace_reduce.WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = windows[0]
    merge = collections.defaultdict(list)
    other = collections.defaultdict(list)
    devices = set()
    for name, s, d, dev in device_ops:
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        devices.add(dev)
        if trace_reduce.opcode(name) in trace_reduce.CONTROL_FLOW:
            continue
        (merge if is_merge(name) else other)[dev].append((a, b))
    if not merge:
        return None
    covered = lambda iv: sum(e - s for s, e in trace_reduce.union(iv))
    total = sum(covered(merge[dev]) for dev in devices)
    # the merge's time outside every other op: |M u O| - |O|
    exposed = sum(covered(merge[dev] + other[dev]) - covered(other[dev])
                  for dev in devices)
    return total * 1e-9 / len(devices), exposed * 1e-9 / len(devices)


def slab_duals(layout, slabs):
    """``tri(d, t, c, i, j, k)`` over the program's slabs, one per bucket
    in the layout's ``(procs, D, 3, T, Cl)`` shape: the three duals of each
    of the reference's live cells of diagonal ``d`` (``t = j - i - 1``),
    as (3, cells). Segment A of lane (dev, r, slot) holds the set whose
    first index is ``i[dev, r, slot]`` at steps ``t < sizes``, segment B
    the set of ``i2`` from step ``sizes`` on (DESIGN.md §3); a diagonal's
    sets have distinct first indices."""
    where = {int(d): (b, r) for b, bl in enumerate(layout.buckets)
             for r, d in enumerate(bl.diag_ids)}

    def tri(d, t, c, i, j, k):
        b, r = where[d]
        bl, slab = layout.buckets[b], slabs[b]
        dev, slot = np.indices(bl.i[:, r].shape)
        lane_dev = np.zeros(layout.n, np.int64)
        lane_slot = np.zeros(layout.n, np.int64)
        start = np.zeros(layout.n, np.int64)
        for first, base in ((bl.i[:, r], 0), (bl.i2[:, r], bl.sizes[:, r])):
            live = first >= 0
            lane_dev[first[live]] = dev[live]
            lane_slot[first[live]] = slot[live]
            start[first[live]] = np.broadcast_to(base, first.shape)[live]
        ld, ls, tt = lane_dev[i], lane_slot[i], start[i] + t
        return np.stack([slab[ld, r, m, tt, ls] for m in range(3)])

    return tri


def gaps(cfg: dict, w, dissim, got: dict, ref: dict) -> dict:
    """``solo.compare``'s numbers, against a reference already computed
    (``solo.reference_chunk``)."""
    n = w.shape[0]
    floor = float(cfg["check"]["pair_floor"])
    live = np.triu(np.ones((n, n), bool), 1)
    duals = lambda s: np.concatenate([s["ypair"], s["ybox"]])
    return {
        "x_gap": check.rel_max_gap(got["x"], ref["x"], live, 1.0),
        "f_gap": check.rel_max_gap(got["f"], ref["f"], live, 1.0),
        "dual_gap": check.rel_max_gap(duals(got), duals(ref), live),
        "tri_dual_gap": solo.triangle_dual_gap(got["tri"], ref["tri"], n),
        "pair_gap": check.pair_gap(got["pair"], ref["pair"], floor),
    }


def run(cfg: dict, traffic: dict, seed: int, seconds: float, tools) -> dict:
    import jax.numpy as jnp

    dissim, w = solo.build_problem(cfg, seed)
    checked = int(traffic["check_every"]) * int(traffic["check_chunks"])
    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
        reference = pool.submit(solo.reference_chunk, w, dissim,
                                float(cfg["eps"]), checked, jnp.float32)
        out, first, layout = _solve(cfg, traffic, dissim, w, seconds, tools)
        # ---- the check, once the window has closed
        t = tools.clock()
        ref = reference.result()
    wait_s = tools.clock() - t
    first["tri"] = slab_duals(layout, first.pop("yd"))
    out["values"] = gaps(cfg, w, dissim, first, ref)
    out["notes"][-1] += (f"; waited {wait_s:.3f} s for the reference; "
                         f"check {tools.clock() - t:.3f} s")
    return out


def _solve(cfg, traffic, dissim, w, seconds, tools):
    """Set-up, the window and the traced chunk: the entry's result without
    its ``values``, the host state after the set-up chunks, the layout."""
    import jax.numpy as jnp

    from repro.core import problems
    from repro.core.sharded_dykstra import ShardedSolver
    from repro.launch.mesh import make_solver_mesh

    n = dissim.shape[0]
    prob = problems.correlation_clustering_lp(dissim, w, eps=float(cfg["eps"]))
    mesh = make_solver_mesh(tools.chips)
    t0 = tools.clock()
    solver = ShardedSolver(prob, mesh, dtype=jnp.float32, **cfg["solver"])
    staging_s = tools.clock() - t0
    ce, tol = int(traffic["check_every"]), float(traffic["tol"])

    def chunk(st):
        with tools.span("bench.chunk"):
            return solver.run_until(st, tol=tol, max_passes=int(st.passes) + ce,
                                    check_every=ce)

    st = solver.init_state()
    for _ in range(int(traffic["check_chunks"])):
        st, info = chunk(st)
    first = solo._host(st)
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
        path = trace_reduce.find_xplane(harness.TRACE_DIR)
        merge = None if path is None else merge_seconds(
            *trace_reduce.from_xplane(path))
        if merge is not None:
            counters["merge_ms"] = merge[0] / traced * 1e3
            counters["merge_exposed_ms"] = merge[1] / traced * 1e3
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
    out = {
        "end_to_end": {
            "pass_ms": tools.window_s * 1e3 / passes,
            "hbm_peak_gb": tools.memory_peak / 1e9,
        },
        "attempted": chunks,
        "failed": diverged,
        "counters": counters,
        "notes": [f"window: {chunks} chunks of {ce} passes in "
                  f"{tools.window_s:.3f} s; staging {staging_s:.3f} s; "
                  f"last chunk {last:.3f} s"],
    }
    return out, first, layout
