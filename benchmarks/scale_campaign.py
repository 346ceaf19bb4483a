"""Scale campaign: largest-n solved per device count → BENCH_scale.json.

The ROADMAP's named success artifact for the paper's headline-scale item
(2.9e12 triangle constraints at n ≈ 2.6e4, arXiv 1901.10084). Per device
count p the campaign walks the n ladder upward until the **modeled
per-device dual-slab footprint** crosses the budget — 3·C(n,3) f32 duals
sharded p ways is the state that actually scales with the mesh
(DESIGN.md §14); the replicated (n, n) planes are identical at every p —
and for each feasible n records:

  * amortized per-pass time of the fused sharded runner (warm),
  * one warm kernel-backed stopping-probe evaluation (the lane-blocked
    Pallas slab kernel + pmax routed by ``use_kernel``),
  * peak live device bytes (``launch.mesh.device_memory_bytes``),
  * the (viol, gap) certificate of a ``run_until`` solve,
  * the donated-snapshot overlap: wall time of a blocking host-transfer
    ``save`` vs the caller-visible dispatch of ``save_async(donate=True)``
    (the difference is solve time reclaimed per checkpoint).

Cube-root law: the budget binds at 3·C(n,3)·4/p ≈ n³·2/p bytes, so
largest-n grows like (p·B)^(1/3) — doubling largest-n needs 8× the
devices, which is exactly the 1 → 8 device leg asserted in CI and the
acceptance bar (largest-n at p=8 ≥ 2× p=1).

One subprocess per device count (jax locks the device count at backend
init; same pattern as fig6_cores). Modes:

  * ``run()`` / ``--smoke``: KB-scale budget, ladder capped at 256 —
    seconds per count, safe for the CI benchmark-smoke leg.
  * ``--full`` (or env REPRO_SCALE_FULL=1): the checked-in artifact's
    budget (2 MB/device → largest-n 96/128/192 at p=1/4/8).

Writes BENCH_scale.json (repo root) and prints one ``BENCH_scale`` row
per (p, n) plus a ``certificate`` line per device count — the CI scale
leg greps both.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LADDER = (16, 24, 32, 48, 64, 96, 128, 160, 192, 256, 320, 384, 512)
DEFAULT_COUNTS = (1, 4, 8)
SMOKE_BUDGET_MB = 0.032  # → largest-n 24/32/48 at p=1/4/8
FULL_BUDGET_MB = 2.0  # → largest-n 96/128/192 at p=1/4/8
SMOKE_CAP = 256  # ladder cap of the CI smoke leg


def dual_slab_bytes(n: int, itemsize: int = 4) -> int:
    """Sharded solver state that scales with n: 3·C(n,3) schedule-native
    triangle duals (DESIGN.md §3). Slab padding and the replicated (n,n)
    planes are excluded — the model ranks n per device count, it does not
    predict the allocator's peak."""
    return 3 * (n * (n - 1) * (n - 2) // 6) * itemsize


def feasible_ladder(p: int, budget_mb: float, ladder=LADDER,
                    cap: int | None = None) -> list[int]:
    """The ladder prefix whose per-device dual-slab bytes fit the budget."""
    out = []
    for n in ladder:
        if cap is not None and n > cap:
            break
        if dual_slab_bytes(n) / p > budget_mb * 1e6:
            break
        out.append(n)
    return out


_WORKER = textwrap.dedent("""
    import json, os, sys, tempfile, time
    cfg = json.loads(sys.argv[1])
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=%d" % cfg["devices"]
    )
    import numpy as np
    import jax
    from repro.core import problems
    from repro.core.sharded_dykstra import ShardedSolver
    from repro.launch import mesh as mesh_lib
    from repro.train import checkpoint as ckpt_lib

    mesh = mesh_lib.make_global_solver_mesh()
    p = mesh.devices.size
    assert p == cfg["devices"], (p, cfg["devices"])

    for n in cfg["ladder"]:
        rng = np.random.default_rng(7)
        d = rng.random((n, n))
        d = (d + d.T) / 2
        np.fill_diagonal(d, 0)
        prob = problems.metric_nearness_l2(d)
        solver = ShardedSolver(
            prob, mesh, num_buckets=cfg["buckets"], use_kernel=True,
            probe_block_c=cfg["block_c"],
        )
        # warm the SAME multi-pass program the timing runs (the fused
        # runner compiles one scan per pass count)
        st = solver.run(passes=cfg["timed_passes"])
        jax.block_until_ready(st.x)
        t0 = time.perf_counter()
        st = solver.run(st, passes=cfg["timed_passes"])
        jax.block_until_ready(st.x)
        pass_ms = (time.perf_counter() - t0) * 1e3 / cfg["timed_passes"]
        probe = solver._probe_fn()
        jax.block_until_ready(probe(st))
        t0 = time.perf_counter()
        jax.block_until_ready(probe(st))
        probe_ms = (time.perf_counter() - t0) * 1e3
        st, info = solver.run_until(
            st, tol=cfg["tol"], max_passes=cfg["max_passes"],
            check_every=cfg["check_every"], stop_rule=cfg["stop_rule"],
        )
        mem_b, mem_src = mesh_lib.device_memory_bytes()
        tmp = tempfile.mkdtemp()
        # warm the snapshot program (jit traces once per state shape) so
        # the timed dispatch measures the steady-state caller cost
        th, st = ckpt_lib.save_async(tmp, 0, st, donate=True)
        th.join()
        ckpt_lib.wait_pending()
        t0 = time.perf_counter()
        ckpt_lib.save(tmp, 1, st)
        block_ms = (time.perf_counter() - t0) * 1e3
        t0 = time.perf_counter()
        th, st = ckpt_lib.save_async(tmp, 2, st, donate=True)
        dispatch_ms = (time.perf_counter() - t0) * 1e3
        th.join()
        ckpt_lib.wait_pending()
        print("ROW " + json.dumps(dict(
            devices=p, n=n, platform=jax.devices()[0].platform,
            pass_ms=round(pass_ms, 3), probe_ms=round(probe_ms, 3),
            peak_live_bytes=int(mem_b), mem_source=mem_src,
            dual_slab_bytes_per_device=cfg["model_bytes"][str(n)],
            viol=float(info["max_violation"]),
            gap=float(info["duality_gap"]),
            converged=bool(info["converged"]), passes=int(info["passes"]),
            snapshot_block_ms=round(block_ms, 3),
            snapshot_dispatch_ms=round(dispatch_ms, 3),
        )), flush=True)
    print("WORKER_DONE", flush=True)
""")


def _campaign(counts, budget_mb, cap, *, buckets=3, block_c=None,
              tol=2e-3, max_passes=200, check_every=10,
              stop_rule="rel_gap", timed_passes=3, timeout=2400):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)  # each worker pins its own device count
    # Forced host devices, never the accelerator: a chip belongs to one
    # process, and the parent may hold it.
    env["JAX_PLATFORMS"] = "cpu"
    rows = []
    for p in counts:
        ladder = feasible_ladder(p, budget_mb, cap=cap)
        if not ladder:
            rows.append(dict(devices=p, error="empty ladder", ladder=[]))
            continue
        cfg = dict(
            devices=p, ladder=ladder, buckets=buckets, block_c=block_c,
            tol=tol, max_passes=max_passes, check_every=check_every,
            stop_rule=stop_rule, timed_passes=timed_passes,
            model_bytes={str(n): dual_slab_bytes(n) // p for n in ladder},
        )
        out = subprocess.run(
            [sys.executable, "-c", _WORKER, json.dumps(cfg)],
            capture_output=True, text=True, env=env, cwd=ROOT,
            timeout=timeout,
        )
        if out.returncode != 0 or "WORKER_DONE" not in out.stdout:
            rows.append(dict(
                devices=p, error=(out.stderr or out.stdout)[-500:],
                ladder=[],
            ))
            continue
        per_n = [
            json.loads(line[len("ROW "):])
            for line in out.stdout.splitlines()
            if line.startswith("ROW ")
        ]
        top = per_n[-1]
        rows.append(dict(
            devices=p, platform=top["platform"], largest_n=top["n"],
            pass_ms=top["pass_ms"],
            probe_ms=top["probe_ms"],
            peak_live_bytes=top["peak_live_bytes"],
            viol=top["viol"], gap=top["gap"], converged=top["converged"],
            snapshot_block_ms=top["snapshot_block_ms"],
            snapshot_dispatch_ms=top["snapshot_dispatch_ms"],
            ladder=per_n,
        ))
    return rows


def _report(rows, mode, budget_mb, json_path):
    for row in rows:
        if "error" in row:
            print(f"BENCH_scale p={row['devices']} FAILED {row['error']}")
            continue
        for r in row["ladder"]:
            print(
                f"BENCH_scale p={r['devices']} n={r['n']} "
                f"pass_ms={r['pass_ms']:.1f} probe_ms={r['probe_ms']:.1f} "
                f"peak_mb={r['peak_live_bytes'] / 1e6:.1f} "
                f"snapshot_block_ms={r['snapshot_block_ms']:.1f} "
                f"snapshot_dispatch_ms={r['snapshot_dispatch_ms']:.1f} "
                f"platform={r['platform']}"
            )
        print(
            f"certificate p={row['devices']} largest_n={row['largest_n']} "
            f"viol={row['viol']:.3e} gap={row['gap']:.3e} "
            f"converged={row['converged']}"
        )
    doc = dict(mode=mode, budget_mb=budget_mb, ladder=list(LADDER),
               rows=rows)
    with open(json_path, "w") as fh:
        json.dump(doc, fh, indent=1)
    print(f"wrote {json_path}")
    return doc


def run() -> list[dict]:
    """benchmarks.run registry entry: the smoke campaign (full with env
    REPRO_SCALE_FULL=1), BENCH_scale.json written as a side effect."""
    full = os.environ.get("REPRO_SCALE_FULL") == "1"
    budget = FULL_BUDGET_MB if full else SMOKE_BUDGET_MB
    cap = None if full else SMOKE_CAP
    rows = _campaign(DEFAULT_COUNTS, budget, cap)
    _report(rows, "full" if full else "smoke", budget,
            os.path.join(ROOT, "BENCH_scale.json"))
    out = []
    for row in rows:
        if "error" in row:
            out.append(dict(name=f"scale/p{row['devices']}", us_per_call=-1,
                            derived="FAILED " + row["error"][:200]))
            continue
        out.append(dict(
            name=f"scale/p{row['devices']}",
            us_per_call=row["pass_ms"] * 1e3,
            derived=(
                f"platform={row['platform']} largest_n={row['largest_n']} "
                f"probe_ms={row['probe_ms']:.1f} "
                f"peak_mb={row['peak_live_bytes'] / 1e6:.1f} "
                f"converged={row['converged']} "
                f"snapshot_overlap_ms="
                f"{row['snapshot_block_ms'] - row['snapshot_dispatch_ms']:.1f}"
            ),
        ))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="KB-scale budget, ladder capped (CI leg)")
    ap.add_argument("--full", action="store_true",
                    help="the checked-in artifact's budget")
    ap.add_argument("--budget-mb", type=float, default=None,
                    help="override the per-device dual-slab budget")
    ap.add_argument("--counts", default=None,
                    help="comma-separated device counts (default 1,4,8)")
    ap.add_argument("--json", default=os.path.join(ROOT, "BENCH_scale.json"))
    ap.add_argument("--max-passes", type=int, default=200)
    ap.add_argument("--tol", type=float, default=2e-3)
    args = ap.parse_args(argv)
    if args.full or os.environ.get("REPRO_SCALE_FULL") == "1":
        mode, budget, cap = "full", FULL_BUDGET_MB, None
    else:
        mode, budget, cap = "smoke", SMOKE_BUDGET_MB, SMOKE_CAP
    if args.budget_mb is not None:
        budget = args.budget_mb
    counts = (
        tuple(int(c) for c in args.counts.split(","))
        if args.counts else DEFAULT_COUNTS
    )
    rows = _campaign(counts, budget, cap, tol=args.tol,
                     max_passes=args.max_passes)
    _report(rows, mode, budget, args.json)
    return 0 if all("error" not in r for r in rows) else 1


if __name__ == "__main__":
    raise SystemExit(main())
