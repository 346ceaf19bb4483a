"""Solver launcher: the paper's application as a first-class framework job.

    PYTHONPATH=src python -m repro.launch.solve --graph ba --n 60 \
        --passes 100 --ckpt-dir /tmp/cc_ckpt

Builds a CC instance (generator or edge-list file), solves the metric-
constrained LP with the parallel conflict-free schedule (multi-device when
devices exist), checkpoints (X, F, duals, pass counter) every ``--ckpt-every``
passes and auto-resumes — the solver analogue of launch/train.py.

Solve-to-tolerance runs on the device-resident convergence engine
(DESIGN.md §7): each checkpoint window is ONE ``run_until`` device program —
a jitted ``lax.while_loop`` of ``--chunk``-pass chunks with the stopping
pair (max violation, |duality gap|) tested on device — so the host is
consulted once per window, not once per chunk. Checkpoint ``extra``
carries the device metrics of the saved state and the layout's lane deal
(``schedule.LANE_DEAL``): sharded dual slabs are saved raw, so a resume
refuses slabs dealt over several devices under another deal.

Fault drills (DESIGN.md §11): ``--inject "kind@site:at[:k=v,..];.."`` or
``--fault-seed N`` arm a deterministic ``FaultInjector`` threaded through
every layer this launcher touches — checkpoint save/restore (corruption
walks back to the newest intact step at resume), the run_until chunk
boundary (NaN poison trips the divergence guard), and, when ``--sharded``,
the mesh site: an injected ``device_loss`` at a window boundary reshards
the live duals onto the survivor mesh (``elastic.degrade_solver``) and
the solve continues — printing ``degraded p=P->Q, resumed at pass K``,
the line the CI chaos leg pins.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core import problems, rounding, schedule as sched
from repro.core.parallel_dykstra import ParallelSolver
from repro.core.sharded_dykstra import ShardedSolver
from repro.graphs import generators, io as gio, jaccard
from repro.launch import elastic, mesh as mesh_lib
from repro.launch.compile_cache import use_compile_cache
from repro.train import checkpoint as ckpt_lib


def check_lane_deal(procs: int):
    """A ``resume_or`` check: dual slabs dealt over ``procs`` > 1 devices
    resume only under the lane deal that wrote them. Checkpoints written
    before the deal was recorded dealt lane f to device f mod procs."""

    def check(manifest):
        deal = manifest.get("extra", {}).get("lane_deal")
        if procs > 1 and deal != sched.LANE_DEAL:
            raise ckpt_lib.CheckpointError(
                f"step {manifest.get('step')} holds dual slabs dealt over "
                f"{procs} devices under lane deal "
                f"{deal or 'f mod procs (untagged)'}, but this solver deals "
                f"{sched.LANE_DEAL!r} runs: its duals would resume in the "
                "wrong lanes. Resume it with the version that wrote it, or "
                "start afresh in another directory."
            )

    return check


def build_injector(args):
    """Arm the deterministic fault plan from --inject / --fault-seed
    (None when neither is given — the fault-free fast path)."""
    if not args.inject and args.fault_seed is None:
        return None
    from repro.serve import faults as flt

    plan = flt.FaultPlan.parse(args.inject) if args.inject else flt.FaultPlan()
    if args.fault_seed is not None:
        plan = plan + flt.FaultPlan.seeded(args.fault_seed)
    return flt.FaultInjector(plan)


def build_instance(args):
    if args.edgelist:
        adj = gio.load_edgelist(args.edgelist)
    elif args.graph == "ba":
        adj = generators.collaboration_like(args.n, seed=args.seed)
    elif args.graph == "ws":
        adj = generators.small_world(args.n, seed=args.seed)
    else:
        adj, _ = generators.planted_partition(args.n, seed=args.seed)
    dissim, weights = jaccard.signed_instance(adj)
    return dissim, weights


def run_serve(args):
    """--serve: a stream of generated instances through the batched
    solve service (drain or continuous mode), reporting the scheduler's
    occupancy / queue high-water / refill telemetry (DESIGN.md §12)."""
    from repro.serve.scheduler import BatchScheduler

    sizes = [int(s) for s in args.serve.split(",")]
    ladder = tuple(int(s) for s in args.serve_ladder.split(","))
    sched = BatchScheduler(
        ladder=ladder, batch=args.serve_batch, tol=args.tol,
        max_passes=args.passes, check_every=args.chunk,
        stop_rule=args.stop_rule, use_kernel=args.use_kernel,
        mode=args.serve_mode, faults=build_injector(args),
    )
    t0 = time.time()
    for i, n in enumerate(sizes):
        adj, _ = generators.planted_partition(n, seed=args.seed + i)
        dissim, weights = jaccard.signed_instance(adj)
        sched.submit(
            problems.correlation_clustering_lp(dissim, weights, eps=args.eps),
            tag=i,
        )
    results = sched.drain()
    wall = time.time() - t0
    for i, n in enumerate(sizes):
        r = results[i]
        if r.get("route") == "failed":
            print(f"serve {i}: n={n} route=failed error={r.get('error')}")
            continue
        print(f"serve {i}: n={n} bucket={r['bucket_n']} route={r['route']} "
              f"passes={r['passes']} converged={r['converged']} "
              f"viol={r['max_violation']:.2e}")
    stats = sched.stats()
    hwm = ",".join(
        f"{k}:{v}" for k, v in sorted(
            stats["queue_depth_hwm"].items(), key=lambda kv: str(kv[0])
        )
    )
    print(f"serve stats: mode={stats['mode']} "
          f"instances={stats['instances_done']} "
          f"occupancy={stats['occupancy']:.2f} queue_hwm=[{hwm}] "
          f"refills={stats['refills']} chunks={stats['chunks_run']} "
          f"dead_letters={stats['faults']['dead_letters']} "
          f"throughput={stats['instances_done'] / max(wall, 1e-9):.3f} inst/s "
          f"(wall {wall:.1f}s)")
    sched.close()
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="ba", choices=["ba", "ws", "sbm"])
    ap.add_argument("--edgelist", default=None)
    ap.add_argument("--n", type=int, default=60)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eps", type=float, default=0.05)
    ap.add_argument("--passes", type=int, default=100)
    ap.add_argument("--chunk", type=int, default=10,
                    help="passes per on-device convergence check")
    ap.add_argument("--buckets", type=int, default=6)
    ap.add_argument("--use-kernel", action="store_true",
                    help="route the sweep through the gen-3 Pallas "
                         "megakernel — identical behavior on solo and "
                         "sharded invocations (DESIGN.md §10)")
    ap.add_argument("--block-c", type=int, default=None,
                    help="kernel lane-tile size (sets the megakernel's "
                         "default block_c; paper Fig. 7 tile-size knob)")
    ap.add_argument("--sharded", action="store_true", help="shard over all devices")
    ap.add_argument("--no-fused", action="store_true",
                    help="legacy one-dispatch-per-pass baseline (both "
                         "solvers; benchmarking only)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--tol", type=float, default=1e-4)
    ap.add_argument("--forget-every", type=int, default=0,
                    help="Project-and-Forget active-set mode (DESIGN.md "
                         "§13): forget/revive constraints every this many "
                         "passes (0 = dense solve). Solo runs only.")
    ap.add_argument("--forget-tol", type=float, default=0.0,
                    help="forget a constraint when max|y| <= this "
                         "(0.0 catches exactly Dykstra's inactive zeros)")
    ap.add_argument("--revive-tol", type=float, default=None,
                    help="re-admit a forgotten constraint violated beyond "
                         "this (default 0.5 * --tol)")
    ap.add_argument("--compact-every", type=int, default=0,
                    help="repack slabs to the active set every this many "
                         "forget rounds (0 = mask only, never repack)")
    ap.add_argument("--stop-rule", default="absolute",
                    choices=["absolute", "rel_gap", "plateau"],
                    help="run_until stopping rule (engine.STOP_RULES)")
    ap.add_argument("--round", action="store_true", help="pivot-round at the end")
    ap.add_argument("--serve", default=None, metavar="SIZES",
                    help="serve mode: route a comma-separated list of "
                         "instance sizes through the BatchScheduler "
                         "(bucketed batched solve) instead of one solo "
                         "solve, and print its occupancy / queue "
                         "high-water / refill stats (DESIGN.md §12)")
    ap.add_argument("--serve-mode", default="drain",
                    choices=["drain", "continuous"],
                    help="scheduler dispatch mode for --serve")
    ap.add_argument("--serve-batch", type=int, default=4,
                    help="batch slots per bucket for --serve")
    ap.add_argument("--serve-ladder", default="32,64,96,128",
                    help="bucket ladder for --serve")
    ap.add_argument("--inject", default=None,
                    help="deterministic fault plan, 'kind@site:at[:k=v,..]' "
                         "specs joined with ';' (serve/faults.py grammar) — "
                         "e.g. 'device_loss@mesh:1:p=4;ckpt_corrupt@ckpt_save:0'")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="additionally draw a seeded random FaultPlan "
                         "(replayable chaos)")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.block_c is not None:
        from repro.kernels.metric_project import ops as kops

        kops.set_default_block_c(args.block_c)

    if args.serve:
        return run_serve(args)

    dissim, weights = build_instance(args)
    n = dissim.shape[0]
    ncon = 3 * n * (n - 1) * (n - 2) // 6 + n * (n - 1)
    print(f"n={n}  constraints={ncon:,}  eps={args.eps}")

    prob = problems.correlation_clustering_lp(dissim, weights, eps=args.eps)
    sparse = args.forget_every > 0
    if sparse:
        for flag, name in ((args.sharded, "--sharded"),
                           (args.use_kernel, "--use-kernel"),
                           (args.no_fused, "--no-fused"),
                           (args.ckpt_dir, "--ckpt-dir")):
            if flag:
                ap.error(f"--forget-every is solo fused only: {name} is "
                         "not supported with the sparse active-set mode "
                         "(DESIGN.md §13)")
        from repro.sparse import SparseSolver

        solver = SparseSolver(
            prob, bucket_diagonals=args.buckets,
            forget_every=args.forget_every, forget_tol=args.forget_tol,
            revive_tol=args.revive_tol, compact_every=args.compact_every,
        )
    elif args.sharded:
        solver = ShardedSolver(prob, mesh_lib.make_solver_mesh(),
                               num_buckets=args.buckets,
                               use_kernel=args.use_kernel,
                               fused=not args.no_fused)
    else:
        solver = ParallelSolver(prob, bucket_diagonals=args.buckets,
                                use_kernel=args.use_kernel,
                                fused=not args.no_fused)
    injector = build_injector(args)
    state = solver.init_state()
    done = 0
    mgr = None
    if args.ckpt_dir:
        mgr = ckpt_lib.CheckpointManager(
            args.ckpt_dir, every=args.ckpt_every, faults=injector
        )
        state, done = mgr.resume_or(
            state, check=check_lane_deal(solver.nproc if args.sharded else 1)
        )
        if done:
            print(f"resumed at pass {done}")

    t0 = time.time()
    converged = False
    extra = {}
    info = {}
    while done < args.passes and not converged:
        if injector is not None and args.sharded:
            # Window boundaries are the degradation points (DESIGN.md
            # §11): an injected device loss reshards the live duals onto
            # the survivor mesh and the same loop continues.
            for spec in injector.poll("mesh"):
                if spec.kind == "device_loss":
                    p_old = int(solver.nproc)
                    p_new = int(spec.payload.get("p", max(1, p_old // 2)))
                    solver, state = elastic.degrade_solver(
                        solver, state, p_new
                    )
                    print(f"degraded p={p_old}->{p_new}, "
                          f"resumed at pass {done}")
        # One checkpoint window = one run_until device program; without
        # checkpointing the whole solve is a single program.
        window = args.passes - done
        if mgr:
            window = min(window, args.ckpt_every)
        prev_done = done
        t_win = time.perf_counter()
        state, info = solver.run_until(
            state, tol=args.tol, max_passes=done + window,
            check_every=min(args.chunk, window), stop_rule=args.stop_rule,
            faults=injector,
        )
        win_s = time.perf_counter() - t_win
        done = info["passes"]
        converged = info["converged"]
        res = info["residuals"]
        res_tail = f" |dx|={res[-1]:.2e}" if len(res) else ""
        if sparse:
            res_tail += f" active_frac={info['active_fraction']:.3f}"
        # Per-window diagnosability at scale (DESIGN.md §14): peak device
        # memory, amortized pass time, and one warm timed stopping probe —
        # so probe-vs-pass split and the memory ceiling read straight off
        # the log. The probe fn is the engine's cached jit; the first
        # window pays its compile in the warm-up call, not the timing.
        probe = solver._probe_fn()
        jax.block_until_ready(probe(state))
        t_pr = time.perf_counter()
        jax.block_until_ready(probe(state))
        probe_ms = (time.perf_counter() - t_pr) * 1e3
        pass_ms = win_s * 1e3 / max(1, int(done) - int(prev_done))
        mem_b, mem_src = mesh_lib.device_memory_bytes()
        print(f"pass {done:4d}: lp={info['lp_objective']:.4f} "
              f"viol={info['max_violation']:.2e} gap={info['duality_gap']:.2e}"
              f"{res_tail} mem={mem_b / 1e6:.1f}MB({mem_src}) "
              f"pass={pass_ms:.1f}ms probe={probe_ms:.1f}ms "
              f"({time.time()-t0:.1f}s)")
        if mgr:
            extra = {
                k: (v.tolist() if isinstance(v, np.ndarray) else v)
                for k, v in info.items()
            }
            # Donated copy-on-save snapshot (DESIGN.md §14): the window's
            # state is rebound to the snapshot program's live alias; the
            # device→host transfer runs on the writer thread.
            _, state = mgr.maybe_save(
                done, state, extra={"n": n, "eps": args.eps,
                                    "lane_deal": sched.LANE_DEAL, **extra},
                donate=True,
            )
        if info.get("diverged"):
            # the guard already restored the last finite iterate; keep it
            # (and its checkpoint) instead of burning the remaining passes.
            print(f"diverged at pass {done}: stopping with the last "
                  "finite iterate")
            break
    if sparse and info:
        # One-line sparsification report (the CI sparsify leg pins it);
        # lp at full precision so the certificate can be compared against
        # the dense full-constraint solve.
        print(f"sparsify: rounds={info['rounds']} "
              f"compactions={info['compactions']} "
              f"active_frac={info['active_fraction']:.3f} "
              f"lp={info['lp_objective']:.6f}")
    if converged:
        print("converged")
        if mgr and done % args.ckpt_every != 0:
            # the cadence would skip the terminal state — force-save it
            # (satellite of DESIGN.md §11's recoverability contract).
            _, state = mgr.maybe_save(
                done, state, extra={"n": n, "eps": args.eps,
                                    "lane_deal": sched.LANE_DEAL, **extra},
                force=True, donate=True,
            )
    if mgr:
        ckpt_lib.wait_pending()

    if args.round:
        x = np.asarray(state.x, np.float64)
        cert = rounding.certificate(x, dissim, weights, trials=8)
        print(f"clusters={cert['num_clusters']} cost={cert['cc_cost']:.3f} "
              f"lp_lb={cert['lp_lower_bound']:.3f} "
              f"ratio={cert['approx_ratio_certificate']:.3f}")
    return state


if __name__ == "__main__":
    main()
