"""End-to-end graph -> clustering serving pipeline (DESIGN.md §8/§9).

One callable, shared by the CLI below, the CI smoke legs and
``benchmarks/serve_throughput.py``:

    adjacency -> signed CC instance (graphs/jaccard.py)
              -> correlation_clustering_lp
              -> batched vmapped solve (scheduler + BatchedSolver) —
                 whole-batch drain mode or slot-level continuous
                 batching (``--mode continuous``, DESIGN.md §12), with
                 optional Poisson arrivals (``--arrival-rate``) —
                 OR, above the ladder's top rung, a dedicated
                 ShardedSolver.run_until slot at native n (§9 routing)
              -> batched device pivot rounding (rounding.pivot_round_device)
              -> labels + per-instance approximation certificates.

The solve never leaves the device between LP and labels: rounding runs on
the *padded* iterate under the ghost-aware live mask (one jitted program
per (bucket_n, trials), vmapped over rounding trials), so per-instance
shapes never recompile anything.

    PYTHONPATH=src python -m repro.serve.pipeline --sizes 18,22,26 --batch 4
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics_device, problems, rounding
from repro.graphs import generators, jaccard
from repro.launch.compile_cache import use_compile_cache
from repro.serve import buckets as bk
from repro.serve.scheduler import BatchScheduler

__all__ = ["cluster_graphs", "round_device_batch"]


@functools.lru_cache(maxsize=16)
def _round_fn(bucket_n: int, trials: int):
    """Jitted (padded) rounding program: vmap over trials, pick the
    cheapest clustering, report cost + LP lower bound."""

    def go(x, orders, dissim, weights, n_real):
        mask = metrics_device.live_pair_mask(bucket_n, n_real)
        labs = jax.vmap(
            lambda o: rounding.pivot_round_device(x, o, n_real=n_real)
        )(orders)  # (trials, bucket_n)
        costs = jax.vmap(
            lambda l: rounding.cc_cost_device(l, dissim, weights, mask)
        )(labs)
        best = jnp.argmin(costs)
        lp_lb = jnp.sum(
            jnp.where(mask, weights * jnp.abs(x - dissim), 0.0)
        )
        return labs[best], costs[best], lp_lb

    return jax.jit(go)


def round_device_batch(
    x_pad, dissim, weights, n_real: int, trials: int = 5, seed: int = 0
):
    """Device pivot rounding of one padded LP point; returns the numpy
    certificate dict of the best trial (same fields as
    ``rounding.certificate``). Pivot orders are permutations of the
    *padded* index range (ghosts skip themselves inside the kernel), so
    the jit cache keys on (bucket_n, trials) only."""
    bucket_n = x_pad.shape[0]
    orders = jnp.asarray(
        rounding.pivot_orders(bucket_n, seed=seed, trials=trials), jnp.int32
    )
    labels, cost, lp_lb = _round_fn(bucket_n, trials)(
        jnp.asarray(x_pad), orders, jnp.asarray(dissim),
        jnp.asarray(weights), n_real,
    )
    labels = np.asarray(labels)[:n_real]
    cost = float(cost)
    lp_lb = float(lp_lb)
    return {
        "labels": labels,
        "cc_cost": cost,
        "lp_lower_bound": lp_lb,
        "approx_ratio_certificate": cost / max(lp_lb, 1e-12),
        "num_clusters": int(len(np.unique(labels))),
    }


def cluster_graphs(
    adjs,
    *,
    ladder=bk.DEFAULT_LADDER,
    batch: int = 8,
    eps: float = 0.05,
    tol: float = 1e-3,
    max_passes: int = 200,
    check_every: int = 10,
    stop_rule: str = "absolute",
    trials: int = 5,
    seed: int = 0,
    dtype=np.float32,
    scheduler: BatchScheduler | None = None,
    use_kernel: bool = False,
    mode: str = "drain",
    arrival_rate: float | None = None,
):
    """Cluster a stream of graphs through the batched solve service.

    Args:
      adjs: iterable of (n, n) boolean adjacency matrices (any mix of
        sizes up to the ladder max).
      scheduler: optionally a pre-warmed ``BatchScheduler`` (shares its
        compile cache across calls); otherwise one is built from the
        solve arguments.
      mode: scheduler dispatch mode — ``"drain"`` micro-batching or
        ``"continuous"`` slot-level continuous batching (DESIGN.md §12).
      arrival_rate: if set, submissions follow a Poisson stream at this
        rate (instances/sec; seeded exponential inter-arrival sleeps)
        instead of arriving as one burst — the sustained-load shape the
        CI smoke leg drives through the continuous scheduler.

    Returns ``(results, stats)``: one dict per input graph — ``labels``,
    ``num_clusters``, ``cc_cost``, ``lp_lower_bound``,
    ``approx_ratio_certificate`` plus the solve telemetry (``passes``,
    ``converged``, ``max_violation``, ``duality_gap``, ``bucket_n``) —
    and the scheduler's throughput/occupancy/cache stats.
    """
    sched_ = scheduler
    if sched_ is None:
        sched_ = BatchScheduler(
            ladder=ladder, batch=batch, dtype=dtype,
            tol=tol, max_passes=max_passes, check_every=check_every,
            stop_rule=stop_rule, use_kernel=use_kernel, mode=mode,
        )
    rng = np.random.default_rng(seed)
    instances = []
    for g, adj in enumerate(adjs):
        if arrival_rate:
            time.sleep(rng.exponential(1.0 / float(arrival_rate)))
        dissim, weights = jaccard.signed_instance(np.asarray(adj))
        prob = problems.correlation_clustering_lp(dissim, weights, eps=eps)
        fut = sched_.submit(prob, tag=g)
        instances.append((fut.tag, prob, dissim, weights))
    solved = sched_.drain()

    results = []
    for tag, prob, dissim, weights in instances:
        r = solved[tag]
        if r.get("route") == "failed":
            # Dead-letter (validation reject, persistent dispatch fault,
            # diverged slot): surface the typed error per graph instead
            # of crashing the whole stream on a missing iterate.
            results.append(
                {
                    "graph": tag,
                    "n": prob.n,
                    "bucket_n": r["bucket_n"],
                    "route": "failed",
                    "error": r.get("error"),
                    "error_detail": r.get("error_detail"),
                    "passes": r.get("passes", 0),
                    "converged": False,
                }
            )
            continue
        n, bucket_n = prob.n, r["bucket_n"]
        # Above-ladder instances come back from the sharded route at
        # native n (bucket_n == n): the pad is a no-op and the ghost-aware
        # rounding degrades to plain device rounding — one code path.
        pad = lambda a: np.pad(a, ((0, bucket_n - n), (0, bucket_n - n)))
        cert = round_device_batch(
            r["x_pad"], pad(dissim), pad(weights), n,
            trials=trials, seed=seed,
        )
        results.append(
            {
                "graph": tag,
                "n": n,
                "bucket_n": bucket_n,
                "route": r.get("route", "batch"),
                "passes": r["passes"],
                "converged": r["converged"],
                "max_violation": r["max_violation"],
                "duality_gap": r["duality_gap"],
                "lp_objective": r["lp_objective"],
                **cert,
            }
        )
    return results, sched_.stats()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", default="18,22,26",
                    help="comma-separated graph sizes")
    ap.add_argument("--kind", default="sbm", choices=["sbm", "ba", "ws"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ladder", default="32,64,96,128")
    ap.add_argument("--eps", type=float, default=0.05)
    ap.add_argument("--tol", type=float, default=1e-3)
    ap.add_argument("--max-passes", type=int, default=200)
    ap.add_argument("--check-every", type=int, default=10)
    ap.add_argument("--stop-rule", default="absolute",
                    choices=["absolute", "rel_gap", "plateau"])
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-kernel", action="store_true",
                    help="route solves through the gen-3 Pallas megakernel "
                         "(batched AND sharded paths; DESIGN.md §10)")
    ap.add_argument("--mode", default="drain",
                    choices=["drain", "continuous"],
                    help="dispatch mode: whole-batch micro-batching or "
                         "slot-level continuous batching (DESIGN.md §12)")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="Poisson arrival rate (instances/sec); default: "
                         "submit everything as one burst")
    args = ap.parse_args(argv)
    use_compile_cache()

    sizes = [int(s) for s in args.sizes.split(",")]
    ladder = tuple(int(s) for s in args.ladder.split(","))
    adjs = generators.graph_batch(sizes, kind=args.kind, seed=args.seed)
    t0 = time.perf_counter()
    results, stats = cluster_graphs(
        adjs, ladder=ladder, batch=args.batch, eps=args.eps, tol=args.tol,
        max_passes=args.max_passes, check_every=args.check_every,
        stop_rule=args.stop_rule, trials=args.trials, seed=args.seed,
        use_kernel=args.use_kernel, mode=args.mode,
        arrival_rate=args.arrival_rate,
    )
    wall = time.perf_counter() - t0
    for r in results:
        if r["route"] == "failed":
            print(
                f"graph {r['graph']}: n={r['n']} route=failed "
                f"error={r['error']} ({r['error_detail']})"
            )
            continue
        print(
            f"graph {r['graph']}: n={r['n']} bucket={r['bucket_n']} "
            f"route={r['route']} "
            f"passes={r['passes']} converged={r['converged']} "
            f"clusters={r['num_clusters']} cost={r['cc_cost']:.3f} "
            f"lp_lb={r['lp_lower_bound']:.3f} "
            f"ratio={r['approx_ratio_certificate']:.3f}"
        )
    print(
        f"pipeline: instances={stats['instances_done']} "
        f"batches={stats['batches_run']} "
        f"occupancy={stats['occupancy']:.2f} "
        f"cache_misses={stats['compile_cache']['misses']} "
        f"instances/sec={stats['instances_done'] / wall:.3f} "
        f"(wall {wall:.1f}s, solve {stats['solve_time_s']:.1f}s)"
    )
    hwm = ",".join(
        f"{k}:{v}" for k, v in sorted(
            stats["queue_depth_hwm"].items(), key=lambda kv: str(kv[0])
        )
    )
    # terminal=K/N pins the §11 invariant the CI sustained-load leg
    # asserts: every submitted graph reached exactly one terminal result.
    print(
        f"serve: mode={stats['mode']} "
        f"refills={stats['refills']} chunks={stats['chunks_run']} "
        f"queue_hwm=[{hwm}] "
        f"dead_letters={stats['faults']['dead_letters']} "
        f"terminal={len(results)}/{len(sizes)}"
    )
    return results, stats


if __name__ == "__main__":
    main()
