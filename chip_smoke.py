#!/usr/bin/env python3
"""Smoke run of the solver's main path on TPU, through its entry points.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the sharded path only

One chip, three phases:

  * serve: 16 planted-partition CC-LP instances (n = 48..128) through
    ``BatchScheduler(use_kernel=True, mode="continuous")`` on the ladder
    32,64,96,128 with batch 8; every request must land on a non-failed
    route, and the n=128 instance is compared with a solo
    ``ParallelSolver`` solve of it;
  * solo: an n=768 collaboration-network CC-LP (``--graph ba``) through
    ``repro.launch.solve`` with ``--use-kernel``, checkpointing every
    window, then run again to resume from the checkpoint;
  * parity: the kernel iterate against the jnp fused path after the same
    passes, and the kernel violation probe against the jnp probe.

``--four-chips`` runs only ``ShardedSolver`` at n=1024 CC-LP over a
4-device mesh (kernel sweep in psum delta mode plus the kernel probe)
against the jnp sharded path on the same mesh, and prints each device's
memory.

Timing and memory lines are smoke numbers, not benchmark results. The
last line of stdout is ``{"ok": true, "device": {...}}``; it is printed
only when every phase passed. Without a TPU the script exits 2 before any
phase. Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import io
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
WORK = os.path.join(ROOT, ".chip_smoke")  # checkpoints; listed in .gitignore

SOLO_N = 768
SHARDED_N = 1024
BUCKETS = 6
SERVE_LADDER = (32, 64, 96, 128)
SERVE_SIZES = tuple(48 + round(80 * i / 15) for i in range(16))  # 48..128
PARITY_TOL = 1e-5  # kernel vs jnp path, same program structure
SERVE_TOL = 1e-4   # batched (B=8) vs solo program, f32, up to 200 passes


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def cc_lp(n: int, graph: str = "ba", seed: int = 0):
    """The CC-LP instance ``repro.launch.solve`` builds for these flags."""
    from repro.core import problems
    from repro.launch import solve

    args = argparse.Namespace(edgelist=None, graph=graph, n=n, seed=seed)
    dissim, weights = solve.build_instance(args)
    return problems.correlation_clustering_lp(dissim, weights, eps=0.05)


def tee_main(main, argv):
    """Run an entry point's ``main(argv)``, echoing and keeping its stdout."""
    buf = io.StringIO()

    class Tee(io.TextIOBase):
        def write(self, s):
            sys.__stdout__.write(s)
            return buf.write(s)

    t0 = time.perf_counter()
    with contextlib.redirect_stdout(Tee()):
        out = main(argv)
    return out, buf.getvalue(), time.perf_counter() - t0


def memory_line(tag: str) -> None:
    from repro.launch import mesh as mesh_lib

    mem, src = mesh_lib.device_memory_bytes()
    log(f"smoke {tag}: device_memory_bytes={mem} source={src}")
    require(src == "device_stats",
            f"device memory came from {src}, not device_stats")


def phase_serve(sizes=SERVE_SIZES, ladder=SERVE_LADDER, batch=8,
                tol=1e-3, max_passes=200, check_every=10):
    """Continuous-batching serve on the kernel path, one instance checked
    against a solo solve."""
    import numpy as np

    from repro.core import problems
    from repro.core.parallel_dykstra import ParallelSolver
    from repro.graphs import generators, jaccard
    from repro.serve.scheduler import BatchScheduler

    probs = []
    for i, n in enumerate(sizes):
        adj, _ = generators.planted_partition(n, seed=i)
        dissim, weights = jaccard.signed_instance(adj)
        probs.append(
            problems.correlation_clustering_lp(dissim, weights, eps=0.05)
        )
    kw = dict(tol=tol, max_passes=max_passes, check_every=check_every)
    t0 = time.perf_counter()
    sched = BatchScheduler(ladder=ladder, batch=batch, use_kernel=True,
                           mode="continuous", **kw)
    for i, p in enumerate(probs):
        sched.submit(p, tag=i)
    results = sched.drain()
    wall = time.perf_counter() - t0
    stats = sched.stats()
    sched.close()
    for i, p in enumerate(probs):
        r = results[i]
        if r["route"] == "failed":
            log(f"serve {i}: n={p.n} route=failed error={r.get('error')}")
            continue
        log(f"serve {i}: n={p.n} bucket={r['bucket_n']} route={r['route']} "
            f"passes={r['passes']} converged={r['converged']} "
            f"viol={r['max_violation']:.3e}")
    require(sorted(results) == list(range(len(probs))),
            f"{len(results)}/{len(probs)} serve requests reached a result")
    failed = [i for i in results if results[i]["route"] == "failed"]
    require(not failed, f"serve requests {failed} failed")
    log(f"smoke serve: requests={len(probs)} wall_s={wall:.3f} "
        f"(compiles included) refills={stats['refills']} "
        f"chunks={stats['chunks_run']}")

    # The top-rung instance fills its bucket, so its solo solve runs the
    # same schedule with no ghost padding.
    i = max(range(len(probs)), key=lambda j: probs[j].n)
    p, r = probs[i], results[i]
    solo = ParallelSolver(p, bucket_diagonals=BUCKETS, use_kernel=True)
    st, info = solo.run_until(**kw)
    dx = float(np.max(np.abs(np.asarray(st.x) - r["x"])))
    log(f"serve vs solo: n={p.n} passes={r['passes']}/{info['passes']} "
        f"max|dX|={dx:.3e}")
    require(r["passes"] == info["passes"],
            "serve and solo stopped at different passes")
    require(dx <= SERVE_TOL, f"serve vs solo max|dX|={dx} > {SERVE_TOL}")


def _window_ms(out: str) -> list[float]:
    return [float(v) for v in re.findall(r" pass=([0-9.]+)ms", out)]


def phase_solo(n=SOLO_N, passes=6, split=4, chunk=2):
    """Solo kernel solve through ``repro.launch.solve``: windows with
    donated checkpoints, then a second run that resumes. Returns the
    kernel iterate (x, f) after ``passes`` passes."""
    import numpy as np

    from repro.launch import solve
    from repro.train import checkpoint as ckpt_lib

    ckpt = os.path.join(WORK, "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    argv = ["--graph", "ba", "--n", str(n), "--use-kernel",
            "--chunk", str(chunk), "--ckpt-dir", ckpt,
            "--ckpt-every", str(chunk), "--tol", "1e-9"]
    st, out1, t1 = tee_main(solve.main, argv + ["--passes", str(split)])
    windows = re.findall(r"^pass +([0-9]+):", out1, re.M)
    require(windows == [str(k) for k in range(chunk, split + 1, chunk)],
            f"solve windows {windows}")
    require(ckpt_lib.latest_step(ckpt) == split,
            f"latest checkpoint {ckpt_lib.latest_step(ckpt)} != {split}")
    ms1 = _window_ms(out1)
    log(f"smoke solo: first run wall_s={t1:.3f} (set-up and compile "
        f"included) window_pass_ms={ms1}")
    memory_line("solo")
    del st
    gc.collect()

    st, out2, t2 = tee_main(solve.main, argv + ["--passes", str(passes)])
    require(f"resumed at pass {split}" in out2, "the second run did not "
            f"resume at pass {split}")
    require(int(st.passes) == passes, f"resumed solve ended at pass "
            f"{int(st.passes)}, not {passes}")
    log(f"smoke solo: resumed run wall_s={t2:.3f} "
        f"window_pass_ms={_window_ms(out2)}")
    x, f = np.asarray(st.x), np.asarray(st.f)
    del st
    gc.collect()
    shutil.rmtree(ckpt, ignore_errors=True)
    return x, f


def phase_parity(x_kernel, f_kernel, n=SOLO_N, passes=6):
    """The kernel iterate against the jnp fused path at the same passes,
    and the kernel violation probe against the jnp probe."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import metrics_device
    from repro.core.parallel_dykstra import ParallelSolver
    from repro.kernels.metric_project import ops as kops

    t0 = time.perf_counter()
    ref = ParallelSolver(cc_lp(n), bucket_diagonals=BUCKETS)
    st0 = ref.init_state()
    m0 = ref.device_metrics(st0)
    t1 = time.perf_counter()
    st = ref.run(st0, passes=passes)
    jax.block_until_ready(st.x)
    t2 = time.perf_counter()
    dx = float(np.max(np.abs(np.asarray(st.x) - x_kernel)))
    log(f"smoke parity: jnp solver set-up_s={t1 - t0:.3f} "
        f"run_s={t2 - t1:.3f} ({passes} passes, compile included)")
    log(f"parity: kernel vs jnp after {passes} passes max|dX|={dx:.3e}")
    require(dx <= PARITY_TOL, f"kernel vs jnp max|dX|={dx} > {PARITY_TOL}")

    xs = metrics_device.symmetrize(ref._dprob.mask, jnp.asarray(x_kernel))
    vk = float(kops.triangle_violation(xs))
    vj = float(jax.jit(metrics_device.triangle_violation)(xs))
    log(f"parity: kernel probe={vk!r} jnp probe={vj!r}")
    require(vk == vj, "kernel and jnp violation probes differ")

    mk = ref.device_metrics(dataclasses.replace(
        st, x=jnp.asarray(x_kernel), f=jnp.asarray(f_kernel)
    ))
    log(f"parity: kernel iterate viol={mk['max_violation']:.3e} "
        f"gap={mk['duality_gap']:.3e} (pass 0 viol="
        f"{m0['max_violation']:.3e})")
    require(np.isfinite(mk["max_violation"]) and np.isfinite(
        mk["duality_gap"]), "viol or gap is not finite")
    require(mk["max_violation"] < m0["max_violation"],
            "violation did not fall from pass 0")


def phase_sharded(n=SHARDED_N, passes=2, devices=4):
    """ShardedSolver: kernel sweep (psum delta mode) and kernel probe
    against the jnp sharded path on the same mesh."""
    import jax
    import numpy as np

    from repro.core import metrics_device
    from repro.core.sharded_dykstra import AXIS, ShardedSolver
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_solver_mesh()
    require(mesh.devices.size == devices,
            f"solver mesh has {mesh.devices.size} devices, not {devices}")
    prob = cc_lp(n)
    iterates = {}
    for use_kernel in (True, False):
        tag = "kernel" if use_kernel else "jnp"
        t0 = time.perf_counter()
        solver = ShardedSolver(prob, mesh, num_buckets=BUCKETS,
                               use_kernel=use_kernel)
        t1 = time.perf_counter()
        st = solver.run(passes=passes)
        jax.block_until_ready(st.x)
        t2 = time.perf_counter()
        st = solver.run(st, passes=passes)
        jax.block_until_ready(st.x)
        t3 = time.perf_counter()
        log(f"smoke sharded {tag}: set-up_s={t1 - t0:.3f} "
            f"first_run_s={t2 - t1:.3f} (compile included) "
            f"pass_ms={(t3 - t2) * 1e3 / passes:.3f}")
        xs = metrics_device.symmetrize(solver._dprob.mask, st.x)
        probe = jax.jit(solver._triangle_violation)
        iterates[tag] = (np.asarray(st.x), float(probe(st.x)))
        if use_kernel:
            vj = float(jax.jit(
                lambda v: metrics_device.triangle_violation_sharded(
                    v, mesh, AXIS)
            )(xs))
            log(f"sharded: kernel probe={iterates[tag][1]!r} "
                f"jnp probe={vj!r}")
            require(iterates[tag][1] == vj,
                    "sharded kernel and jnp probes differ")
            staged = sum(a.nbytes for a in jax.tree.leaves(
                solver._staged_arrays()))
            for d in mesh.devices.flat:
                stats = d.memory_stats()
                require(stats and "peak_bytes_in_use" in stats,
                        f"{d} reports no memory stats")
                log(f"sharded memory: device={d.id} "
                    f"peak_bytes_in_use={stats['peak_bytes_in_use']} "
                    f"bytes_in_use={stats['bytes_in_use']} "
                    f"staged_share={staged // devices}")
                require(stats["peak_bytes_in_use"] >= staged // devices,
                        f"device {d.id} holds less than its staged share")
        del solver, st, xs
        gc.collect()
    dx = float(np.max(np.abs(iterates["kernel"][0] - iterates["jnp"][0])))
    log(f"sharded: kernel vs jnp after {2 * passes} passes "
        f"max|dX|={dx:.3e}")
    require(dx <= PARITY_TOL, f"sharded kernel vs jnp max|dX|={dx}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 4-chip mesh")
    args = ap.parse_args(argv)

    info = device_info()
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        log("chip_smoke: no TPU found")
        return 2
    want = 4 if args.four_chips else 1
    if info["count"] < want:
        log(f"chip_smoke: {want} chips needed, {info['count']} found")
        return 2

    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    if args.four_chips:
        phase_sharded()
    else:
        phase_serve()
        x, f = phase_solo()
        phase_parity(x, f)
    shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
