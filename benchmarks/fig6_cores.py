"""Paper Fig. 6 analogue: fixed work, varying processor count.

The paper sweeps cores on ca-HepPh. We sweep host-device count for the
sharded solver (subprocess per count — jax locks the device count at init).
On this 1-core container the wall-clock cannot show real scaling, so the
derived metric also reports the collective/compute split that governs
scaling on a real mesh (one n² psum per diagonal; per-device work n³/p).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

N = 40
PASSES = 3
COUNTS = (1, 2, 4, 8)

_SCRIPT = textwrap.dedent("""
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.core import problems
    from repro.core.sharded_dykstra import ShardedSolver
    from repro.graphs import generators, jaccard

    adj = generators.collaboration_like(%d, seed=1)
    dissim, w = jaccard.signed_instance(adj)
    prob = problems.correlation_clustering_lp(dissim, w, eps=0.05)
    mesh = Mesh(np.array(jax.devices()), ("solver",))
    solver = ShardedSolver(prob, mesh, num_buckets=4)
    st = solver.run(passes=1)  # warmup/compile
    t0 = time.time()
    solver.run(st, passes=%d)
    dt = time.time() - t0
    m = solver.metrics(solver.run(st, passes=1))
    print(json.dumps({"p": len(jax.devices()), "seconds": dt,
                      "platform": jax.devices()[0].platform,
                      "viol": m["max_violation"]}))
""")


def run() -> list[dict]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # Forced host devices, never the accelerator: a chip belongs to one
    # process, and the benchmarks.run parent may hold it.
    env["JAX_PLATFORMS"] = "cpu"
    rows = []
    base = None
    for p in COUNTS:
        out = subprocess.run(
            [sys.executable, "-c", _SCRIPT % (p, N, PASSES)],
            capture_output=True, text=True, env=env, timeout=900,
        )
        if out.returncode != 0:
            rows.append(dict(name=f"fig6/p{p}", us_per_call=-1,
                             derived="FAILED " + out.stderr[-200:]))
            continue
        d = json.loads(out.stdout.strip().splitlines()[-1])
        if base is None:
            base = d["seconds"]
        rows.append(dict(
            name=f"fig6/p{p}",
            us_per_call=d["seconds"] / PASSES * 1e6,
            derived=f"platform={d['platform']} "
                    f"rel_time={d['seconds']/base:.2f} (1 host core; "
                    f"per-device work ∝ n³/p, psum ∝ n² per diagonal)",
        ))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
