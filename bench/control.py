"""The control of a cell's check: the reference put in the program's
place, computed one precision lower (bfloat16 for the float32 the
configurations state), and compared by the cell's own check. Each line
gives one seed's numbers beside the limits; the control has to come out
not correct. Benchmark runs never run this.

    python3 bench/control.py --workload <cell> --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check, harness  # noqa: E402


def control_values(cfg: dict, traffic: dict, seed: int,
                   dtype=None) -> dict:
    import jax.numpy as jnp

    from bench.entries import solo

    dissim, w = solo.build_problem(cfg, seed)
    passes = int(traffic["check_every"]) * int(traffic["check_chunks"])
    got = solo.reference_chunk(w, dissim, float(cfg["eps"]), passes,
                               dtype or jnp.bfloat16)
    return solo.compare(cfg, w, dissim, got, jnp.float32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    man = harness.manifest()
    cell = harness.cell_of(man, args.workload)
    cfg = harness.config_of(man, cell)
    traffic = harness.traffic_of(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        values = control_values(cfg, traffic, seed)
        correct, checks = check.verdict(values, cfg["check"]["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": correct, "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
