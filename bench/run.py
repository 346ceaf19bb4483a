"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` a short steady part of the window runs under the profiler and
the line carries the per-layer metrics, ``busy_s``/``window_s`` and the
``breakdown``. Every run checks the timed path against the reference and
prints each compared number beside its limit, last on stderr and last in
the result line. Without a TPU, or with a device the peak table does not
know, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check, harness, trace_reduce  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def layer_metrics(man, cell, ctx) -> dict:
    out = {}
    for m in harness.metrics_for(man, cell, "per_layer"):
        value = harness.reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    man = harness.manifest()
    cell = harness.cell_of(man, args.workload)
    cfg = harness.config_of(man, cell)
    traffic = harness.traffic_of(cell)
    entry = harness.entry_of(cfg)

    harness.use_compile_cache()
    device = harness.check_devices(int(cell["chips"]))
    tools = harness.Tools(T_START, bool(args.trace), int(cell["chips"]))
    out = entry.run(cfg, traffic, args.seed, args.seconds, tools)

    for line in out["notes"]:
        harness.log(line)
    harness.log(f"setup_s {tools.setup_s:.3f}; compiles in window "
                f"{tools.compiles}; memory peak {tools.memory_peak} B")
    extra, breakdown = {"memory_peak_bytes": tools.memory_peak}, None
    if args.trace:
        summary = None
        path = trace_reduce.find_xplane(harness.TRACE_DIR)
        if path is not None:
            summary = trace_reduce.reduce(*trace_reduce.from_xplane(path))
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
        if summary is None:
            raise harness.BenchError("the trace holds no device op")
        ctx = {"trace": summary, "counters": out["counters"],
               "peaks": harness.peaks_for(device["kind"]),
               "config": cfg, "cell": cell}
        metrics = layer_metrics(man, cell, ctx)
        extra |= {"busy_s": summary["busy_s"], "window_s": summary["window_s"]}
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
        harness.log(f"trace: busy {summary['busy_s']:.6f} s of "
                    f"{summary['window_s']:.6f} s; classes "
                    f"{summary['class_s']}")
    else:
        values = out["end_to_end"] | {"setup_s": tools.setup_s}
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in harness.metrics_for(man, cell, "end_to_end")}
    correct, checks = check.verdict(out["values"], cfg["check"]["limits"])
    harness.log(f"correct {correct}; the numbers compared:")
    for name, c in checks.items():
        harness.log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(harness.result_line(
        correct=correct, attempted=out["attempted"], failed=out["failed"],
        metrics=metrics, device=device, checks=checks, breakdown=breakdown,
        extra_device=extra), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.BenchError as e:
        harness.log(f"bench: {e}")
        sys.exit(3)
