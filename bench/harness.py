"""What every cell shares: the manifest, the device check, the window's
clock and compile count, the profiler, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own and is found by name:

  * ``bench/configs/<config>.json``: the deployment; its ``entry`` names
    the module ``bench/entries/<entry>.py`` that drives it;
  * ``bench/traffic/<traffic>.json``: the parameters of one mix, read by
    the configuration's entry;
  * ``bench/metrics/<metric>.py``: a reader ``read(ctx)`` that returns the
    metric's value, or None where it finds nothing to read.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class BenchError(RuntimeError):
    """The run cannot give a result (no chip, unknown cell or device)."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(MANIFEST)


def cell_of(man: dict, workload: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == workload:
            return w
    raise BenchError(f"no workload {workload!r} in BENCHMARK.json")


def config_of(man: dict, cell: dict) -> dict:
    for c in man["configs"]:
        if c["name"] == cell["config"]:
            return load_json(os.path.join(ROOT, c["file"]))
    raise BenchError(f"no config {cell['config']!r} in BENCHMARK.json")


def traffic_of(cell: dict) -> dict:
    return load_json(os.path.join(BENCH_DIR, "traffic",
                                  cell["traffic"] + ".json"))


def entry_of(cfg: dict):
    return importlib.import_module(f"bench.entries.{cfg['entry']}")


def metrics_for(man: dict, cell: dict, kind: str) -> list[dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metrics."""
    return [m for m in man[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def reader(metric: str):
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str) -> dict:
    peaks = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def use_compile_cache() -> None:
    """The program's persistent compile cache, at its fixed path, keeping
    every program so that only a checkout's first run compiles."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def check_devices(chips: int) -> dict:
    """The chip the cell runs on: a TPU, at least ``chips`` of them, and a
    kind the peak table knows. Anything else is an error, not a default."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    kind = devs[0].device_kind
    peaks_for(kind)
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """``peak_bytes_in_use`` of the fullest chip the cell uses."""
    import jax

    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()[:chips]]
    return max(peaks)


class Tools:
    """Handed to an entry: the window's clock, the compile count inside
    it, the profiler and the host spans the trace reduction reads."""

    COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self, t_start: float, trace: bool, chips: int):
        import jax

        self.t_start = t_start
        self.trace = trace
        self.chips = chips
        self.t_open = self.t_close = None
        self.compiles = 0
        self._counting = False
        self.memory_peak = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if self._counting and event == self.COMPILE_EVENT:
            self.compiles += 1

    @staticmethod
    def clock() -> float:
        return time.perf_counter()

    def open_window(self) -> float:
        self.t_open = self.clock()
        self._counting = True
        return self.t_open

    def close_window(self) -> float:
        self.t_close = self.clock()
        self._counting = False
        return self.t_close

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_start

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def read_memory(self) -> int:
        self.memory_peak = memory_peak_bytes(self.chips)
        return self.memory_peak

    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace (no cost when it is off)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def profile(self):
        """Profile the enclosed steady part of the window (trace runs only),
        inside one ``bench.window`` span that bounds the reduction."""
        if not self.trace:
            yield
            return
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        try:
            with self.span("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def result_line(*, correct, attempted, failed, metrics, device, checks,
                breakdown=None, extra_device=None) -> str:
    out = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device | (extra_device or {}),
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)
