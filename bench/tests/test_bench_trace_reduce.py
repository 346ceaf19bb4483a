"""The trace reduction on a synthetic trace and on a recorded CPU trace."""

import pytest

from bench import trace_reduce as tr

MS = 1_000_000  # ns
# op names as the TPU trace writes them: HLO instruction text
GATHER = ("%fusion.3 = f32[192]{0:T(256)S(1)} fusion(f32[768,768]{1,0:T(8,128)}"
          " %p, s32[192]{0:T(256)S(1)} %i), kind=kCustom, calls=%fc.3")
SWEEP = ("%metric_sweep.48 = (f32[1,768,256]{2,1,0:T(8,128)}) custom-call("
         "f32[1,768,256]{2,1,0:T(8,128)} %a), custom_call_target=\"tpu\"")
LOOP = ("%fusion.2 = f32[768,768]{1,0:T(8,128)} fusion(f32[768,768]{1,0:T(8,"
        "128)} %a), kind=kLoop, calls=%fc.2")
WHILE = ("%while.1 = (f32[768,768]{1,0:T(8,128)}, s32[]) while((f32[768,768]"
         "{1,0:T(8,128)}, s32[]) %t), condition=%c, body=%b")


def _synthetic():
    spans = [("bench.window", 0, 100 * MS), ("bench.chunk", 0, 60 * MS),
             ("bench.wait", 60 * MS, 40 * MS)]
    ops = [
        (GATHER, 0, 10 * MS, "/device:TPU:0"),
        (SWEEP, 5 * MS, 10 * MS, "/device:TPU:0"),  # overlaps
        ("%scatter.1 = f32[8,8]{1,0} scatter(f32[8,8]{1,0} %x, s32[4]{0} "
         "%i, f32[4]{0} %u)", 20 * MS, 10 * MS, "/device:TPU:0"),
        ("%dynamic-update-slice.7 = f32[8]{0} dynamic-update-slice(f32[8]"
         "{0} %a, f32[1]{0} %b, s32[] %c)", 50 * MS, 5 * MS, "/device:TPU:0"),
        (LOOP, 70 * MS, 10 * MS, "/device:TPU:0"),
        (LOOP, 95 * MS, 20 * MS, "/device:TPU:0"),  # clipped at 100
        (GATHER, 200 * MS, 10 * MS, "/device:TPU:0"),  # outside
        (WHILE, 0, 100 * MS, "/device:TPU:0"),  # control flow: busy only
    ]
    return ops, spans


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_reduce_busy_classes_and_gaps():
    ops, spans = _synthetic()
    s = tr.reduce(ops, spans)
    assert s["window_s"] == pytest.approx(0.1)
    # the while spans the window: busy throughout, but no op time
    assert s["busy_s"] == pytest.approx(0.1)
    assert not any(k.startswith("while") for k in s["op_s"])
    assert s["class_s"]["sweep"] == pytest.approx(0.010)
    assert s["class_s"]["gather_scatter"] == pytest.approx(0.025)
    assert s["op_s"]["fusion.2 f32[768,768] kLoop"] == pytest.approx(0.015)
    assert s["device_ops"][0][0] == "fusion.2 f32[768,768] kLoop"
    assert s["idle_gaps"] == []


def test_idle_gaps_by_innermost_span():
    ops, spans = _synthetic()
    s = tr.reduce(ops[:-1], spans)  # without the while
    # busy: [0,15) [20,30) [50,55) [70,80) [95,100) = 45 ms
    assert s["busy_s"] == pytest.approx(0.045)
    # idle: [15,20) [30,50) [55,60) under bench.chunk = 30 ms;
    # [60,70) [80,95) under bench.wait = 25 ms
    gaps = dict(s["idle_gaps"])
    assert gaps["bench.chunk"] == pytest.approx(0.030)
    assert gaps["bench.wait"] == pytest.approx(0.025)


def test_reduce_averages_over_devices():
    ops, spans = _synthetic()
    ops = ops[:-1]
    two = ops + [(n, s, d, "/device:TPU:1") for n, s, d, _ in ops[:1]]
    s = tr.reduce(two, spans)
    assert s["devices"] == 2
    assert s["busy_s"] == pytest.approx((0.045 + 0.010) / 2)


def test_reduce_without_window_or_ops_reads_nothing():
    ops, spans = _synthetic()
    assert tr.reduce(ops, spans[1:]) is None
    assert tr.reduce([], spans) is None


def test_from_xplane_reads_bench_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones(64)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.chunk"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    assert path is not None
    ops, spans = tr.from_xplane(path)
    names = [n for n, _, _ in spans]
    assert "bench.window" in names and "bench.chunk" in names
    # the CPU backend writes no TPU plane: no device op, no summary
    assert ops == [] and tr.reduce(ops, spans) is None
