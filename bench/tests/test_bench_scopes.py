"""The scope reduction (``bench/scopes.py``) on synthetic traces, and the
readers of the program's own spans and counters."""

import gzip
import json

import pytest

from bench import harness, scopes

MS = 1_000_000  # ns
GATHER = ("%fusion.3 = f32[192]{0:T(256)S(1)} fusion(f32[768,768]{1,0:T(8,128)}"
          " %p, s32[192]{0:T(256)S(1)} %i), kind=kCustom, calls=%fc.3")
# a scatter the compiler rebuilt: no scope of its own, its fusion wraps one
SCATTER = ("%fusion.7 = f32[589824]{0:T(1024)S(1)} fusion(f32[589824]{0:T("
           "1024)S(1)} %a, s32[147456]{0:T(1024)S(1)} %b, f32[147456]{0:T("
           "1024)S(1)} %c), kind=kCustom, calls=%fused_computation.7.clone")
PAIR = "%fusion.9 = f32[768,768]{1,0} fusion(f32[768,768]{1,0} %x), kind=kLoop"
OBJ = "%reduce.1 = f32[] reduce(f32[768,768]{1,0} %x, f32[] %z)"
WHILE = ("%while.1 = (f32[768,768]{1,0:T(8,128)}, s32[]) while((f32[768,768]"
         "{1,0:T(8,128)}, s32[]) %t), condition=%c, body=%b")
PATH = "jit(traced)/while/body/while/body/closed_call/repro.bucket/"
TF_OP = {GATHER: PATH + "repro.bucket.gather/vmap()/gather:",
         SCATTER: "jit(traced)/while/body/while/body/closed_call:",
         PAIR: "jit(traced)/while/body/repro.pass.pair/max:",
         WHILE: "jit(traced)/while:"}
HLO = """\
%fused_computation.7.clone (param_0: f32[589824], param_1: s32[147456]) -> f32[589824] {
  %param_0 = f32[589824]{0} parameter(0)
  %reshape.1 = f32[256,64]{1,0} reshape(%param_2), metadata={op_name="jit(traced)/while/repro.bucket/repro.bucket.scatter/vmap(jit(_where))/select_n" stack_frame_id=95}
  ROOT %scatter.1 = f32[589824]{0} scatter(%param_0, %t, %reshape.1), unique_indices=true, to_apply=%region
}

%fused_computation.8 (param_0: f32[8]) -> f32[8] {
  %a.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(traced)/repro.chunk.probe/add"}
  ROOT %m.1 = f32[8]{0} multiply(%a.1, %a.1), metadata={op_name="jit(traced)/repro.chunk.objective/mul"}
}

ENTRY %main.1 (x: f32[589824]) -> f32[589824] {
  %fusion.7 = f32[589824]{0:T(1024)S(1)} fusion(%a, %b, %c), kind=kCustom, calls=%fused_computation.7.clone, metadata={op_name="jit(traced)/while/body/closed_call"}
  %fusion.8 = f32[8]{0} fusion(%d), kind=kLoop, calls=%fused_computation.8
  ROOT %fusion.3 = f32[192]{0:T(256)S(1)} fusion(%p, %i), kind=kCustom, calls=%fc.3, metadata={op_name="jit(traced)/repro.bucket/repro.bucket.gather/gather"}
}
"""


@pytest.mark.parametrize("op_name,scope", [
    (PATH + "repro.bucket.gather/vmap()/gather:", "repro.bucket.gather"),
    (PATH + "add:", "repro.bucket"),
    ("jit(traced)/while/body/repro.chunk.probe:", "repro.chunk.probe"),
    ("jit(obj)/reduce_sum:", None),
    (None, None),
])
def test_scope_of_is_the_deepest_repro_component(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_hlo_scopes_maps_a_fusion_to_the_one_scope_it_wraps():
    # fusion.7 wraps one scope; fusion.8 wraps two (ambiguous, unmapped);
    # fusion.3 carries its own scope and needs no map
    assert scopes.hlo_scopes(HLO) == {"fusion.7": "repro.bucket.scatter"}


def _synthetic():
    spans = [("bench.window", 0, 100 * MS), ("bench.chunk", 0, 100 * MS),
             ("repro.run_until", 1 * MS, 98 * MS),
             ("repro.run_until.wait", 2 * MS, 50 * MS),
             ("repro.run_until.harvest", 60 * MS, 30 * MS)]
    ops = [
        (GATHER, 0, 20 * MS, "/device:TPU:0"),
        (SCATTER, 20 * MS, 30 * MS, "/device:TPU:0"),
        (PAIR, 55 * MS, 5 * MS, "/device:TPU:0"),
        (OBJ, 70 * MS, 4 * MS, "/device:TPU:0"),
        (WHILE, 0, 60 * MS, "/device:TPU:0"),  # control flow: busy only
    ]
    return ops, spans


def test_reduce_classifies_ops_by_scope_and_through_the_hlo():
    ops, spans = _synthetic()
    s = scopes.reduce(ops, spans, TF_OP, scopes.hlo_scopes(HLO))
    assert s["scope_s"] == pytest.approx({
        "repro.bucket.gather": 0.020, "repro.bucket.scatter": 0.030,
        "repro.pass.pair": 0.005, scopes.UNSCOPED: 0.004})
    labels = dict(s["device_ops"])
    assert labels["repro.bucket.scatter/fusion.7 f32[589824] kCustom"] == (
        pytest.approx(0.030))
    assert labels[scopes.UNSCOPED + "/reduce.1 f32[]"] == pytest.approx(0.004)
    # without the HLO map the rebuilt scatter is claimed by no scope
    bare = scopes.reduce(ops, spans, TF_OP, {})
    assert bare["scope_s"][scopes.UNSCOPED] == pytest.approx(0.034)


def test_reduce_puts_gaps_under_repro_spans_inside_bench_chunk():
    ops, spans = _synthetic()
    s = scopes.reduce(ops, spans, TF_OP, {})
    # busy [0,60) [70,74); idle [60,70) and [74,90) under the harvest,
    # [90,99) under repro.run_until, [99,100) under bench.chunk
    assert s["busy_s"] == pytest.approx(0.064)
    assert dict(s["idle_gaps"]) == pytest.approx({
        "repro.run_until.harvest": 0.026, "repro.run_until": 0.009,
        "bench.chunk": 0.001})


def test_reduce_without_window_reads_nothing():
    ops, spans = _synthetic()
    assert scopes.reduce(ops, spans[1:], TF_OP, {}) is None


def test_tf_ops_joins_by_instruction_text(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    events = [{"ph": "X", "name": "fusion.3",
               "args": {"long_name": GATHER, "tf_op": TF_OP[GATHER]}},
              {"ph": "X", "name": "copy.1", "args": {"long_name": "%copy.1"}},
              {"ph": "M", "name": "process_name", "args": {"name": "x"}}]
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    assert scopes.tf_ops(str(tmp_path)) == {GATHER: TF_OP[GATHER]}


READERS = ("host_syncs", "stage_build_s", "stage_put_s", "program_load_s")


def test_registry_readers_read_the_solver_spans_and_counters():
    """After a constructor and two ``run_until`` calls the four readers
    find their spans and counters; with an empty table they read nothing."""
    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.core import problems
    from repro.core.parallel_dykstra import ParallelSolver

    obs.reset()
    assert all(harness.reader(m)({}) is None for m in READERS)
    d = np.triu(np.random.default_rng(0).uniform(0, 1, (9, 9)), 1)
    prob = problems.correlation_clustering_lp((d > 0.5) * 1.0, eps=0.05)
    solver = ParallelSolver(prob, dtype=jnp.float32, use_kernel=True)
    st, _ = solver.run_until(tol=0.0, max_passes=2, check_every=2)
    solver.run_until(st, tol=0.0, max_passes=4, check_every=2)
    got = {m: harness.reader(m)({}) for m in READERS}
    snap = obs.snapshot()
    assert got["host_syncs"] == 10
    assert got["stage_build_s"] == pytest.approx(
        snap["repro.stage.layout"]["s"] + snap["repro.stage.slabs"]["s"])
    assert got["stage_put_s"] == snap["repro.stage.put"]["s"] > 0
    launch = snap["repro.run_until.launch"]
    assert got["program_load_s"] == launch["first_s"] > launch["s"] / 2
