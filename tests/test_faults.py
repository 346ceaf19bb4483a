"""Fault injection + hardening (DESIGN.md §11): the deterministic fault
plan grammar and seeded replay, scheduler retry / bisect-isolation /
dead-letter behavior, intake validation, the solo and batched divergence
guards, checkpoint corruption detection + walk-back (including injected
save/restore faults and a kill mid-save in a subprocess), and the
device-loss degrade-and-resume chaos drill on 8 host devices."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import problems
from repro.core.parallel_dykstra import ParallelSolver, ParallelState
from repro.graphs import generators, jaccard
from repro.serve import buckets as bk, faults as flt
from repro.serve.scheduler import BatchScheduler
from repro.train import checkpoint as ckpt


def _cc_problem(n, seed=0, eps=0.05):
    adj, _ = generators.planted_partition(n, seed=seed)
    dissim, w = jaccard.signed_instance(adj)
    return problems.correlation_clustering_lp(dissim, w, eps=eps)


#: shared compiled-runner cache — the schedulers below reuse warm runners
#: across tests instead of recompiling per test.
_CACHE = bk.SolverCache()

_SOLVE = dict(tol=1e-3, max_passes=60, check_every=10)


def _scheduler(**kw):
    kw.setdefault("ladder", (12,))
    kw.setdefault("batch", 3)
    kw.setdefault("cache", _CACHE)
    kw.setdefault("sleep", lambda dt: None)
    return BatchScheduler(**{**_SOLVE, **kw})


# ------------------------------------------------------------ fault plans
def test_spec_parse_roundtrip():
    s = flt.parse_spec("device_loss@mesh:2:p=4")
    assert (s.kind, s.site, s.at, s.payload) == ("device_loss", "mesh", 2, {"p": 4})
    assert flt.parse_spec(s.spec_str()) == s
    assert flt.parse_spec("nan_poison@dispatch").at == 0
    p = flt.FaultPlan.parse("kill@ckpt_save:1:code=17; straggler@chunk:0:seconds=0.5")
    assert len(p) == 2 and p.specs[0].payload == {"code": 17}
    assert p.specs[1].payload == {"seconds": 0.5}
    assert flt.FaultPlan.parse(p.specs[0].spec_str()) + flt.FaultPlan(
        [p.specs[1]]
    ) == p
    with pytest.raises(ValueError):
        flt.parse_spec("nonsense")  # no @site
    with pytest.raises(ValueError):
        flt.parse_spec("frobnicate@dispatch:0")  # unknown kind
    with pytest.raises(ValueError):
        flt.FaultSpec("nan_poison", "mesh")  # kind/site mismatch
    with pytest.raises(ValueError):
        flt.FaultSpec("nan_poison", "chunk", at=-1)


def test_seeded_plan_replayable():
    a = flt.FaultPlan.seeded(11)
    assert a == flt.FaultPlan.seeded(11) and len(a) == 3
    assert all(s.kind != "kill" for s in a)  # excluded by default
    assert all(s.site in flt.KIND_SITES[s.kind] for s in a)
    assert any(flt.FaultPlan.seeded(s) != a for s in range(1, 8))
    only = flt.FaultPlan.seeded(3, n_faults=5, kinds=("straggler",),
                                sites=("dispatch",))
    assert all(s.kind == "straggler" and s.site == "dispatch" for s in only)
    with pytest.raises(ValueError):
        flt.FaultPlan.seeded(0, kinds=("kill",), sites=("mesh",))


def test_injector_counter_and_tag_semantics():
    inj = flt.FaultInjector("straggler@chunk:1:seconds=0")
    assert inj.poll("chunk") == []  # count 0 < at
    assert [s.kind for s in inj.poll("chunk")] == ["straggler"]
    assert inj.poll("chunk") == []  # one-shot: at == count only
    assert inj.count("chunk") == 3 and inj.count("dispatch") == 0
    assert inj.log() == [("chunk", 1, "straggler")]

    # tag specs are persistent: every matching poll once count >= at
    spec = flt.FaultSpec("dispatch_error", "dispatch", at=1,
                         payload={"tag": "bad"})
    inj2 = flt.FaultInjector(flt.FaultPlan([spec]))
    assert inj2.poll("dispatch", tags=("bad",)) == []  # count 0 < at
    assert inj2.poll("dispatch", tags=("good",)) == []  # tag absent
    assert inj2.poll("dispatch", tags=("good", "bad")) == [spec]
    assert inj2.poll("dispatch", tags=("bad",)) == [spec]  # still firing
    assert inj2.log() == [("dispatch", 2, "dispatch_error"),
                          ("dispatch", 3, "dispatch_error")]
    with pytest.raises(ValueError):
        inj2.poll("nowhere")


# ------------------------------------------------------- intake hardening
def test_validation_rejects_dead_letter():
    p = _cc_problem(8)
    d_bad = np.array(p.d)
    d_bad[0, 1] = np.nan
    bad = dataclasses.replace(p, d=d_bad)
    s = _scheduler()
    assert s.submit(bad, tag="poison") == "poison"  # submit never raises
    r = s.results()["poison"]
    assert r["route"] == "failed" and r["error"] == "validation"
    assert r["error_type"] == "ValidationError" and r["x"] is None
    st = s.stats()["faults"]
    assert st["validation_rejects"] == 1 and st["dead_letters"] == 1
    # healthy traffic through the same scheduler still lands
    s.submit(p, tag="ok")
    out = s.drain()
    assert out["ok"]["route"] == "batch" and out["ok"]["x"] is not None

    with pytest.raises(bk.ValidationError):
        bk.validate_problem(dataclasses.replace(p, eps=0.0))
    with pytest.raises(bk.ValidationError):
        bk.validate_problem(dataclasses.replace(p, w=-np.array(p.w)))
    with pytest.raises(bk.ValidationError):
        bk.validate_problem(dataclasses.replace(p, box=(1.0, 0.0)))
    bk.validate_problem(p)  # the clean instance passes


def test_duplicate_tag_raises():
    s = _scheduler(batch=4)
    s.submit(_cc_problem(8), tag="t")
    with pytest.raises(ValueError):
        s.submit(_cc_problem(8), tag="t")  # still pending
    s.drain()
    with pytest.raises(ValueError):
        s.submit(_cc_problem(8), tag="t")  # unclaimed result
    auto = [s.submit(_cc_problem(8, seed=i)) for i in range(3)]
    assert len(set(auto)) == 3  # auto tags monotone-unique
    out = s.drain()
    assert all(t in out for t in auto)


# ------------------------------------------------- retry / bisect / guard
def test_retry_heals_transient_dispatch_error():
    sleeps = []
    inj = flt.FaultInjector("dispatch_error@dispatch:0")
    s = _scheduler(faults=inj, sleep=sleeps.append, backoff_s=0.05)
    tags = [s.submit(_cc_problem(8, seed=i)) for i in range(3)]  # full batch
    out = s.results()
    assert all(out[t]["route"] == "batch" for t in tags)
    st = s.stats()["faults"]
    assert st["retries"] == 1 and st["dead_letters"] == 0
    assert st["injected_fired"] == 1
    assert sleeps == [0.05]  # one backoff, then the retry healed
    assert inj.log() == [("dispatch", 0, "dispatch_error")]


def test_bisect_isolates_persistent_poison():
    spec = flt.FaultSpec("dispatch_error", "dispatch", payload={"tag": "bad"})
    inj = flt.FaultInjector(flt.FaultPlan([spec]))
    s = _scheduler(batch=4, faults=inj, max_retries=0)
    for i in range(4):
        s.submit(_cc_problem(8, seed=i), tag="bad" if i == 1 else f"ok{i}")
    out = s.results()
    assert out["bad"]["route"] == "failed" and out["bad"]["error"] == "injected"
    assert out["bad"]["error_type"] == "InjectedFault"
    for t in ("ok0", "ok2", "ok3"):
        assert out[t]["route"] == "batch"
        assert np.isfinite(out[t]["max_violation"])
    st = s.stats()
    assert st["faults"]["dead_letters"] == 1
    assert st["instances_done"] == 3


def test_nan_poison_slot_isolated_healthy_bitwise():
    """One request poisoned past intake (NaN in its problem data): the
    per-slot divergence guard dead-letters that slot; the healthy slots
    of the SAME batch land bitwise identical to a fault-free run."""
    probs = [_cc_problem(9, seed=i) for i in range(3)]
    clean = _scheduler()
    for i, p in enumerate(probs):
        clean.submit(p, tag=f"g{i}")
    ref = clean.drain()

    spec = flt.FaultSpec("nan_poison", "dispatch", payload={"tag": "g1"})
    inj = flt.FaultInjector(flt.FaultPlan([spec]))
    s = _scheduler(faults=inj)
    for i, p in enumerate(probs):
        s.submit(p, tag=f"g{i}")
    out = s.results()
    assert out["g1"]["route"] == "failed" and out["g1"]["error"] == "diverged"
    assert out["g1"]["error_type"] == "ArithmeticError"
    for t in ("g0", "g2"):
        assert out[t]["route"] == "batch"
        np.testing.assert_array_equal(out[t]["x"], ref[t]["x"])
        assert out[t]["passes"] == ref[t]["passes"]
    assert inj.log() == [("dispatch", 0, "nan_poison")]
    assert s.stats()["faults"]["dead_letters"] == 1


def test_engine_divergence_guard_entry_poison():
    p = _cc_problem(9)
    solver = ParallelSolver(p, bucket_diagonals=3)
    inj = flt.FaultInjector("nan_poison@chunk:0")
    st, info = solver.run_until(solver.init_state(), faults=inj, **_SOLVE)
    assert info["diverged"] and not info["converged"]
    assert info["passes"] == 0  # nothing finite ever ran
    assert inj.log() == [("chunk", 0, "nan_poison")]
    # a no-op injector leaves the solve untouched
    st2, info2 = solver.run_until(
        solver.init_state(), faults=flt.FaultInjector(), **_SOLVE
    )
    assert not info2["diverged"] and info2["converged"]


class _PoisonAtPass(ParallelSolver):
    """Solver whose iterate goes NaN ON DEVICE after a fixed pass — a
    mid-while_loop divergence the guard must catch without host help."""

    POISON_AT = 7

    def _one_pass(self, st):
        st = super()._one_pass(st)
        bad = st.passes == self.POISON_AT
        x = st.x + jnp.where(bad, jnp.nan, 0.0)
        return ParallelState(x, st.f, st.yd, st.ypair, st.ybox, st.passes)


def test_engine_divergence_guard_midloop_restores_last_finite():
    p = _cc_problem(9)
    solver = _PoisonAtPass(p, bucket_diagonals=3)
    st, info = solver.run_until(
        solver.init_state(), tol=1e-9, max_passes=40, check_every=5
    )
    assert info["diverged"] and not info["converged"]
    # poison lands during chunk (5, 10]; the guard rewinds to the pass-5
    # boundary — the last finite state — instead of burning max_passes.
    assert info["passes"] == 5
    assert np.isfinite(np.asarray(st.x)).all()
    assert np.isfinite(info["max_violation"]) and np.isfinite(info["duality_gap"])


# ---------------------------------------------------- checkpoint hardening
def _tree(step):
    return {"x": np.full((4, 4), float(step)), "k": np.arange(3) + step}


def test_ckpt_truncate_detected_and_walked_back(tmp_path):
    d = str(tmp_path)
    inj = flt.FaultInjector("ckpt_truncate@ckpt_save:2:fraction=0.5")
    mgr = ckpt.CheckpointManager(d, keep=5, every=1, faults=inj)
    for s in (1, 2, 3):
        mgr.maybe_save(s, _tree(s), asynchronous=False)
    assert inj.log() == [("ckpt_save", 2, "ckpt_truncate")]
    # the truncated step COMMITTED (the fault hits after staging) but the
    # checksum manifest convicts it at restore time...
    with pytest.raises(ckpt.CorruptCheckpointError):
        ckpt.restore(d, _tree(0), step=3)
    # ...and resume_or walks back to the newest intact step.
    tree, step = mgr.resume_or(_tree(0))
    assert step == 2 and tree["x"][0, 0] == 2.0


def test_ckpt_restore_fault_walks_back(tmp_path):
    d = str(tmp_path)
    mgr = ckpt.CheckpointManager(d, every=1)
    for s in (1, 2):
        mgr.maybe_save(s, _tree(s), asynchronous=False)
    inj = flt.FaultInjector("ckpt_corrupt@ckpt_restore:0")
    mgr2 = ckpt.CheckpointManager(d, every=1, faults=inj)
    tree, step = mgr2.resume_or(_tree(0))  # newest reports corrupt
    assert step == 1 and tree["x"][0, 0] == 1.0
    assert inj.log() == [("ckpt_restore", 0, "ckpt_corrupt")]


def test_wait_pending_surfaces_background_errors(tmp_path):
    blocker = tmp_path / "blocked"
    blocker.write_text("not a directory")
    ckpt.save_async(str(blocker), 1, _tree(1))
    with pytest.raises(ckpt.CheckpointError):
        ckpt.wait_pending()
    ckpt.wait_pending()  # the failure is consumed, not sticky


def test_maybe_save_force(tmp_path):
    d = str(tmp_path)
    mgr = ckpt.CheckpointManager(d, every=100)
    assert mgr.maybe_save(7, _tree(7), asynchronous=False) is None
    mgr.maybe_save(7, _tree(7), asynchronous=False, force=True)
    assert ckpt.latest_step(d) == 7


_KILL_SCRIPT = textwrap.dedent(
    """
    import numpy as np
    from repro.serve import faults as flt
    from repro.train import checkpoint as ckpt

    d = {ckpt_dir!r}
    inj = flt.FaultInjector("kill@ckpt_save:1:code=17")
    tree = lambda s: {{"x": np.full((4, 4), float(s)), "k": np.arange(3) + s}}
    ckpt.save(d, 1, tree(1), faults=inj)
    ckpt.save(d, 2, tree(2), faults=inj)  # os._exit(17) mid-save
    print("NOT_REACHED")
    """
)


def test_kill_mid_save_previous_checkpoint_survives(tmp_path):
    """A process killed between staging and commit must leave the previous
    checkpoint restorable and only orphan debris behind."""
    d = str(tmp_path)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _KILL_SCRIPT.format(ckpt_dir=d)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 17, out.stderr[-3000:]
    assert "NOT_REACHED" not in out.stdout
    assert ckpt.latest_step(d) == 1  # step 2 never committed
    leftovers = [f for f in os.listdir(d) if ".tmp-" in f]
    assert leftovers  # the staged dir was orphaned by the kill...
    mgr = ckpt.CheckpointManager(d, every=1)  # ...and swept at startup
    assert not any(".tmp-" in f for f in os.listdir(d))
    tree, step = mgr.resume_or(_tree(0))
    assert step == 1 and tree["x"][0, 0] == 1.0


_DEAL4_SCRIPT = textwrap.dedent(
    """
    import glob, json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    from repro.launch import solve
    from repro.train import checkpoint as ckpt

    d = {ckpt_dir!r}
    argv = ["--n", "12", "--chunk", "1", "--sharded", "--ckpt-dir", d,
            "--ckpt-every", "1"]
    solve.main(argv + ["--passes", "2"])
    manifests = sorted(glob.glob(os.path.join(d, "step_*", "manifest.json")))
    assert manifests

    def tag(deal):
        for path in manifests:
            with open(path) as fh:
                m = json.load(fh)
            assert m["extra"].get("lane_deal") in ("contiguous", None)
            m["extra"].pop("lane_deal", None)
            if deal:
                m["extra"]["lane_deal"] = deal
            with open(path, "w") as fh:
                json.dump(m, fh)

    tag(None)  # as a checkpoint written before the deal was recorded
    try:
        solve.main(argv + ["--passes", "3"])
    except ckpt.CheckpointError as e:
        print("REFUSED", e)
    tag("contiguous")
    solve.main(argv + ["--passes", "3"])
    """
)


def test_sharded_resume_refuses_slabs_of_another_lane_deal(tmp_path):
    """Sharded dual slabs are saved raw, so a checkpoint records the
    layout's lane deal, and on four devices the launcher refuses to
    resume one that lacks it (written under the f mod p deal, its duals
    would land in the wrong lanes), saying why; tagged, it resumes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _DEAL4_SCRIPT.format(ckpt_dir=str(tmp_path))],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    refused = [ln for ln in out.stdout.splitlines()
               if ln.startswith("REFUSED")]
    assert len(refused) == 1 and "lane deal" in refused[0]
    assert "wrong lanes" in refused[0]
    assert out.stdout.count("resumed at pass 2") == 1


# --------------------------------------------- device-loss degrade-and-resume
_CHAOS8_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.core import problems
    from repro.core.sharded_dykstra import ShardedSolver
    from repro.launch import elastic

    assert len(jax.devices()) == 8
    n = 14
    rng = np.random.default_rng(7)
    d = np.triu(rng.uniform(0, 1, (n, n)), k=1)
    p = problems.metric_nearness_l2(d)
    mesh = Mesh(np.array(jax.devices()), ("solver",))
    solve = dict(tol=1e-4, max_passes=200, check_every=10)

    # faulted run: 6 passes on p=8, lose half the mesh, finish on p=4
    solver = ShardedSolver(p, mesh, num_buckets=3)
    state = solver.init_state()
    state, _ = solver.run_until(state, tol=1e-12, max_passes=6, check_every=3)
    solver2, state2 = elastic.degrade_solver(solver, state, 4)
    assert int(solver2.nproc) == 4
    state2, info2 = solver2.run_until(state2, **solve)
    assert info2["converged"], info2

    # reference: the same solve on the fixed 8-device mesh
    ref = ShardedSolver(p, mesh, num_buckets=3)
    rstate, rinfo = ref.run_until(ref.init_state(), **solve)
    assert rinfo["converged"], rinfo

    # same certificate: the metric-nearness QP projection is unique, so
    # the degraded run must land on the fixed-mesh solution
    assert info2["max_violation"] <= 2e-4 and rinfo["max_violation"] <= 2e-4
    np.testing.assert_allclose(
        info2["qp_objective"], rinfo["qp_objective"], rtol=1e-3
    )
    np.testing.assert_allclose(
        np.asarray(state2.x), np.asarray(rstate.x), atol=5e-3
    )
    print("CHAOS8_OK")
    """
)


@pytest.mark.multidevice
def test_device_loss_degrade_certificate_matches_8dev_subprocess():
    """Chaos drill on 8 real host devices: lose half the mesh mid-solve,
    reshard the live duals onto the survivors, finish the solve — the
    degraded run's certificate must match the fixed-mesh run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _CHAOS8_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "CHAOS8_OK" in out.stdout


# ------------------------------------------------------- end-to-end chaos
def test_end_to_end_seeded_chaos():
    """Replayable chaos through the full serve stack: a transient
    dispatch error (heals on retry), a persistently poisoned request
    (isolated to a dead-letter), seeded stragglers — every submitted
    request reaches exactly one terminal result, the scheduler never
    raises, and the healthy certificates match the fault-free run."""
    plan = (
        flt.FaultPlan.parse("dispatch_error@dispatch:0")
        + flt.FaultPlan(
            [flt.FaultSpec("nan_poison", "dispatch", payload={"tag": "g1"})]
        )
        + flt.FaultPlan.seeded(
            5, n_faults=2, kinds=("straggler",), sites=("dispatch",)
        )
    )
    probs = [_cc_problem(9, seed=i) for i in range(6)]

    clean = _scheduler()
    for i, p in enumerate(probs):
        clean.submit(p, tag=f"g{i}")
    ref = clean.drain()

    inj = flt.FaultInjector(plan)
    s = _scheduler(faults=inj)
    tags = [s.submit(p, tag=f"g{i}") for i, p in enumerate(probs)]
    out = s.drain()

    assert set(out) == set(tags)  # every request terminal
    assert out["g1"]["route"] == "failed" and out["g1"]["error"] == "diverged"
    for t in tags:
        if t == "g1":
            continue
        assert out[t]["route"] == "batch"
        np.testing.assert_array_equal(out[t]["x"], ref[t]["x"])
    st = s.stats()["faults"]
    assert st["retries"] >= 1 and st["dead_letters"] == 1
    assert st["validation_rejects"] == 0
    assert st["injected_fired"] >= 2
    assert all(site == "dispatch" for site, _, _ in inj.log())
