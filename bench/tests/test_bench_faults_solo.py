"""The solo cell's check on the CPU at a tiny size: a sound run is correct;
the control and each fault the cell can have come out not correct."""

import dataclasses

import jax.numpy as jnp
import numpy as np

from bench import check, control, harness
from bench.tests import tiny

CELL = "cclp-ba-768.passes"


def test_sound_run_is_correct(monkeypatch):
    line = tiny.run_cell(monkeypatch, CELL)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"pass_ms", "hbm_peak_gb", "setup_s"}


def test_control_is_not_correct():
    man = harness.manifest()
    cell = harness.cell_of(man, CELL)
    cfg = tiny.tiny_config(man, cell)
    for seed in (1, 2, 3):
        values = control.control_values(cfg, harness.traffic_of(cell), seed,
                                        jnp.bfloat16)
        assert not check.verdict(values, cfg["check"]["limits"])[0], values


def test_step_that_leaves_the_state_unchanged_fails(monkeypatch):
    from repro.core.parallel_dykstra import ParallelSolver

    monkeypatch.setattr(
        ParallelSolver, "_one_pass",
        lambda self, st: dataclasses.replace(st, passes=st.passes + 1))
    assert not tiny.run_cell(monkeypatch, CELL)["correct"]


def test_answer_altered_where_produced_fails(monkeypatch):
    from repro.core.parallel_dykstra import ParallelSolver

    orig = ParallelSolver.run_until

    def altered(self, *a, **k):
        st, info = orig(self, *a, **k)
        bump = np.zeros(st.x.shape, np.float32)
        bump[0, 1] = 0.1
        return dataclasses.replace(st, x=st.x + bump), info

    monkeypatch.setattr(ParallelSolver, "run_until", altered)
    assert not tiny.run_cell(monkeypatch, CELL)["correct"]
