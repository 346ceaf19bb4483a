"""BENCHMARK.json against the files the harness finds by name."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MAN = harness.manifest()


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert MAN["paths"] == ["bench"]
    assert 1 <= MAN["run_seconds"] <= 51


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    cfg = harness.config_of(MAN, cell)
    assert cfg["name"] == cell["config"]
    harness.traffic_of(cell)
    assert hasattr(harness.entry_of(cfg), "run")
    assert cell["chips"] in (1, 4)
    reported = {m["name"] for m in harness.metrics_for(MAN, cell,
                                                       "end_to_end")}
    assert "setup_s" in reported and len(reported) >= 2
    assert harness.metrics_for(MAN, cell, "per_layer")


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_reader_and_moves(metric):
    assert callable(harness.reader(metric["name"]))
    moved = {m["name"]: m for m in MAN["end_to_end"]}[metric["moves"]]
    cells = metric.get("workloads", [w["name"] for w in MAN["workloads"]])
    for cell in cells:
        assert cell in moved.get("workloads", [cell])


def test_names_units_and_keys():
    names = [c["name"] for c in MAN["configs"]]
    names += [w["name"] for w in MAN["workloads"]]
    names += [w["traffic"] for w in MAN["workloads"]]
    metrics = MAN["end_to_end"] + MAN["per_layer"]
    names += [m["name"] for m in metrics]
    names += [k for c in MAN["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for kind in ("configs", "workloads"):
        assert len({c["name"] for c in MAN[kind]}) == len(MAN[kind])
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    layers = {m["layer"] for m in MAN["per_layer"]}
    assert all(0 < len(la) <= 200 and "\n" not in la for la in layers)
    for c in MAN["configs"]:
        assert c["file"].startswith("bench/configs/")
    for e in MAN["configs"] + MAN["workloads"]:
        assert 0 < len(e["why"]) <= 200 and "\n" not in e["why"]
    assert len(json.dumps(MAN)) < 64 * 1024


def test_unknown_device_kind_raises():
    with pytest.raises(harness.BenchError):
        harness.peaks_for("no such chip")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", MAN["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "no TPU" in proc.stderr
