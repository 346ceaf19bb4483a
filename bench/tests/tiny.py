"""Cells cut to a size a CPU test holds, and a driver that runs one through
the harness with the look for a chip skipped."""

import contextlib
import io
import json

from bench import harness

SEED = 2**33 + 17


def tiny_config(man, cell):
    cfg = harness.load_json(next(
        f"{harness.ROOT}/{c['file']}" for c in man["configs"]
        if c["name"] == cell["config"]))
    cfg["graph"]["n"] = 16
    return cfg


def cpu_device(chips: int) -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def run_cell(monkeypatch, workload: str, seconds: float = 2.0) -> dict:
    """The result line of one run of ``workload`` at the tiny size."""
    from bench import run

    monkeypatch.setattr(harness, "config_of", tiny_config)
    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "check_devices", cpu_device)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(SEED),
                       "--seconds", str(seconds), "--trace", "0"])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
