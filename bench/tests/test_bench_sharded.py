"""The sharded cell on the CPU at a tiny size, on four host devices in a
subprocess: a sound run is correct, and a merge that drops one device's
delta is not. Beside it, the entry's own pieces: the merge's interval
arithmetic on hand-made op tuples, the slab reader against the dense
conversion, and the new readers on missing input."""

import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, control, harness
from bench.entries import sharded
from bench.tests import tiny

CELL = "cclp-ba-1024.sharded4"

_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
    import jax, pytest
    from bench.tests import tiny

    assert len(jax.devices()) == 4
    with pytest.MonkeyPatch.context() as mp:
        print("SOUND", json.dumps(tiny.run_cell(mp, {cell!r})), flush=True)
    psum = jax.lax.psum

    def dropping(x, axis):
        # device 1's delta never reaches the sum
        keep = jax.lax.axis_index(axis) != 1
        return psum(jax.numpy.where(keep, x, 0), axis)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.lax, "psum", dropping)
        print("DROP", json.dumps(tiny.run_cell(mp, {cell!r})), flush=True)
    """
)


@pytest.fixture(scope="module")
def lines():
    """The result lines of a sound run and of a run whose merge drops
    device 1's delta, each at n = 16 over four host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c",
         _SCRIPT.format(root=harness.ROOT, cell=CELL)],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = {}
    for line in proc.stdout.splitlines():
        tag, _, body = line.partition(" ")
        if tag in ("SOUND", "DROP"):
            out[tag] = json.loads(body)
    return out


def test_sound_run_is_correct(lines):
    line = lines["SOUND"]
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"pass_ms", "hbm_peak_gb", "setup_s"}
    assert list(line)[-1] == "checks"


def test_merge_that_drops_a_device_delta_fails(lines):
    line = lines["DROP"]
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_control_is_not_correct():
    man = harness.manifest()
    cell = harness.cell_of(man, CELL)
    cfg = tiny.tiny_config(man, cell)
    for seed in (1, 2, 3):
        values = control.control_values(cfg, harness.traffic_of(cell), seed,
                                        jnp.bfloat16)
        assert not check.verdict(values, cfg["check"]["limits"])[0], values


MERGE = "%psum.60 = f32[64,64]{1,0} all-reduce(%fusion.3), channel_id=1"
PROBE = "%all-reduce.19 = (f32[], f32[]) all-reduce(%a, %b), channel_id=3"
SWEEP = ("%metric_sweep.7 = f32[1,64,128]{2,1,0} custom-call(%x), "
         "custom_call_target=\"tpu_custom_call\"")
LOOP = "%while.2 = (f32[64,64]{1,0}) while(%t), condition=%c, body=%b"
WINDOW = [("bench.window", 0, 1000)]


@pytest.mark.parametrize("other,exposed_ns", [
    ((100, 200), 0),     # the sweep covers the whole all-reduce
    ((400, 100), 100),   # nothing covers it
    ((150, 100), 50),    # the sweep covers its second half
], ids=["full", "none", "partial"])
def test_merge_exposure_interval_arithmetic(other, exposed_ns):
    dev = "/device:TPU:0"
    ops = [(MERGE, 100, 100, dev), (SWEEP, other[0], other[1], dev),
           (PROBE, 600, 100, dev), (LOOP, 0, 1000, dev)]
    total, exposed = sharded.merge_seconds(ops, WINDOW)
    assert total == pytest.approx(100e-9)
    assert exposed == pytest.approx(exposed_ns * 1e-9)


def test_merge_is_averaged_over_devices_and_clipped_to_the_window():
    ops = [(MERGE, 900, 200, "/device:TPU:0"),
           (MERGE, 100, 100, "/device:TPU:1"),
           (SWEEP, 100, 50, "/device:TPU:1")]
    total, exposed = sharded.merge_seconds(ops, WINDOW)
    assert total == pytest.approx((100 + 100) / 2 * 1e-9)
    assert exposed == pytest.approx((100 + 50) / 2 * 1e-9)
    assert sharded.merge_seconds(ops, []) is None
    assert sharded.merge_seconds([(SWEEP, 0, 10, "/device:TPU:0")],
                                 WINDOW) is None


def test_is_merge_reads_opcode_and_result_shape():
    assert sharded.is_merge(MERGE)
    assert sharded.is_merge("%all-reduce.2 = f32[8,8,3]{2,1,0} "
                            "all-reduce(%d)")
    assert not sharded.is_merge(PROBE)
    assert not sharded.is_merge("%psum.1 = f32[64]{0} all-reduce(%d)")
    assert not sharded.is_merge(SWEEP)
    assert not sharded.is_merge("all-reduce")


@pytest.mark.parametrize("n,buckets,procs", [(9, 2, 4), (16, 6, 4),
                                             (13, 3, 1)])
def test_slab_reader_matches_the_dense_conversion(n, buckets, procs):
    from bench import reference
    from repro.core import schedule

    layout = schedule.build_layout(n, num_buckets=buckets, procs=procs)
    rng = np.random.default_rng(n)
    slabs = [rng.standard_normal(bl.slab_shape).astype(np.float32)
             for bl in layout.buckets]
    dense = schedule.duals_to_dense(layout, slabs)
    tri = sharded.slab_duals(layout, slabs)
    for d in range(len(reference.diagonals(n))):
        t, c, i, j, k = reference.triangle_cells(n, d)
        want = np.stack([dense[i, j, k], dense[i, k, j], dense[j, k, i]])
        np.testing.assert_array_equal(tri(d, t, c, i, j, k), want)


@pytest.mark.parametrize("metric", ["merge_ms", "merge_exposed_ms",
                                    "pass_roofline.sharded4",
                                    "device_idle_pct.sharded4"])
def test_new_readers_return_none_without_input(metric):
    ctx = {"trace": {"busy_s": 0.0, "window_s": 0.0}, "counters": {},
           "peaks": {}, "cell": {"chips": 4}}
    assert harness.reader(metric)(ctx) is None
