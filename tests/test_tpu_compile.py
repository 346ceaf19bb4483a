"""Compile rehearsal: the TPU kernels of the solver's main path, compiled
for a described (not attached) v5e chip at real shapes.

Nothing runs and nothing is timed — a compile that passes is not a chip
run. What it guards is what interpret mode cannot see: Mosaic's alignment
rules, VMEM limits, and whether the program fits one chip's HBM. The
kernel functions are called directly (``ops`` would ask
``jax.default_backend()`` and take its CPU branch here).
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import schedule as sched
from repro.kernels.metric_project.fused_pass import fused_bucket_pass_pallas
from repro.kernels.metric_project.violation import (
    max_triangle_violation_pallas,
    max_triangle_violation_slab_pallas,
)

HBM_BYTES = 16 * 2**30  # one TPU v5e chip (Google Cloud, "TPU v5e")


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described v5e 2x2 (four chips), with the persistent compilation
    cache off (entries written for a described chip cannot be read back
    without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    own_log_dir = "TPU_LOG_DIR" not in os.environ
    if own_log_dir:  # otherwise the TPU compiler logs under /tmp
        os.environ["TPU_LOG_DIR"] = "disabled"
    # No skip: without the TPU compiler (libtpu, requirements-dev.txt)
    # this raises, and the rehearsal fails rather than passing unseen.
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_on)
    cc.reset_cache()
    if own_log_dir:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    """A single-device sharding on one chip of the described v5e."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2.devices[0])


def _compiled(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in c.as_text()
    m = c.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total <= HBM_BYTES, total
    return c


def _bucket(n, num_buckets=6, procs=1, which="first"):
    dims = sched.slab_dims(n, num_buckets=num_buckets, procs=procs)
    if which == "largest":
        return max(dims, key=lambda d: d[0] * d[1] * d[2])
    return dims[0]


@pytest.mark.parametrize(
    "n,batch,procs,which,delta",
    [
        (96, 1, 1, "first", False),     # solo bucket
        (128, 8, 1, "first", False),    # top serve rung, batch 8
        (1024, 1, 4, "largest", True),  # sharded mode over four chips
        (768, 1, 1, "largest", False),  # solo smoke size, largest bucket
    ],
    ids=["n96-B1", "n128-B8", "n1024-p4-delta", "n768-largest"],
)
def test_fused_pass_compiles_for_v5e(one_chip, v5e_2x2, n, batch, procs,
                                     which, delta):
    """The bucket program compiles and fits one chip. It reads and writes
    X as dense blocks (the band engine): no XLA gather or scatter, and no
    custom fusion, which is how the TPU compiler lowers them. The sharded
    case is one chip's program of four (``shard_map`` over the described
    2x2, the per-diagonal all-reduce included) at its share of the
    largest n = 1024 bucket."""
    D, T, C = _bucket(n, procs=procs, which=which)
    fn = functools.partial(fused_bucket_pass_pallas, mode="tpu",
                           interpret=False, in_place=True)
    where = one_chip
    if delta:
        mesh = Mesh(np.array(v5e_2x2.devices), ("solver",))
        assert mesh.size == procs
        where = NamedSharding(mesh, P())
        fn = jax.shard_map(
            functools.partial(fn, axis_name="solver"), mesh=mesh,
            in_specs=P(), out_specs=P(), check_vma=False,
        )
    f = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=where
    )
    tc = f(batch, D, T, C)
    args = (
        f(batch, n, n), f(batch, D, 3, T, C), f(6, D, C, dt=jnp.int32),
        tc, tc, tc, tc, f(batch, D, T, C, dt=jnp.bool_),
        f(D, T, C, dt=jnp.bool_), f(3, D, T, C, dt=jnp.int32),
    ) + ((f(D, 2, dt=jnp.int32),) if delta else ())
    text = _compiled(fn, *args).as_text()
    assert not re.findall(r" (gather|scatter)\(", text)
    assert "kind=kCustom" not in text
    if delta:
        assert re.search(r"all-reduce(-start)?\(", text)


def test_violation_kernel_compiles_for_v5e(one_chip):
    xs = jax.ShapeDtypeStruct((768, 768), jnp.float32, sharding=one_chip)
    _compiled(
        functools.partial(max_triangle_violation_pallas, interpret=False),
        xs,
    )


def test_violation_slab_kernel_compiles_for_v5e(one_chip):
    """The per-device body of the sharded probe at n=1024 over 4 chips."""
    n, p, block = 1024, 4, 8
    m = -(-n // (p * block)) * block
    f = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip
    )
    _compiled(
        functools.partial(
            max_triangle_violation_slab_pallas, interpret=False
        ),
        f(m, n), jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        f(n, n),
    )
