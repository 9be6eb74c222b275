"""The serving path's programs compile for a described TPU v5e.

Nothing runs: JAX's TPU compiler, which is installed with JAX, compiles
for a v5e:2x2 topology that is described, not attached, and raises what
the chip's compiler would raise (an unsupported op, a program that does
not fit HBM, a collective that cannot be partitioned).  Results and times
need a chip (``chip_smoke.py``).

The topology is described inside a module-scoped fixture only, never at
import: one process at a time may load the TPU library, and under
pytest-xdist every worker imports this file.  The persistent compilation
cache is off around these compiles — an entry written for a described
chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import PIConfig, distributed as dist, index as pi
from repro.pipeline.dispatcher import _step_single
from repro.pipeline.ranges import _sharded_range_executor, execute_ranges

BATCH = 8192
ONE_CHIP = PIConfig(capacity=1 << 16, pending_capacity=1 << 14, fanout=8,
                    backend="xla")
# the four-chip programs are checked for partitioning, not size
PER_SHARD = PIConfig(capacity=1 << 12, pending_capacity=1 << 12, fanout=8,
                     backend="xla")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices[:4]), ("data",))


def index_shapes(cfg, sharding, n_shards=None):
    """Abstract PIIndex leaves (stacked over ``n_shards`` if given)."""
    lead = () if n_shards is None else (n_shards,)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(lead + x.shape, x.dtype,
                                       sharding=sharding),
        jax.eval_shape(lambda: pi.empty(cfg)))


def lanes(sharding):
    return jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=sharding)


def test_step_single_compiles(one_chip):
    compiled = _step_single.lower(index_shapes(ONE_CHIP, one_chip),
                                  lanes(one_chip), lanes(one_chip),
                                  lanes(one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()  # the xla backend


def test_execute_ranges_compiles(one_chip):
    execute_ranges.lower(index_shapes(ONE_CHIP, one_chip), lanes(one_chip),
                         lanes(one_chip), lanes(one_chip), 1024).compile()


def test_rebuild_compiles(one_chip):
    compiled = pi.rebuild.lower(index_shapes(ONE_CHIP, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 4 * ONE_CHIP.capacity


def test_sharded_step_compiles(mesh):
    """Routing all_to_all + per-shard execute, then per-shard rebuild."""
    by_shard = NamedSharding(mesh, P("data"))
    shards = index_shapes(PER_SHARD, by_shard, 4)
    run, _ = dist.make_sharded_executor(mesh, PER_SHARD, BATCH // 4)
    compiled = run.lower(
        shards,
        jax.ShapeDtypeStruct((5,), jnp.int32,
                             sharding=NamedSharding(mesh, P())),
        lanes(by_shard), lanes(by_shard), lanes(by_shard)).compile()
    assert "all-to-all" in compiled.as_text()
    dist._sharded_maybe_rebuild(mesh).lower(shards).compile()


def test_sharded_ranges_compile(mesh):
    replicated = NamedSharding(mesh, P())
    fn = _sharded_range_executor(mesh, 1024)
    compiled = fn.lower(
        index_shapes(PER_SHARD, NamedSharding(mesh, P("data")), 4),
        jax.ShapeDtypeStruct((5,), jnp.int32, sharding=replicated),
        lanes(replicated), lanes(replicated), lanes(replicated)).compile()
    assert "all-reduce" in compiled.as_text()
