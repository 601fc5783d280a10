"""The main path's programs compiled for a described TPU v5e (no chip
needed): the unified batch program and its weighted twin for one chip,
and the halo batch program on a (2, 2) mesh.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import engine
from repro.core.sharded import make_sharded_apply

N = 1024          # vertices
CAP = 4096        # slot-table capacity
LANES = 256       # padded batch lanes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


def _batch_program_args(table, vertex, rep):
    s = jax.ShapeDtypeStruct
    state = [s((CAP,), jnp.int32, sharding=table),
             s((CAP,), jnp.int32, sharding=table),
             s((CAP,), jnp.bool_, sharding=table),
             s((N,), jnp.int32, sharding=vertex),
             s((N,), jnp.int64, sharding=vertex),
             s((), jnp.int32, sharding=rep)]
    lanes = [s((LANES,), jnp.int32, sharding=rep),
             s((LANES,), jnp.int32, sharding=rep),
             s((LANES,), jnp.bool_, sharding=rep)]
    return state + lanes + lanes  # inserts, then removals


def test_unified_batch_program_compiles_for_one_v5e_chip(topo):
    one = SingleDeviceSharding(topo.devices[0])
    compiled = engine.apply_batch.lower(
        *_batch_program_args(one, one, one),
        n=N, n_levels=N + 2, active_cap=CAP // 2,
    ).compile()
    mem = compiled.memory_analysis()
    # the six state buffers are donated: updated in place on the chip
    assert mem.alias_size_in_bytes > 0
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 << 30


def test_weighted_batch_program_compiles_for_one_v5e_chip(topo):
    one = SingleDeviceSharding(topo.devices[0])
    a = _batch_program_args(one, one, one)
    w = jax.ShapeDtypeStruct((CAP,), jnp.int32, sharding=one)
    ins_w = jax.ShapeDtypeStruct((LANES,), jnp.int32, sharding=one)
    # (src, dst, valid, w, core, label, n_edges, ins_u, ins_v, ins_w,
    # ins_ok, rm_u, rm_v, rm_ok)
    args = a[:3] + [w] + a[3:8] + [ins_w] + a[8:]
    compiled = engine.apply_batch_weighted.lower(
        *args, n=N, n_levels=N + 2, active_cap=CAP // 2,
    ).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0


def test_halo_batch_program_compiles_on_v5e_2x2_mesh(topo):
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2), ("edge", "data"))
    fn = make_sharded_apply(mesh, N, N + 2, axis="data",
                            local_active=CAP // 8, vertex_sharding="halo")
    compiled = fn.lower(*_batch_program_args(
        NamedSharding(mesh, P(("edge", "data"))),
        NamedSharding(mesh, P("data")),
        NamedSharding(mesh, P()),
    )).compile()
    text = compiled.as_text()
    assert "all-reduce" in text or "all-gather" in text  # a real mesh program

