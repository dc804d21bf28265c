"""Compile the Pallas kernels for a described TPU v5e chip.

Interpret-mode tests cannot see what the chip's compiler (Mosaic) refuses:
unlowered primitives, block shapes that do not match the chip's tiling,
too much VMEM. These tests lower and compile every kernel for device 0 of
a described ``v5e:2x2`` topology, at the default tile sizes and a 2^20-edge
window, with ``JAX_PLATFORMS=cpu``. Nothing runs; a compile that passes is
not a chip run.

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, and several test workers import
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import SchedulerConfig
from repro.core.edge_store import EdgeStore
from repro.core.temporal_index import TemporalIndex
from repro.kernels.fused_step import fused_walk_step
from repro.kernels.walk_step import walk_step_tiled
from repro.kernels.weight_prefix import weight_prefix

EDGES = 1 << 20
NODES = 1 << 16
WALKS = 1 << 14
CFG = SchedulerConfig()


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _index_spec(sharding) -> TemporalIndex:
    i32 = lambda *shape: _spec(sharding, shape)             # noqa: E731
    f32 = lambda *shape: _spec(sharding, shape, jnp.float32)  # noqa: E731
    store = EdgeStore(src=i32(EDGES), dst=i32(EDGES), ts=i32(EDGES),
                      num_edges=i32())
    return TemporalIndex(
        store=store, ns_order=i32(EDGES), ns_src=i32(EDGES),
        ns_dst=i32(EDGES), ns_ts=i32(EDGES), node_starts=i32(NODES + 2),
        node_group_counts=i32(NODES), pexp=f32(EDGES + 1),
        plin=f32(EDGES + 1), node_tref=i32(NODES), node_tbase=i32(NODES),
        pexp_store=f32(EDGES + 1), plin_store=f32(EDGES + 1),
        adj_order=i32(EDGES), adj_dst=i32(EDGES))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", ["index", "weight"])
def test_fused_walk_step_compiles(one_chip, mode):
    def step(index, node, time, code, u):
        return fused_walk_step(index, node, time, code, u, mode, CFG,
                               interpret=False)

    walks = _spec(one_chip, (WALKS,))
    compiled = jax.jit(step).lower(
        _index_spec(one_chip), walks, walks, walks,
        _spec(one_chip, (WALKS,), jnp.float32)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("mode,bias", [("index", "uniform"),
                                       ("index", "linear"),
                                       ("index", "exponential"),
                                       ("weight", "exponential"),
                                       ("weight", "linear")])
def test_walk_step_tiled_compiles(one_chip, mode, bias):
    def step(*args):
        return walk_step_tiled(*args, mode=mode, bias=bias,
                               tile_walks=CFG.tile_walks,
                               tile_edges=CFG.tile_edges, interpret=False)

    edges = _spec(one_chip, (EDGES,))
    prefix = _spec(one_chip, (EDGES,), jnp.float32)
    walks = _spec(one_chip, (WALKS,))
    tiles = _spec(one_chip, (WALKS // CFG.tile_walks,))
    compiled = jax.jit(step).lower(
        edges, edges, prefix, prefix, tiles, walks, walks, walks,
        _spec(one_chip, (WALKS,), jnp.float32), walks).compile()
    _assert_kernel(compiled)


def test_weight_prefix_compiles(one_chip):
    compiled = jax.jit(
        lambda dt, valid: weight_prefix(dt, valid, interpret=False)).lower(
        _spec(one_chip, (EDGES,), jnp.float32),
        _spec(one_chip, (EDGES,), jnp.bool_)).compile()
    _assert_kernel(compiled)
