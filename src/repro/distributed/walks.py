"""Walk-axis sharding: device-parallel walk generation over a replicated
index (DESIGN.md §10).

Complements ``core/distributed.py``: that module range-partitions the *edge
store* across devices and migrates walks between owners every hop — the
mechanism for windows that exceed one chip's HBM. This module is the other
regime: the window fits on-chip, throughput is the constraint, so the
dual index is **replicated** and the *walk axis* is sharded with
``shard_map`` — walks are embarrassingly parallel, so a hop involves zero
cross-device communication and scaling is linear in devices.

RNG: shard ``s`` folds ``s`` into the key and generates its walks exactly
like a single-device ``generate_walks`` over ``W/D`` walks. Results are
deterministic for a fixed (key, device count); a D-device run is not
bit-identical to a 1-device run (``core/distributed.py`` pays a per-walk
``fold_in`` every hop for that stronger property). ``all_nodes`` starts
keep their global assignment via ``walk_offset``: shard s's walk w starts
where global walk ``s·W/D + w`` would.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import SamplerConfig, SchedulerConfig, WalkConfig
from repro.core.walk_engine import WalkResult, _generate_walks_impl

WALK_AXIS = "walks"


def walk_mesh(devices=None, axis_name: str = WALK_AXIS) -> Mesh:
    """1-D mesh over all (or the given) devices for walk-axis sharding."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devs, (axis_name,))


@functools.lru_cache(maxsize=None)
def _sharded_walk_fn(mesh: Mesh, axis_name: str, wcfg: WalkConfig,
                     scfg: SamplerConfig, sched_cfg: SchedulerConfig):
    D = mesh.shape[axis_name]
    if wcfg.num_walks % D:
        raise ValueError(f"num_walks {wcfg.num_walks} not divisible by "
                         f"{D} devices on axis {axis_name!r}")
    wd = dataclasses.replace(wcfg, num_walks=wcfg.num_walks // D)

    def shard_fn(index, key):
        s = jax.lax.axis_index(axis_name)
        res = _generate_walks_impl(
            index, jax.random.fold_in(key, s), wd, scfg, sched_cfg,
            walk_offset=s * wd.num_walks)
        return res.nodes, res.times, res.lengths

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(), P()),              # index + key replicated
                   out_specs=(P(axis_name), P(axis_name), P(axis_name)),
                   check_vma=False)
    return jax.jit(fn)


def generate_walks_sharded(index, key: jax.Array, wcfg: WalkConfig,
                           scfg: SamplerConfig, sched_cfg: SchedulerConfig,
                           *, mesh: Optional[Mesh] = None,
                           axis_name: str = WALK_AXIS) -> WalkResult:
    """Generate ``wcfg.num_walks`` walks sharded over the mesh's devices.

    Drop-in for ``generate_walks`` (stats collection excepted): each device
    runs the full scheduler path (fullwalk/grouped/tiled, bucket or lexsort
    regroup) on its ``W/D`` walk slice against the replicated index; the
    result arrays come back sharded along the walk axis. Defaults to a
    fresh 1-D mesh over every visible device.
    """
    if mesh is None:
        mesh = walk_mesh(axis_name=axis_name)
    fn = _sharded_walk_fn(mesh, axis_name, wcfg, scfg, sched_cfg)
    nodes, times, lengths = fn(index, key)
    return WalkResult(nodes=nodes, times=times, lengths=lengths, stats=None)
