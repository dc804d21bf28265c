"""Dispatch plane (paper §2.4.4, Fig. 5) — TPU adaptation.

The paper partitions per-step work into five terminal kernels keyed on
(W = walks co-located at a node, G = the node's timestamp-group count).
On TPU there are no per-task kernel launches; the same two axes instead
select between three execution layouts (SchedulerConfig.path) and, inside
the tiled path, whether a task's metadata slice fits a VMEM tile (the smem
analog) or must fall back to global-memory-style gathers.

This module computes:
* per-step tier statistics (the paper's Table 3 / launch-count analog),
* the modeled HBM traffic of the fullwalk vs grouped layouts (the paper's
  structural metric "global-memory traffic amortized across co-located
  walks" — measurable on real TPU, modeled here on CPU),
* fixed-shape task tables for the Pallas tiled kernel.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import SchedulerConfig
from repro.core.temporal_index import TemporalIndex

# stats vector layout (per step)
STAT_ALIVE = 0            # alive walks
STAT_UNIQUE_NODES = 1     # distinct nodes carrying walks
STAT_SOLO = 2             # tasks dispatched solo (W <= solo_threshold)
STAT_GROUP_SMEM = 3       # grouped tasks whose G fits the VMEM tile
STAT_GROUP_GLOBAL = 4     # grouped tasks needing global fallback
STAT_MEGA = 5             # mega-hub sub-tasks (ceil(W / max_task_walks))
STAT_BYTES_FULLWALK = 6   # modeled HBM bytes, per-walk layout
STAT_BYTES_GROUPED = 7    # modeled HBM bytes, grouped layout
STAT_FUSED_SMALL = 8      # fused tier-S lanes (span fits the staged window)
STAT_FUSED_BIG = 9        # fused tier-L lanes (edge-window sweep)
STAT_FUSED_BLOCKS = 10    # modeled tier-L swept edge blocks
NUM_STATS = 11

_BYTES_PER_EDGE_ROW = 8   # (dst, ts) int32 pair
_BYTES_PER_OFFSET = 4


def dispatch_stats(index: TemporalIndex, cur_node: jax.Array,
                   alive: jax.Array, cfg: SchedulerConfig) -> jax.Array:
    """Per-step dispatch-plane statistics (paper Alg. 1 lines 4-9 analog)."""
    nc = index.node_capacity
    node = jnp.clip(cur_node, 0, nc - 1)
    w_per_node = jax.ops.segment_sum(alive.astype(jnp.int32), node,
                                     num_segments=nc)
    occupied = w_per_node > 0
    g = index.node_group_counts

    solo = occupied & (w_per_node <= cfg.solo_threshold)
    grouped = occupied & (w_per_node > cfg.solo_threshold) \
        & (w_per_node <= cfg.max_task_walks)
    mega_tasks = jnp.where(
        occupied & (w_per_node > cfg.max_task_walks),
        -(-w_per_node // cfg.max_task_walks), 0)
    fits_tile = g <= cfg.tile_edges

    deg = index.node_starts[1:nc + 1] - index.node_starts[:nc]
    # modeled bytes: the search touches ~log2(deg) edge rows + 2 offsets.
    probes = jnp.ceil(jnp.log2(jnp.maximum(deg, 2).astype(jnp.float32)))
    per_lookup = probes * _BYTES_PER_EDGE_ROW + 2 * _BYTES_PER_OFFSET
    wf = w_per_node.astype(jnp.float32)
    # fullwalk: every walk pays the lookup + one edge-row read.
    bytes_full = jnp.sum(wf * (per_lookup + _BYTES_PER_EDGE_ROW))
    # grouped: the lookup is paid once per occupied node (time-dedup is
    # strictly better; this is the conservative node-level bound), each walk
    # still pays its sampled edge-row read.
    bytes_grp = jnp.sum(jnp.where(occupied, per_lookup, 0.0)
                        + wf * _BYTES_PER_EDGE_ROW)

    # fused-kernel tier split (kernels/fused_step.py): a lane whose whole
    # region span fits the staged 2·tile_edges window is tier S, else tier
    # L. This is the idealized per-lane rule — the kernel's split is
    # tile-anchored and can only demote additional lanes — and the block
    # count models one sweep block per tile_edges of span plus the
    # alignment slop, per tier-L lane (per-tile dedup not modeled).
    fused_small = alive & (deg[node] <= 2 * cfg.tile_edges)
    fused_big = alive & (deg[node] > 2 * cfg.tile_edges)
    fused_blocks = jnp.where(fused_big,
                             -(-deg[node] // cfg.tile_edges) + 1, 0)

    return jnp.stack([
        jnp.sum(alive.astype(jnp.float32)),
        jnp.sum(occupied.astype(jnp.float32)),
        jnp.sum(solo.astype(jnp.float32)),
        jnp.sum((grouped & fits_tile).astype(jnp.float32)),
        jnp.sum((grouped & ~fits_tile).astype(jnp.float32)),
        jnp.sum(mega_tasks.astype(jnp.float32)),
        bytes_full,
        bytes_grp,
        jnp.sum(fused_small.astype(jnp.float32)),
        jnp.sum(fused_big.astype(jnp.float32)),
        jnp.sum(fused_blocks.astype(jnp.float32)),
    ])


# ---------------------------------------------------------------------------
# Per-hop regrouping by node (DESIGN.md §10)
# ---------------------------------------------------------------------------

_TIME_SUBSORT_BITS = 16   # quantized relative-time subsort resolution


def bucket_regroup(node_key: jax.Array, time_key: jax.Array,
                   node_capacity: int, *, time_subsort: bool = True
                   ) -> jax.Array:
    """Per-hop regroup of the walk lanes (DESIGN.md §10).

    Returns a permutation (output position -> input lane) grouping lanes by
    ``node_key`` (a stable sort; dead lanes keyed ``node_capacity + 1``
    land in the trailing bucket). When ``time_subsort`` is set and some
    occupied node carries lanes of different times, lanes of one node are
    further ordered by a span-scaled 16-bit quantized relative time (equal
    times always share a key, so grouping coarsens with the window span
    instead of saturating away), so equal-(node, time) lanes coalesce into
    single segments; the second sort sits behind a ``lax.cond``, so the
    common near-sorted steady state pays one sort. Each is one stable XLA
    sort with the lane ids as payload: on a TPU that is far cheaper than
    radix passes, whose scatters and gathers are single-element accesses.
    The permutation is purely an execution layout: any grouping is correct
    (segment heads are re-derived from the materialized order), so the
    quantization never affects emitted walks.
    """
    lanes = jnp.arange(node_key.shape[0], dtype=jnp.int32)
    by_node, perm = jax.lax.sort((node_key, lanes), num_keys=1,
                                 is_stable=True)
    if not time_subsort:
        return perm

    t = time_key[perm]
    mixed = jnp.any((by_node[1:] == by_node[:-1])
                    & (by_node[1:] <= node_capacity - 1) & (t[1:] != t[:-1]))

    def with_time(_):
        tlo = jnp.min(time_key)
        span = jnp.maximum(jnp.max(time_key) - tlo, 1)
        shift = jnp.maximum(
            jnp.floor(jnp.log2(span.astype(jnp.float32))).astype(
                jnp.int32) - (_TIME_SUBSORT_BITS - 1), 0)
        rel = jnp.clip((time_key - tlo) >> shift, 0,
                       (1 << _TIME_SUBSORT_BITS) - 1).astype(jnp.int32)
        return jax.lax.sort((node_key, rel, lanes), num_keys=2,
                            is_stable=True)[2]

    return jax.lax.cond(mixed, with_time, lambda _: perm, None)


class TaskTable(NamedTuple):
    """Fixed-shape task table for the Pallas tiled kernel.

    Each *task* covers one tile of ``tile_walks`` sorted walk lanes plus the
    edge-array window [edge_base, edge_base + tile_edges) that contains the
    neighborhoods of every walk in the tile (tasks are split so this holds;
    the split mirrors the paper's mega-hub expansion).
    """

    edge_base: jax.Array    # int32[T] base offset into the ns view
    walk_lo: jax.Array      # int32[W] per-walk tile-local region start
    walk_hi: jax.Array      # int32[W] per-walk tile-local region end
    oversize: jax.Array     # bool[W] neighborhood exceeds the tile => fallback


def build_task_table(index: TemporalIndex, s_node: jax.Array,
                     a: jax.Array, b: jax.Array,
                     cfg: SchedulerConfig) -> TaskTable:
    """Build the tile table for walks already sorted by node.

    Tiles are aligned windows of the ns view: a walk whose node region fits
    entirely inside the tile anchored at its own region start participates;
    walks whose regions span more than ``tile_edges`` are flagged oversize
    and served by the global-fallback path (paper's G-axis fallback).
    """
    W = s_node.shape[0]
    tw = cfg.tile_walks
    T = W // tw
    # anchor each tile at the smallest region start among its walks
    a_tiles = a.reshape(T, tw)
    b_tiles = b.reshape(T, tw)
    base = jnp.min(a_tiles, axis=1)
    span_ok = (b_tiles - base[:, None]) <= cfg.tile_edges
    walk_lo = (a_tiles - base[:, None]).reshape(W)
    walk_hi = (b_tiles - base[:, None]).reshape(W)
    oversize = ~span_ok.reshape(W)
    walk_lo = jnp.clip(walk_lo, 0, cfg.tile_edges)
    walk_hi = jnp.clip(walk_hi, 0, cfg.tile_edges)
    base = jnp.clip(base, 0, jnp.maximum(index.edge_capacity - cfg.tile_edges, 0))
    return TaskTable(edge_base=base.astype(jnp.int32),
                     walk_lo=walk_lo.astype(jnp.int32),
                     walk_hi=walk_hi.astype(jnp.int32),
                     oversize=oversize)
