"""Distributed walk engine: node-partitioned edge store + per-step
walk migration over ``all_to_all`` (shard_map).

Scale-out design (KnightKing-style walk migration, recast as collectives):

* nodes are partitioned across devices by a pluggable ``Placement``
  policy (repro/distributed/placement.py, DESIGN.md §15; default: range,
  ``owner(v) = v // range``); each device holds the dual-index of exactly
  its nodes' out-edges, so a resident walk's Γ_t(v) is always served
  locally;
* each step: (1) local hop via the same sampler stack as the single-device
  engine, (2) walks bucketed by destination owner, (3) one ``all_to_all``
  moves walk payloads (id, node, time + trace) to their new owners,
  (4) received walks compact into resident slots;
* RNG is keyed by (walk_id, step) via fold_in, so results are
  **bit-identical to the single-device engine** regardless of placement
  (tested in tests/test_distributed_walks.py);
* buckets are fixed-capacity (static shapes); overflow drops are counted
  and surface in the result — at production scale bucket capacity is a
  provisioning knob exactly like the paper's walk-array capacity.

This is a beyond-paper feature: Tempest is single-GPU; pod-scale walk
generation needs the store sharded (81B-edge windows exceed one chip's
HBM) and this module supplies the mechanism.

The owner-bucketed exchange (``exchange_by_owner``) and the resident-walk
hop (``hop_resident``) are shared with the *streaming* side of the same
partition: repro/distributed/streaming_shard.py keeps a node-partitioned
sliding window per shard (DESIGN.md §12) and advances walks over the
freshly ingested shard-local indexes with the exact same migration
machinery — there the per-(walk, step) RNG is the streaming engine's
(``uniform(fold_in(walk_key, step), (W,))[walk_id]``), which makes the
sharded replay bit-identical to the single-device
``StreamingEngine.replay_device``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import SamplerConfig
from repro.core.edge_store import TS_PAD, EdgeStore
from repro.core.temporal_index import (
    TemporalIndex,
    build_index,
    node_range,
    temporal_cutoff,
)
from repro.core.samplers import (
    pick_in_neighborhood,
    pick_in_neighborhood_lanes,
)
from repro.core.walk_engine import NODE_PAD


class ShardedWalkState(NamedTuple):
    walk_id: jax.Array    # int32[D, Wd]  (-1 = empty slot)
    cur_node: jax.Array   # int32[D, Wd]
    cur_time: jax.Array   # int32[D, Wd]
    alive: jax.Array      # bool[D, Wd]
    trace_n: jax.Array    # int32[D, Wd, L+1]
    trace_t: jax.Array    # int32[D, Wd, L+1]
    length: jax.Array     # int32[D, Wd]
    dropped: jax.Array    # int32[D] bucket-overflow counter


def partition_edges(src, dst, ts, num_nodes: int, num_shards: int,
                    edge_capacity_per_shard: int, placement=None):
    """Host-side: partition edges by source-node owner (``placement``,
    default range policy); build one TemporalIndex per shard, stacked on a
    leading device axis. Returns (stacked index, placement)."""
    if placement is None:
        from repro.distributed.placement import RangePlacement
        placement = RangePlacement(num_shards=num_shards,
                                   node_capacity=num_nodes)
    owners = placement.owner_np(np.asarray(src))
    stores = []
    for d in range(num_shards):
        sel = owners == d
        from repro.core.edge_store import store_from_arrays
        stores.append(store_from_arrays(
            np.asarray(src)[sel], np.asarray(dst)[sel], np.asarray(ts)[sel],
            edge_capacity=edge_capacity_per_shard,
            node_capacity=num_nodes))
    indexes = [build_index(s, num_nodes) for s in stores]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *indexes)
    return stacked, placement


def init_sharded_walks(num_shards: int, walks_per_shard: int,
                       max_length: int, start_nodes, start_times,
                       placement) -> ShardedWalkState:
    """Place walks on their start node's owner (host-side)."""
    D, Wd, L = num_shards, walks_per_shard, max_length
    wid = np.full((D, Wd), -1, np.int32)
    node = np.zeros((D, Wd), np.int32)
    tme = np.zeros((D, Wd), np.int32)
    alive = np.zeros((D, Wd), bool)
    tn = np.full((D, Wd, L + 1), NODE_PAD, np.int32)
    tt = np.full((D, Wd, L + 1), NODE_PAD, np.int32)
    ln = np.zeros((D, Wd), np.int32)
    fill = np.zeros(D, np.int32)
    start_owner = placement.owner_np(np.asarray(start_nodes))
    for i, (v, t) in enumerate(zip(np.asarray(start_nodes),
                                   np.asarray(start_times))):
        d = int(start_owner[i])
        s = fill[d]
        if s >= Wd:
            raise ValueError(f"shard {d} start overflow")
        wid[d, s] = i
        node[d, s] = v
        tme[d, s] = t
        alive[d, s] = True
        tn[d, s, 0] = v
        tt[d, s, 0] = t
        ln[d, s] = 1
        fill[d] += 1
    return ShardedWalkState(
        walk_id=jnp.asarray(wid), cur_node=jnp.asarray(node),
        cur_time=jnp.asarray(tme), alive=jnp.asarray(alive),
        trace_n=jnp.asarray(tn), trace_t=jnp.asarray(tt),
        length=jnp.asarray(ln), dropped=jnp.zeros((D,), jnp.int32))


def owner_range_size(num_nodes: int, num_shards: int) -> int:
    """Node-range width per shard: owner(v) = v // owner_range_size(...)."""
    return math.ceil(num_nodes / num_shards)


def hop_resident(idx: TemporalIndex, scfg: SamplerConfig, node, time, alive,
                 u):
    """One local hop for resident rows given per-row uniforms.

    The pure sampling half of a migration step, shared by the static walker
    (legacy per-(walk, step) fold_in keying) and the distributed streaming
    engine (engine keying, DESIGN.md §12): Γ_t(v) lives entirely on v's
    owner, so (cutoff, pick, gather) are all shard-local. Returns
    (next_node, next_time, has_next); rows without a next hop keep their
    (node, time).
    """
    a, b = node_range(idx, node)
    c = temporal_cutoff(idx, a, b, time)
    n = b - c
    has = alive & (n > 0)
    k = pick_in_neighborhood(idx, scfg, c, b, u, node)
    k = jnp.clip(k, 0, idx.edge_capacity - 1)
    return (jnp.where(has, idx.ns_dst[k], node),
            jnp.where(has, idx.ns_ts[k], time), has)


def hop_resident_lanes(idx: TemporalIndex, code, node, time, alive, u):
    """``hop_resident`` with a per-row bias *code* instead of a config bias.

    The migrating half of sharded lane serving (DESIGN.md §13): each
    resident row is one coalesced-query lane, whose bias dispatches
    branchlessly over the three closed-form inverse CDFs
    (``samplers.index_pick_lanes``) exactly as in the single-device lane
    engine — so the pick is a pure function of (code, u, |Γ_t(v)|) and the
    migrated walk stays bit-identical to its solo single-device run.
    """
    a, b = node_range(idx, node)
    c = temporal_cutoff(idx, a, b, time)
    has = alive & (b - c > 0)
    k = pick_in_neighborhood_lanes(idx, code, c, b, u)
    k = jnp.clip(k, 0, idx.edge_capacity - 1)
    return (jnp.where(has, idx.ns_dst[k], node),
            jnp.where(has, idx.ns_ts[k], time), has)


def exchange_by_owner(axis: str, num_shards: int, capacity: int,
                      owner, valid, payloads, fills):
    """Bucket rows by destination shard and move them with one all_to_all.

    ``owner``/``valid`` are [n] (destination shard id / live-row mask);
    ``payloads`` is a tuple of [n, ...] arrays and ``fills`` their padding
    values. Each destination bucket holds ``capacity`` rows; a valid row
    ranked past capacity in its bucket is **not sent** (static shapes make
    overflow a provisioning event, exactly like the paper's walk-array
    capacity) and counted in the returned scalar. Returns
    (received leaves [num_shards * capacity, ...], fits, n_dropped) —
    ``fits`` marks the rows that were actually sent, so callers can keep
    or retire the overflow locally.

    Rank within a bucket preserves row order, so receivers see each
    sender's rows contiguously in sender-position order — the property the
    sharded window ingest (DESIGN.md §12) relies on for stable timestamp
    tie-breaking.
    """
    n = owner.shape[0]
    owner = jnp.where(valid, owner, num_shards)
    # rank within destination bucket: stable sort by owner (distinct keys)
    sort_key = owner * n + jnp.arange(n, dtype=jnp.int32)
    order = jnp.argsort(sort_key).astype(jnp.int32)
    owner_sorted = owner[order]
    first = jnp.searchsorted(owner_sorted, owner_sorted,
                             side="left").astype(jnp.int32)
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - first
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    fits = (rank < capacity) & valid
    n_drop = jnp.sum(valid & ~fits)

    o = jnp.where(fits, owner, num_shards - 1)
    r = jnp.where(fits, rank, capacity)

    def move(payload, fillv):
        buf = jnp.full((num_shards, capacity) + payload.shape[1:], fillv,
                       payload.dtype)
        buf = buf.at[o, r].set(payload, mode="drop")
        res = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                 tiled=True)
        return res.reshape((num_shards * capacity,) + payload.shape[1:])

    received = tuple(move(p, f) for p, f in zip(payloads, fills))
    return received, fits, n_drop


def make_distributed_walker(mesh: Mesh, axis: str, index_stacked,
                            scfg: SamplerConfig, *, placement,
                            max_length: int, bucket_capacity: int):
    """Returns a jitted function advancing all walks ``max_length`` steps."""
    D = mesh.devices.size

    def local_hop(idx: TemporalIndex, node, time, alive, wid, step):
        # per-(walk, step) RNG: placement-independent
        base = jax.random.PRNGKey(0)
        sk = jax.vmap(lambda w: jax.random.fold_in(
            jax.random.fold_in(base, step), w))(wid)
        u = jax.vmap(lambda k: jax.random.uniform(k, ()))(sk)
        return hop_resident(idx, scfg, node, time, alive, u)

    def step_fn(idx, state_leaf_tuple, step):
        (wid, node, time, alive, tn, tt, ln, dropped) = state_leaf_tuple
        Wd = wid.shape[0]
        nn, nt, has = local_hop(idx, node, time, alive, wid, step)
        # record hop locally before migration
        tn = jnp.where(has[:, None] & (jnp.arange(tn.shape[1]) == ln[:, None]),
                       nn[:, None], tn)
        tt = jnp.where(has[:, None] & (jnp.arange(tt.shape[1]) == ln[:, None]),
                       nt[:, None], tt)
        ln = ln + has.astype(jnp.int32)
        occupied = wid >= 0
        alive = has

        # dead-but-occupied walks stay put (their trace lives here); only
        # ALIVE walks migrate to their destination's owner.
        owner = placement.owner(nn)
        ((r_wid, r_node, r_time, r_tn, r_tt, r_ln), fits,
         n_drop) = exchange_by_owner(
            axis, D, bucket_capacity, owner, alive & occupied,
            (wid, nn, nt, tn, tt, ln),
            (-1, 0, 0, NODE_PAD, NODE_PAD, 0))

        # keep: dead walks stay resident (their trace is gathered here);
        # bucket-overflow walks also stay but STOP (counted as dropped).
        keep = occupied & (~alive | ~fits)
        wid = jnp.where(keep, wid, -1)
        alive_keep = jnp.zeros_like(alive)
        # compact: place received walks into free slots
        free = wid < 0
        free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
        slot_of_free_rank = jnp.full((Wd,), Wd, jnp.int32).at[
            jnp.where(free, free_rank, Wd)].set(jnp.arange(Wd, dtype=jnp.int32),
                                                mode="drop")
        inc_valid = r_wid >= 0
        inc_rank = jnp.cumsum(inc_valid.astype(jnp.int32)) - 1
        dest = jnp.where(inc_valid,
                         slot_of_free_rank[jnp.clip(inc_rank, 0, Wd - 1)],
                         Wd)
        recv_drop = jnp.sum(inc_valid & (dest >= Wd))

        def place(cur, payload):
            return cur.at[dest].set(payload, mode="drop")

        wid = place(wid, r_wid)
        node = place(jnp.where(keep, node, 0), r_node)
        time = place(jnp.where(keep, time, 0), r_time)
        tn = place(jnp.where(keep[:, None], tn, NODE_PAD), r_tn)
        tt = place(jnp.where(keep[:, None], tt, NODE_PAD), r_tt)
        ln = place(jnp.where(keep, ln, 0), r_ln)
        alive = place(alive_keep, inc_valid)
        dropped = dropped + n_drop + recv_drop
        return (wid, node, time, alive, tn, tt, ln, dropped)

    def walker(index_st, state: ShardedWalkState):
        # strip the size-1 sharded leading axis shard_map leaves in place
        idx_local = jax.tree.map(lambda a: a[0], index_st)
        leaves = tuple(l[0] for l in
                       (state.walk_id, state.cur_node, state.cur_time,
                        state.alive, state.trace_n, state.trace_t,
                        state.length))
        leaves = leaves + (state.dropped[0],)

        def body(carry, step):
            return step_fn(idx_local, carry, step), None

        out, _ = jax.lax.scan(body, leaves,
                              jnp.arange(max_length, dtype=jnp.int32))
        return ShardedWalkState(*(o[None] for o in out))

    pspec_idx = jax.tree.map(lambda _: P(axis), index_stacked)
    pspec_state = ShardedWalkState(
        walk_id=P(axis), cur_node=P(axis), cur_time=P(axis), alive=P(axis),
        trace_n=P(axis), trace_t=P(axis), length=P(axis), dropped=P(axis))

    fn = shard_map(walker, mesh=mesh,
                   in_specs=(pspec_idx, pspec_state),
                   out_specs=pspec_state, check_vma=False)

    def run(state: ShardedWalkState) -> ShardedWalkState:
        return jax.jit(fn)(index_stacked, state)

    return run


def gather_walks(state: ShardedWalkState, num_walks: int):
    """Assemble (nodes, times, lengths) in walk-id order (host-side)."""
    wid = np.asarray(state.walk_id).reshape(-1)
    tn = np.asarray(state.trace_n).reshape(-1, state.trace_n.shape[-1])
    tt = np.asarray(state.trace_t).reshape(-1, state.trace_t.shape[-1])
    ln = np.asarray(state.length).reshape(-1)
    L1 = tn.shape[-1]
    nodes = np.full((num_walks, L1), NODE_PAD, np.int32)
    times = np.full((num_walks, L1), NODE_PAD, np.int32)
    lengths = np.zeros((num_walks,), np.int32)
    for i, w in enumerate(wid):
        if w >= 0:
            nodes[w] = tn[i]
            times[w] = tt[i]
            lengths[w] = ln[i]
    return nodes, times, lengths
