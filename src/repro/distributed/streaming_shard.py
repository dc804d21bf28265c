"""Node-partitioned sliding window: distributed streaming ingest + walks
(DESIGN.md §12).

``core/distributed.py`` shards the *static* edge store across devices and
migrates walks between owners; every streaming path so far (`ingest`,
`replay_scan`, `StreamingEngine`) still lives on one device, and
``sample_walks_sharded`` shards only the walk axis over a *replicated*
index. This module makes the **window itself** sharded, so both ingestion
capacity and walk throughput scale with device count — the regime where an
81B-edge window exceeds one chip's HBM:

* **Ownership** — nodes are partitioned by a pluggable ``Placement``
  policy (repro/distributed/placement.py, DESIGN.md §15; default
  ``range``: ``owner(v) = v // ceil(node_capacity / D)``, the same rule as
  ``core/distributed.py``); shard d holds the merge-sorted window slice
  of edges whose *source* it owns, so Γ_t(v) is always served locally.
  Every owner decision in this module — ingest bucketing, walk start
  claims, per-hop migration, serving lane claims — consults the same
  placement object, so swapping the policy (hash tables, hot-node skew
  overrides) re-routes all of them coherently; ``reshard`` re-buckets a
  *resident* window from one placement to another (or to a different
  shard count) through one all_to_all without dropping edges.
* **Sharded ingest** — each shard takes a 1/D slice of the incoming batch,
  buckets it by edge-source owner, and one ``all_to_all``
  (``exchange_by_owner``) delivers every edge to its owner. The owner
  compacts its received edges to a ts-sorted prefix and runs the
  single-device rank-based two-run merge (``window.ingest_impl``) locally.
* **Watermark agreement** — eviction must be causally consistent: the new
  ``t`` is the max batch timestamp across *all* shards (one ``pmax``
  before the exchange), passed to ``ingest_impl`` through its ``watermark``
  hook so every shard evicts against the same cutoff t − Δ even when its
  local batch slice is old.
* **Sharded walks** — per batch, walks start on their start node's owner
  and migrate every hop (``hop_resident`` + ``exchange_by_owner``) against
  the freshly ingested shard-local dual indexes. Hop draws are the
  streaming engine's own: ``uniform(fold_in(walk_key, step), (W,))``
  indexed by walk id — a pure function of (walk, step), independent of
  placement — so for ``SamplerConfig.mode="index"`` the replay is
  **bit-identical to the single-device ``StreamingEngine.replay_device``**
  for identical keys at any shard count (tested at 1/2/8 in
  tests/test_streaming_shard.py). ``mode="weight"`` runs but is only
  numerically (not bit-) equivalent: its prefix-sum arrays accumulate in a
  different float order per shard.
* **Trace handling** — unlike ``core/distributed.py`` (which migrates each
  walk's full trace every hop), each shard scatters the hops it executes
  into a resident ``[W, L+1]`` walk-order buffer; one ``psum`` at the end
  reassembles the global result (every cell is written by at most one
  shard). Migration payload shrinks from O(L) to 3 ints per walk, at the
  cost of an O(W·L) buffer per shard.

All capacities are static (``ShardConfig``): exchange buckets, resident
walk slots, and walk-migration buckets drop on overflow and count the
event per shard — provisioning knobs exactly like the paper's walk-array
capacity.

**Sharded lane serving** (DESIGN.md §13): ``serve_lanes_sharded`` runs one
coalesced multi-tenant lane batch (``walk_engine.LaneParams``) over the
node-partitioned window. Start lanes are claimed by their owner shard
(nodes mode: owner of the start node; edges mode: owner of the picked
edge's destination, resolved from a replicated ``window.TsView`` of the
global store), then migrate per hop exactly like the replay walker — the
3-int payload carries (lane id, node, time), and the lane's sampler params
(bias code, max length, per-request RNG identity) ride with it *by lane
id* through the replicated ``LaneParams`` arrays, so a lane keeps its own
sampler across owner hops without widening the wire format. Per-lane
draws are ``walk_engine._lane_uniform`` streams — pure functions of
(request seed, walk-within-request, step) — so the coalesced sharded
batch is **bit-identical to each query run solo on the single-device
engine** at any shard count (tested at 1/2/8 in
tests/test_serve_sharded.py). ``ingest_sharded_nodonate`` is the
non-donating ingest twin backing the serving snapshot double-buffer.
"""
from __future__ import annotations

import time
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (
    EngineConfig,
    SamplerConfig,
    ShardConfig,
    WalkConfig,
)
from repro.core.distributed import (
    exchange_by_owner,
    hop_resident,
    hop_resident_lanes,
)
from repro.core.edge_store import (
    TS_PAD,
    EdgeBatch,
    EdgeStore,
    stack_batches,
)
from repro.core.temporal_index import build_index
from repro.distributed.placement import (
    Placement,
    RangePlacement,
    SkewPlacement,
    make_placement,
)
from repro.core.samplers import index_pick_lanes
from repro.core.streaming import ReplayStats
from repro.obs.probes import (
    RP_WALKS_EMITTED,
    SP_HOPS,
    SP_LANES_CLAIMED,
    SP_WALK_DROPS,
    flush_replay_probes,
    replay_probe_update,
    replay_probe_zeros,
    serve_probe_zeros,
)
from repro.obs.registry import MetricsRegistry, count_drop, get_registry
from repro.core.walk_engine import (
    NODE_PAD,
    LaneFeatures,
    LaneParams,
    WalkResult,
    _lane_keys,
    _lane_uniform,
    check_capabilities,
)
from repro.core.window import TsView, WindowState, ingest_impl, init_window

WINDOW_AXIS = "window_shards"


class ShardedWindowState(NamedTuple):
    """Per-shard window slices, stacked on a leading [D] device axis.

    ``window`` holds one ``WindowState`` per shard (its counters are
    shard-local: summed over shards, ``late_drops``/``overflow_drops``
    equal the single-device window's, and ``ingested`` counts edges
    *delivered* — it lags the global count by ``exchange_drops``).
    """

    window: WindowState          # leaves [D, ...]
    exchange_drops: jax.Array    # int32[D] cumulative ingest-exchange drops


class DistReplayStats(NamedTuple):
    """Distributed replay statistics.

    ``replay`` carries the global per-batch trajectory in the same layout
    as the single-device ``ReplayStats`` — bit-comparable field by field
    when no shard dropped anything. The drop counters are per-batch,
    per-shard [K, D] (senders count their own exchange overflow).
    """

    replay: ReplayStats
    exchange_drops: jax.Array    # int32[K, D] batch-edge exchange overflow
    walk_drops: jax.Array        # int32[K, D] walk migration + slot overflow


def window_mesh(num_shards: int = 0, devices=None,
                axis_name: str = WINDOW_AXIS) -> Mesh:
    """1-D mesh over the first ``num_shards`` (default: all) devices."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    if num_shards:
        if num_shards > devs.size:
            raise ValueError(f"{num_shards} shards > {devs.size} devices")
        devs = devs[:num_shards]
    return Mesh(devs, (axis_name,))


def init_sharded_window(num_shards: int, edge_capacity_per_shard: int,
                        node_capacity: int, window: int,
                        bias_scale: float = 1.0,
                        mesh: Optional[Mesh] = None,
                        axis_name: str = WINDOW_AXIS,
                        table=None) -> ShardedWindowState:
    """D empty per-shard windows; placed onto the mesh when given.

    ``table`` (a ``core.alias.TableSpec``) makes every per-shard window
    carry alias tables over its *resident* regions, maintained
    incrementally by ``ingest_sharded`` (pass the same spec there).
    Sharded *sampling* under bias='table' stays refused — a migrating
    walk's draw would need its owner's table — but the maintenance
    itself shards cleanly because regions are node-local."""
    def build() -> ShardedWindowState:
        one = init_window(edge_capacity_per_shard, node_capacity, window,
                          bias_scale, table=table)
        return ShardedWindowState(
            window=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (num_shards,) + x.shape), one),
            exchange_drops=jnp.zeros((num_shards,), jnp.int32))

    if mesh is None:
        return build()
    # built under the output sharding, so each device materializes only
    # its own slice (stacking first would hold all D slices on one device)
    return jax.jit(build, out_shardings=NamedSharding(mesh, P(axis_name)))()


# ---------------------------------------------------------------------------
# Per-shard bodies (run under shard_map; all arrays are local views)
# ---------------------------------------------------------------------------


def _shard_ingest(wstate: WindowState, bsrc, bdst, bts, bvalid, *, axis: str,
                  num_shards: int, placement: Placement,
                  exchange_capacity: int,
                  node_capacity: int, bias_scale: float, table=None):
    """One shard's window advance for its slice of the incoming batch.

    batch slice → owner buckets → all_to_all → compact → local merge, with
    the eviction watermark agreed across shards *before* the exchange (so
    it reflects every arriving edge, even one a full bucket drops).
    """
    # (1) watermark agreement: global max batch timestamp
    local_max = jnp.max(jnp.where(bvalid, bts, -TS_PAD))
    watermark = jax.lax.pmax(local_max, axis)

    # (2) bucket by edge-source owner, one all_to_all
    owner = placement.owner(bsrc)
    (r_src, r_dst, r_ts), _, x_drop = exchange_by_owner(
        axis, num_shards, exchange_capacity, owner, bvalid,
        (bsrc, bdst, bts), (0, 0, TS_PAD))

    # (3) compact received edges to a ts-sorted prefix. Empty exchange
    # slots carry TS_PAD, so one stable ts-argsort both drops them to the
    # back and pre-sorts the run; ties keep (sender, sender-position) ==
    # global batch order, matching the single-device stable batch sort.
    order = jnp.argsort(r_ts).astype(jnp.int32)
    cnt = jnp.sum((r_ts != TS_PAD).astype(jnp.int32))
    local_batch = EdgeBatch(src=r_src[order], dst=r_dst[order],
                            ts=r_ts[order], count=cnt)

    # (4) the single-device rank-based two-run merge, shard-locally,
    # evicting against the agreed watermark; with a TableSpec the merge
    # also maintains this shard's alias tables over its resident regions
    new = ingest_impl(wstate, local_batch, node_capacity, bias_scale,
                      watermark=watermark, table=table)
    return new, x_drop


def _shard_walks(idx, walk_key: jax.Array, wcfg: WalkConfig,
                 scfg: SamplerConfig, *, axis: str, num_shards: int,
                 placement: Placement, walk_slots: int,
                 walk_bucket_capacity: int):
    """One batch's walks over the sharded window (start_mode="all_nodes").

    Returns this shard's trace contributions (walk-order [W, L+1] arrays,
    NODE_PAD where this shard executed no hop), its [W] length
    contributions, its drop count, and its start-claim count (the number
    of position-0 cells it wrote — obs probes derive per-shard hop counts
    as ``sum(ln) - claims``; DCE'd when unused). ``psum`` across shards
    reassembles the exact single-device WalkResult.
    """
    W, L = wcfg.num_walks, wcfg.max_length
    nc = idx.node_capacity
    Ws = walk_slots
    shard_id = jax.lax.axis_index(axis)

    # global t_floor: min in-window timestamp across shards, minus one
    # (empty shards report TS_PAD via their padded store)
    any_edges = jax.lax.pmax(idx.num_edges, axis) > 0
    global_min = jax.lax.pmin(idx.store.ts[0], axis)
    t_floor = jnp.where(any_edges, global_min - 1, 0)

    # place walk w (start node w % nc) on its start node's owner
    w_all = jnp.arange(W, dtype=jnp.int32)
    v_all = (w_all % nc).astype(jnp.int32)
    mine = placement.owner(v_all) == shard_id
    rankm = jnp.cumsum(mine.astype(jnp.int32)) - 1
    wid = jnp.full((Ws,), -1, jnp.int32).at[
        jnp.where(mine, rankm, Ws)].set(w_all, mode="drop")
    start_drop = jnp.maximum(jnp.sum(mine.astype(jnp.int32)) - Ws, 0)
    node = jnp.where(wid >= 0, wid % nc, 0).astype(jnp.int32)
    vc = jnp.clip(node, 0, nc - 1)
    deg = idx.node_starts[vc + 1] - idx.node_starts[vc]
    alive = (wid >= 0) & (deg > 0)
    claims = jnp.sum(alive.astype(jnp.int32))
    cur_time = jnp.full((Ws,), 1, jnp.int32) * t_floor

    # walk-order trace contributions; every cell this shard writes is PAD
    # on all other shards, so psum(x - PAD) + PAD reassembles the result
    tn = jnp.full((W, L + 1), NODE_PAD, jnp.int32)
    tt = jnp.full((W, L + 1), NODE_PAD, jnp.int32)
    ln = jnp.zeros((W,), jnp.int32)
    row0 = jnp.where(alive, wid, W)
    tn = tn.at[row0, 0].set(node, mode="drop")
    tt = tt.at[row0, 0].set(cur_time, mode="drop")
    ln = ln.at[row0].add(1, mode="drop")

    def record_hop(wid, node, cur_time, alive, tn, tt, ln, step):
        # the streaming engine's hop draw: one walk-order [W] vector per
        # step, indexed by walk id — placement-independent bits
        u_full = jax.random.uniform(jax.random.fold_in(walk_key, step), (W,))
        u = u_full[jnp.clip(wid, 0, W - 1)]
        nn, nt, has = hop_resident(idx, scfg, node, cur_time, alive, u)
        row = jnp.where(has, wid, W)
        tn = tn.at[row, step + 1].set(nn, mode="drop")
        tt = tt.at[row, step + 1].set(nt, mode="drop")
        ln = ln.at[row].add(1, mode="drop")
        return nn, nt, has, tn, tt, ln

    def hop(carry, step):
        wid, node, cur_time, alive, tn, tt, ln, dropped = carry
        nn, nt, has, tn, tt, ln = record_hop(wid, node, cur_time, alive,
                                             tn, tt, ln, step)

        # migrate surviving walks to their new owner (dead walks just free
        # their slot: the trace already lives in the resident buffers)
        owner = placement.owner(nn)
        (r_wid, r_node, r_time), _, n_drop = exchange_by_owner(
            axis, num_shards, walk_bucket_capacity, owner, has,
            (wid, nn, nt), (-1, 0, 0))

        inc_valid = r_wid >= 0
        dest = jnp.where(inc_valid,
                         jnp.cumsum(inc_valid.astype(jnp.int32)) - 1, Ws)
        recv_drop = jnp.sum(inc_valid & (dest >= Ws))
        wid = jnp.full((Ws,), -1, jnp.int32).at[dest].set(r_wid, mode="drop")
        node = jnp.zeros((Ws,), jnp.int32).at[dest].set(r_node, mode="drop")
        cur_time = jnp.zeros((Ws,), jnp.int32).at[dest].set(r_time,
                                                            mode="drop")
        alive = jnp.zeros((Ws,), bool).at[dest].set(inc_valid, mode="drop")
        return (wid, node, cur_time, alive, tn, tt, ln,
                dropped + n_drop + recv_drop), None

    # L-1 migrating hops under the scan, then one record-only final hop:
    # the last hop's migration would place walks nobody ever advances, so
    # skipping it saves one all_to_all per batch without touching the
    # traces (and therefore the bit-identity guarantee)
    carry0 = (wid, node, cur_time, alive, tn, tt, ln,
              jnp.asarray(0, jnp.int32))
    (wid, node, cur_time, alive, tn, tt, ln, dropped), _ = jax.lax.scan(
        hop, carry0, jnp.arange(max(L - 1, 0), dtype=jnp.int32))
    if L >= 1:
        _, _, _, tn, tt, ln = record_hop(
            wid, node, cur_time, alive, tn, tt, ln,
            jnp.asarray(L - 1, jnp.int32))
    return tn, tt, ln, dropped + start_drop, claims


def _shard_walk_lanes(idx, view: TsView, lanes: LaneParams, lane_keys,
                      wcfg: WalkConfig, *, axis: str, num_shards: int,
                      placement: Placement, walk_slots: int,
                      walk_bucket_capacity: int):
    """One coalesced lane batch's walks over the sharded window.

    The serving twin of ``_shard_walks``: every array-of-lanes input
    (``lanes``, ``lane_keys``, the ``view`` start directory) is replicated,
    so any shard can evaluate any lane's next draw — but each lane is
    *claimed* by exactly one shard per step (its current node's owner), so
    every trace cell is written by at most one shard and one ``psum``
    reassembles the exact single-device ``generate_walk_lanes`` result.

    Start claims: nodes mode places lane i on owner(start_node[i]) when the
    node has in-window out-edges (the owner holds the full degree); edges
    mode computes the global start-edge pick from the replicated ts-view —
    bit-identical to the single-device pick because the view's store is —
    and places the lane on owner(dst). Migration then carries 3 ints
    (lane id, node, time); bias / max_len / RNG identity are recovered from
    the replicated ``LaneParams`` by lane id at every hop.
    """
    S, L = wcfg.num_walks, wcfg.max_length
    nc = idx.node_capacity
    Ws = walk_slots
    shard_id = jax.lax.axis_index(axis)
    edges_mode = wcfg.start_mode == "edges"
    lane_ids = jnp.arange(S, dtype=jnp.int32)
    gstore = view.store

    # lane-order trace contributions (see _shard_walks: psum(x - PAD) + PAD)
    tn = jnp.full((S, L + 1), NODE_PAD, jnp.int32)
    tt = jnp.full((S, L + 1), NODE_PAD, jnp.int32)
    ln = jnp.zeros((S,), jnp.int32)

    if edges_mode:
        # global start-edge draw over the replicated ts-view: same formula,
        # same arrays (bitwise) as the single-device start_walks lane path
        u0 = _lane_uniform(lane_keys, 0)
        n_glob = jnp.broadcast_to(gstore.num_edges, (S,)).astype(jnp.int32)
        e = index_pick_lanes(lanes.start_bias, u0, n_glob)
        e = jnp.clip(e, 0, gstore.capacity - 1)
        s_src = gstore.src[e]
        s_cur = gstore.dst[e]
        s_ts = gstore.ts[e]
        alive0 = lanes.active & (gstore.num_edges > 0)
        owner = placement.owner(s_cur)
        mine = alive0 & (owner == shard_id)
        row0 = jnp.where(mine, lane_ids, S)
        tn = tn.at[row0, 0].set(s_src, mode="drop")
        tt = tt.at[row0, 0].set(s_ts, mode="drop")
        tn = tn.at[row0, 1].set(s_cur, mode="drop")
        tt = tt.at[row0, 1].set(s_ts, mode="drop")
        ln = ln.at[row0].add(2, mode="drop")
        start_node, start_time = s_cur, s_ts
        hops, offset = max(L - 1, 0), 1
    else:
        # explicit per-lane start nodes; the owner holds all of v's
        # out-edges, so its degree test equals the single-device one
        v = lanes.start_node
        vc = jnp.clip(v, 0, nc - 1)
        deg = idx.node_starts[vc + 1] - idx.node_starts[vc]
        owner = placement.owner(vc)
        t_floor = jnp.where(gstore.num_edges > 0, gstore.ts[0] - 1, 0)
        mine = (lanes.active & (v >= 0) & (v < nc) & (deg > 0)
                & (owner == shard_id))
        row0 = jnp.where(mine, lane_ids, S)
        start_node = vc
        start_time = jnp.full((S,), 1, jnp.int32) * t_floor
        tn = tn.at[row0, 0].set(start_node, mode="drop")
        tt = tt.at[row0, 0].set(start_time, mode="drop")
        ln = ln.at[row0].add(1, mode="drop")
        hops, offset = L, 0

    # per-shard start-claim counter (ServeStats.lanes_by_shard): counted on
    # device, so edges-mode claims — whose owners are data-dependent — are
    # observable exactly like nodes-mode ones
    claims = jnp.sum(mine.astype(jnp.int32))

    # place claimed lanes into resident slots
    rankm = jnp.cumsum(mine.astype(jnp.int32)) - 1
    wid = jnp.full((Ws,), -1, jnp.int32).at[
        jnp.where(mine, rankm, Ws)].set(lane_ids, mode="drop")
    start_drop = jnp.maximum(jnp.sum(mine.astype(jnp.int32)) - Ws, 0)
    wc0 = jnp.clip(wid, 0, S - 1)
    node = jnp.where(wid >= 0, start_node[wc0], 0).astype(jnp.int32)
    cur_time = jnp.where(wid >= 0, start_time[wc0], 0).astype(jnp.int32)
    alive = wid >= 0

    def record_hop(wid, node, cur_time, alive, tn, tt, ln, step):
        # per-lane draw stream (tag step+1; tag 0 was the start draw) and
        # per-lane bias/budget, recovered from the replicated arrays by the
        # slot's lane id — placement-independent bits, like the replay's
        u_full = _lane_uniform(lane_keys, step + 1)
        wc = jnp.clip(wid, 0, S - 1)
        nn, nt, has = hop_resident_lanes(idx, lanes.bias[wc], node, cur_time,
                                         alive, u_full[wc])
        write_pos = step + offset
        has = has & ((write_pos + 1) <= lanes.max_len[wc])
        row = jnp.where(has, wid, S)
        tn = tn.at[row, write_pos + 1].set(nn, mode="drop")
        tt = tt.at[row, write_pos + 1].set(nt, mode="drop")
        ln = ln.at[row].add(1, mode="drop")
        return nn, nt, has, tn, tt, ln

    def hop(carry, step):
        wid, node, cur_time, alive, tn, tt, ln, dropped = carry
        nn, nt, has, tn, tt, ln = record_hop(wid, node, cur_time, alive,
                                             tn, tt, ln, step)
        owner = placement.owner(nn)
        (r_wid, r_node, r_time), _, n_drop = exchange_by_owner(
            axis, num_shards, walk_bucket_capacity, owner, has,
            (wid, nn, nt), (-1, 0, 0))

        inc_valid = r_wid >= 0
        dest = jnp.where(inc_valid,
                         jnp.cumsum(inc_valid.astype(jnp.int32)) - 1, Ws)
        recv_drop = jnp.sum(inc_valid & (dest >= Ws))
        wid = jnp.full((Ws,), -1, jnp.int32).at[dest].set(r_wid, mode="drop")
        node = jnp.zeros((Ws,), jnp.int32).at[dest].set(r_node, mode="drop")
        cur_time = jnp.zeros((Ws,), jnp.int32).at[dest].set(r_time,
                                                            mode="drop")
        alive = jnp.zeros((Ws,), bool).at[dest].set(inc_valid, mode="drop")
        return (wid, node, cur_time, alive, tn, tt, ln,
                dropped + n_drop + recv_drop), None

    # L-1 migrating hops + one record-only final hop, as in _shard_walks
    carry0 = (wid, node, cur_time, alive, tn, tt, ln,
              jnp.asarray(0, jnp.int32))
    (wid, node, cur_time, alive, tn, tt, ln, dropped), _ = jax.lax.scan(
        hop, carry0, jnp.arange(max(hops - 1, 0), dtype=jnp.int32))
    if hops >= 1:
        _, _, _, tn, tt, ln = record_hop(
            wid, node, cur_time, alive, tn, tt, ln,
            jnp.asarray(hops - 1, jnp.int32))
    return tn, tt, ln, dropped + start_drop, claims


# ---------------------------------------------------------------------------
# Standalone sharded ingest: advance the window by one batch (no walks)
# ---------------------------------------------------------------------------


def _ingest_sharded_impl(state: ShardedWindowState, bsrc, bdst, bts, count, *,
                         mesh: Mesh, axis_name: str, node_capacity: int,
                         shard_cfg: ShardConfig, bias_scale: float = 1.0,
                         placement: Optional[Placement] = None,
                         table=None) -> ShardedWindowState:
    """Advance the sharded window by one batch (``bsrc/bdst/bts`` are
    [D, Bd], the batch axis pre-split per shard; ``count`` the global valid
    prefix length). The shard_map'd single-batch twin of the replay's
    ingest stage; see ``ingest_sharded`` / ``ingest_sharded_nodonate``."""
    D = mesh.devices.size
    if placement is None:
        placement = RangePlacement(num_shards=D, node_capacity=node_capacity)

    def shard_fn(state, bsrc, bdst, bts, count):
        wstate = jax.tree.map(lambda a: a[0], state.window)
        Bd = bsrc.shape[-1]
        gpos = jax.lax.axis_index(axis_name) * Bd + jnp.arange(
            Bd, dtype=jnp.int32)
        new, x_drop = _shard_ingest(
            wstate, bsrc[0], bdst[0], bts[0], gpos < count, axis=axis_name,
            num_shards=D, placement=placement,
            exchange_capacity=shard_cfg.exchange_capacity,
            node_capacity=node_capacity, bias_scale=bias_scale, table=table)
        return ShardedWindowState(
            window=jax.tree.map(lambda a: a[None], new),
            exchange_drops=(state.exchange_drops[0] + x_drop)[None])

    sharded = P(axis_name)
    state_spec = ShardedWindowState(
        window=jax.tree.map(lambda _: sharded, state.window),
        exchange_drops=sharded)
    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(state_spec, sharded, sharded, sharded, P()),
                   out_specs=state_spec, check_vma=False)
    return fn(state, bsrc, bdst, bts, count)


# Donating entry point: the replay-style in-place window advance.
ingest_sharded = partial(
    jax.jit,
    static_argnames=("mesh", "axis_name", "node_capacity", "shard_cfg",
                     "bias_scale", "placement", "table"),
    donate_argnums=(0,))(_ingest_sharded_impl)

# Non-donating twin for the sharded serving snapshot double-buffer
# (serve/snapshot.py, DESIGN.md §13): the old ShardedWindowState must stay
# serveable while the next one builds, so the input cannot be donated —
# exactly the ``window.ingest_nodonate`` trade, one sharded window level
# up. Same shard_map'd body, pmax-agreed watermark included.
ingest_sharded_nodonate = partial(
    jax.jit,
    static_argnames=("mesh", "axis_name", "node_capacity", "shard_cfg",
                     "bias_scale", "placement", "table"))(_ingest_sharded_impl)


# ---------------------------------------------------------------------------
# Fused sharded replay: one shard_map'd lax.scan over all batches
# ---------------------------------------------------------------------------


def _check_supported(wcfg: WalkConfig, scfg: SamplerConfig, *,
                     lanes: bool = False) -> None:
    """Static validation of a sharded walk dispatch.

    ``lanes=False`` is the replay walker (all_nodes placement only);
    ``lanes=True`` is the serving lane walker, where start placement is
    owner-computable per lane: explicit start nodes, or start edges
    resolved from the replicated ts-view (DESIGN.md §13).

    The start-mode checks are sharding-specific and live here; every
    sampler-capability refusal (mode, node2vec, bias='table') delegates
    to the engine's single chokepoint, ``walk_engine.check_capabilities``
    with ``sharded=True`` — one matrix, one set of messages.
    """
    if lanes:
        if wcfg.start_mode not in ("nodes", "edges"):
            raise ValueError(
                "sharded lane serving supports start_mode 'nodes'|'edges' "
                f"(got {wcfg.start_mode!r})")
    elif wcfg.start_mode != "all_nodes":
        raise ValueError(
            "sharded streaming walks require start_mode='all_nodes' (start "
            "placement must be owner-computable without global state; got "
            f"{wcfg.start_mode!r})")
    check_capabilities(scfg, "grouped",
                       LaneFeatures() if lanes else None, sharded=True)


@partial(jax.jit,
         static_argnames=("mesh", "axis_name", "node_capacity", "wcfg",
                          "scfg", "shard_cfg", "placement", "with_probes"))
def serve_lanes_sharded(state: ShardedWindowState, view: TsView,
                        key: jax.Array, lanes: LaneParams, *, mesh: Mesh,
                        axis_name: str, node_capacity: int,
                        wcfg: WalkConfig, scfg: SamplerConfig,
                        shard_cfg: ShardConfig,
                        placement: Optional[Placement] = None,
                        with_probes: bool = False):
    """One coalesced lane batch over the node-partitioned window.

    ``state`` is the sharded window (NOT donated: the serving snapshot
    keeps it readable across dispatches), ``view`` the replicated ts-view
    of the same window version, ``key`` the service's stable base key and
    ``lanes`` the packed per-lane params. Returns (nodes, times, lengths,
    drops, claims): walk leaves with a leading [D] replicated axis
    (callers read row 0) shaped like the single-device
    ``generate_walk_lanes`` result, plus two per-shard [D] counters —
    ``drops`` (start-slot + migration overflow — 0 under healthy
    provisioning, and required for the bit-identity guarantee) and
    ``claims`` (start lanes claimed by each shard, the device-side source
    of ``ServeStats.lanes_by_shard`` for both start modes).
    ``with_probes=True`` appends a sixth output — an obs serve-probe
    matrix int32[D, NUM_SERVE_PROBES] (claims / drops / per-shard hop
    cells) for ``obs.flush_serve_probes`` — computed from values the
    dispatch already produces, so walks stay bit-identical (pinned by
    tests/test_obs_probes.py).
    """
    _check_supported(wcfg, scfg, lanes=True)
    D = mesh.devices.size
    if placement is None:
        placement = RangePlacement(num_shards=D, node_capacity=node_capacity)

    def shard_fn(state, view, key, lanes):
        wstate = jax.tree.map(lambda a: a[0], state.window)
        # lane RNG identity: fold (request seed, walk-within-request) into
        # the base key — replicated math, identical on every shard
        lane_keys = _lane_keys(key, lanes)
        tn, tt, ln, drop, claims = _shard_walk_lanes(
            wstate.index, view, lanes, lane_keys, wcfg, axis=axis_name,
            num_shards=D, placement=placement,
            walk_slots=shard_cfg.walk_slots,
            walk_bucket_capacity=shard_cfg.walk_bucket_capacity)
        nodes = NODE_PAD + jax.lax.psum(tn - NODE_PAD, axis_name)
        times = NODE_PAD + jax.lax.psum(tt - NODE_PAD, axis_name)
        lengths = jax.lax.psum(ln, axis_name)
        outs = (nodes[None], times[None], lengths[None], drop[None],
                claims[None])
        if with_probes:
            # start cells are written only by the claiming shard (2 per
            # lane in edges mode: src + first dst), so this shard's hop
            # cells are its length contributions minus its start cells
            start_cells = claims * (2 if wcfg.start_mode == "edges" else 1)
            sp = serve_probe_zeros()
            sp = sp.at[SP_LANES_CLAIMED].add(claims)
            sp = sp.at[SP_WALK_DROPS].add(drop)
            sp = sp.at[SP_HOPS].add(jnp.sum(ln) - start_cells)
            outs = outs + (sp[None],)
        return outs

    sharded = P(axis_name)
    state_spec = ShardedWindowState(
        window=jax.tree.map(lambda _: sharded, state.window),
        exchange_drops=sharded)
    view_spec = jax.tree.map(lambda _: P(), view)
    lane_spec = LaneParams(*([P()] * len(LaneParams._fields)))
    out_specs = (sharded,) * (6 if with_probes else 5)
    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(state_spec, view_spec, P(), lane_spec),
                   out_specs=out_specs, check_vma=False)
    return fn(state, view, key, lanes)


@partial(jax.jit,
         static_argnames=("axis_name", "node_capacity", "wcfg", "scfg",
                          "shard_cfg", "bias_scale", "mesh", "placement",
                          "with_probes"),
         donate_argnums=(0,))
def _replay_scan_sharded(state: ShardedWindowState, bsrc, bdst, bts, bcount,
                         key, *, mesh: Mesh, axis_name: str,
                         node_capacity: int, wcfg: WalkConfig,
                         scfg: SamplerConfig, shard_cfg: ShardConfig,
                         bias_scale: float = 1.0,
                         placement: Optional[Placement] = None,
                         with_probes: bool = False):
    """Replay K stacked batches over the sharded window, fully on device.

    ``bsrc/bdst/bts`` are [K, D, Bd] (the batch axis pre-split per shard),
    ``bcount`` [K]. Returns (new state, per-batch stat leaves, final-batch
    walk leaves); everything carries a leading [D] axis — psum'd leaves are
    replicated so callers read row 0. ``with_probes=True`` appends one
    obs probe matrix int32[D, NUM_REPLAY_PROBES] (shard-local counters
    accumulated across batches in the scan carry — pure arithmetic on
    values the replay already computes, RNG chain untouched).
    """
    D = mesh.devices.size
    if placement is None:
        placement = RangePlacement(num_shards=D, node_capacity=node_capacity)

    def shard_fn(state, bsrc, bdst, bts, bcount, key):
        wstate = jax.tree.map(lambda a: a[0], state.window)
        xdrops = state.exchange_drops[0]
        lsrc, ldst, lts = bsrc[:, 0], bdst[:, 0], bts[:, 0]   # [K, Bd]
        Bd = lsrc.shape[-1]
        shard_id = jax.lax.axis_index(axis_name)
        # local slice covers global batch positions [shard_id*Bd, ...+Bd)
        gpos = shard_id * Bd + jnp.arange(Bd, dtype=jnp.int32)

        def batch_step(carry, xs):
            if with_probes:
                wstate, xdrops, k, pv = carry
            else:
                wstate, xdrops, k = carry
            w0 = wstate
            src, dst, ts, cnt = xs
            k, sub = jax.random.split(k)
            wstate, x_drop = _shard_ingest(
                wstate, src, dst, ts, gpos < cnt, axis=axis_name,
                num_shards=D, placement=placement,
                exchange_capacity=shard_cfg.exchange_capacity,
                node_capacity=node_capacity, bias_scale=bias_scale)

            # same key chain as the single-device replay_scan
            _, walk_key = jax.random.split(sub)
            tn, tt, ln, w_drop, claims = _shard_walks(
                wstate.index, walk_key, wcfg, scfg, axis=axis_name,
                num_shards=D, placement=placement,
                walk_slots=shard_cfg.walk_slots,
                walk_bucket_capacity=shard_cfg.walk_bucket_capacity)

            lengths = jax.lax.psum(ln, axis_name)
            stats = ReplayStats(
                edges_active=jax.lax.psum(wstate.index.num_edges, axis_name),
                t_now=wstate.t_now,      # watermark-agreed: replicated
                ingested=jax.lax.psum(wstate.ingested, axis_name),
                late_drops=jax.lax.psum(wstate.late_drops, axis_name),
                overflow_drops=jax.lax.psum(wstate.overflow_drops,
                                            axis_name),
                mean_len=jnp.mean(lengths.astype(jnp.float32)),
            )
            if with_probes:
                # shard-local deltas (the flush sums label series); the
                # emitted-walk count is global, so only shard 0 records it
                pv = replay_probe_update(
                    pv,
                    ingested_delta=wstate.ingested - w0.ingested,
                    late_delta=wstate.late_drops - w0.late_drops,
                    overflow_delta=wstate.overflow_drops - w0.overflow_drops,
                    exchange_drops=x_drop,
                    walk_drops=w_drop,
                    hops=jnp.sum(ln) - claims)
                emitted = jnp.sum((lengths >= 2).astype(jnp.int32))
                pv = pv.at[RP_WALKS_EMITTED].add(
                    jnp.where(shard_id == 0, emitted, 0))
                return ((wstate, xdrops + x_drop, k, pv),
                        (stats, x_drop, w_drop, tn, tt, ln))
            return ((wstate, xdrops + x_drop, k),
                    (stats, x_drop, w_drop, tn, tt, ln))

        carry0 = [wstate, xdrops, key]
        if with_probes:
            carry0.append(replay_probe_zeros())
        carry, (stats, x_drops, w_drops, tns, tts, lns) = \
            jax.lax.scan(batch_step, tuple(carry0),
                         (lsrc, ldst, lts, bcount))
        wstate, xdrops = carry[0], carry[1]

        # reassemble the final batch's walks (each cell written by ≤ 1
        # shard; contributions are PAD elsewhere)
        tn, tt, ln = tns[-1], tts[-1], lns[-1]
        nodes = NODE_PAD + jax.lax.psum(tn - NODE_PAD, axis_name)
        times = NODE_PAD + jax.lax.psum(tt - NODE_PAD, axis_name)
        lengths = jax.lax.psum(ln, axis_name)

        new_state = ShardedWindowState(
            window=jax.tree.map(lambda a: a[None], wstate),
            exchange_drops=xdrops[None])
        expand = lambda a: a[None]
        outs = (new_state, jax.tree.map(expand, stats), x_drops[None],
                w_drops[None], expand(nodes), expand(times), expand(lengths))
        if with_probes:
            outs = outs + (expand(carry[3]),)
        return outs

    sharded = P(axis_name)
    state_spec = ShardedWindowState(
        window=jax.tree.map(lambda _: sharded, state.window),
        exchange_drops=sharded)
    stats_spec = ReplayStats(*([sharded] * len(ReplayStats._fields)))
    out_specs = (state_spec, stats_spec, sharded, sharded, sharded,
                 sharded, sharded)
    if with_probes:
        out_specs = out_specs + (sharded,)
    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(state_spec, P(None, axis_name), P(None, axis_name),
                  P(None, axis_name), P(), P()),
        out_specs=out_specs,
        check_vma=False)
    return fn(state, bsrc, bdst, bts, bcount, key)


class DistributedStreamingEngine:
    """Streaming ingest → rebuild → walk over a node-partitioned window.

    The distributed counterpart of ``StreamingEngine.replay_device``: the
    window lives sharded across ``mesh`` (per-shard capacity
    ``cfg.shard.edge_capacity_per_shard``, so total window capacity scales
    with device count), batches ingest through one all_to_all per batch,
    and walks migrate between owners per hop. For
    ``SamplerConfig.mode="index"`` the replay is bit-identical to the
    single-device engine for identical keys (any shard count, provided no
    capacity drops — check ``DistReplayStats``); per-hop grouping does not
    apply (the migration layout is its own schedule), which changes nothing
    observable since every scheduler path emits identical walks.
    """

    def __init__(self, cfg: EngineConfig, batch_capacity: int, *,
                 mesh: Optional[Mesh] = None, num_shards: int = 0,
                 placement: Optional[Placement] = None,
                 registry: Optional[MetricsRegistry] = None,
                 probes: bool = True):
        self.cfg = cfg
        # obs integration (DESIGN.md §16); ``probes=False`` pins
        # replay_device to the historical uninstrumented program
        self.registry = registry if registry is not None else get_registry()
        self.probes = probes
        self.mesh = mesh if mesh is not None else window_mesh(
            num_shards or cfg.shard.num_shards)
        self.axis_name = self.mesh.axis_names[0]
        D = self.mesh.devices.size
        self.num_shards = D
        if placement is None:
            placement = make_placement(
                cfg.shard.placement, D, cfg.window.node_capacity,
                hash_buckets=cfg.shard.hash_buckets)
        if placement.num_shards != D:
            raise ValueError(
                f"placement covers {placement.num_shards} shards; mesh has "
                f"{D} devices")
        if placement.node_capacity != cfg.window.node_capacity:
            raise ValueError(
                f"placement node_capacity {placement.node_capacity} != "
                f"window node_capacity {cfg.window.node_capacity}")
        self.placement = placement
        # per-shard batch slice: round the capacity up to a D multiple
        self._requested_batch_capacity = batch_capacity
        self.batch_slice = -(-batch_capacity // D)
        self.batch_capacity = self.batch_slice * D
        self.state = init_sharded_window(
            D, cfg.shard.edge_capacity_per_shard, cfg.window.node_capacity,
            int(cfg.window.duration), mesh=self.mesh,
            axis_name=self.axis_name)
        self.key = jax.random.PRNGKey(cfg.seed)

    def ingest_batch(self, src, dst, ts) -> None:
        """Advance the sharded window by one batch (no walks) — the
        distributed twin of ``StreamingEngine.ingest_batch``."""
        from repro.core.edge_store import make_batch
        batch = make_batch(src, dst, ts, capacity=self.batch_capacity)
        split = lambda a: a.reshape(self.num_shards, self.batch_slice)
        self.state = ingest_sharded(
            self.state, split(batch.src), split(batch.dst), split(batch.ts),
            batch.count, mesh=self.mesh, axis_name=self.axis_name,
            node_capacity=self.cfg.window.node_capacity,
            shard_cfg=self.cfg.shard, placement=self.placement)

    def replay_device(self, batches, wcfg: WalkConfig):
        """One shard_map'd ``lax.scan`` over all batches; a single host
        sync at the end. Returns (DistReplayStats, final-batch WalkResult,
        wall seconds)."""
        _check_supported(wcfg, self.cfg.sampler)
        stacked = stack_batches(batches, self.batch_capacity)
        K = stacked.src.shape[0]
        split = lambda a: a.reshape(K, self.num_shards, self.batch_slice)
        self.key, sub = jax.random.split(self.key)
        t0 = time.perf_counter()
        outs = _replay_scan_sharded(
            self.state, split(stacked.src), split(stacked.dst),
            split(stacked.ts), stacked.count, sub, mesh=self.mesh,
            axis_name=self.axis_name,
            node_capacity=self.cfg.window.node_capacity, wcfg=wcfg,
            scfg=self.cfg.sampler, shard_cfg=self.cfg.shard,
            placement=self.placement, with_probes=self.probes)
        if self.probes:
            (self.state, stats, x_drops, w_drops, nodes, times, lengths,
             pv) = outs
            # the single sync point — probes ride the same materialization
            jax.block_until_ready((lengths, pv))
        else:
            (self.state, stats, x_drops, w_drops, nodes, times,
             lengths) = outs
            jax.block_until_ready(lengths)      # the single sync point
        elapsed = time.perf_counter() - t0
        replay = ReplayStats(*(np.asarray(a)[0] for a in stats))
        if self.probes:
            self._publish_replay(pv, replay, elapsed)
        dstats = DistReplayStats(
            replay=replay,
            exchange_drops=np.asarray(x_drops).T,     # [D, K] -> [K, D]
            walk_drops=np.asarray(w_drops).T,
        )
        walks = WalkResult(nodes=np.asarray(nodes)[0],
                           times=np.asarray(times)[0],
                           lengths=np.asarray(lengths)[0], stats=None)
        return dstats, walks, elapsed

    def _publish_replay(self, pv, replay: ReplayStats, elapsed: float
                        ) -> None:
        """Flush the per-shard probe matrix + window gauges after a
        replay's single host sync (the arrays are already materialized)."""
        reg = self.registry
        mat = np.asarray(pv)                     # [D, NUM_REPLAY_PROBES]
        for d in range(mat.shape[0]):
            flush_replay_probes(reg, mat[d], driver="sharded", shard=d)
        loads = self.shard_loads()
        for d, v in enumerate(loads):
            reg.set_gauge("shard_edges_active", int(v),
                          labels={"shard": str(d)},
                          help="resident window edges per shard")
        cap = self.cfg.shard.edge_capacity_per_shard * self.num_shards
        edges = int(replay.edges_active[-1]) if replay.edges_active.size \
            else 0
        reg.set_gauge("window_edges_active", edges,
                      help="edges resident in the temporal window")
        reg.set_gauge("window_t_now",
                      int(replay.t_now[-1]) if replay.t_now.size else 0,
                      help="watermark timestamp of the window")
        reg.set_gauge("window_occupancy", edges / cap,
                      help="window fill fraction (edges_active / capacity)")
        reg.observe("replay_seconds", elapsed, labels={"driver": "sharded"},
                    help="wall time per replay_device call")

    # ------------------------------------------------------------------
    # Placement control plane: measured load -> new placement -> reshard
    # ------------------------------------------------------------------

    def node_loads(self) -> np.ndarray:
        """Per-node in-window out-degree [node_capacity] (host-side).

        The skew signal: under a power-law stream, range placement piles
        the hub nodes' edges onto few shards; feeding these loads to
        ``SkewPlacement.from_loads`` builds the hot-node override table
        that ``rebalance`` reshards onto.
        """
        # node_starts spans nc real nodes + the virtual padding node; the
        # per-node degree diff is trimmed to the real ids
        ns = np.asarray(self.state.window.index.node_starts)
        nc = self.cfg.window.node_capacity
        return (ns[:, 1:] - ns[:, :-1]).sum(axis=0)[:nc]

    def shard_loads(self) -> np.ndarray:
        """Resident window edges per shard [D] (the imbalance metric)."""
        return np.asarray(self.state.window.index.num_edges)

    def reshard_to(self, new_placement: Placement) -> None:
        """Live reshard: re-bucket the resident window onto
        ``new_placement`` (different policy and/or shard count) through
        one all_to_all; ingest/replay continue against the new layout.
        The walk RNG chain is untouched — replay stays bit-identical to
        the single-device engine across the reshard (absent drops)."""
        before = int(np.asarray(self.state.exchange_drops).sum())
        self.state, self.mesh = reshard(
            self.state, self.placement, new_placement,
            axis_name=self.axis_name)
        # exchange_drops is cumulative; the reshard's own contribution is
        # the per-shard capacity clip — published under its canonical kind
        after = int(np.asarray(self.state.exchange_drops).sum())
        count_drop(self.registry, "reshard_clip", max(0, after - before))
        self.registry.inc("reshards_total", 1,
                          help="live placement reshards executed")
        self.placement = new_placement
        D = new_placement.num_shards
        self.num_shards = D
        self.batch_slice = -(-self._requested_batch_capacity // D)
        self.batch_capacity = self.batch_slice * D

    def rebalance(self, k: Optional[int] = None) -> Placement:
        """Measure per-node load, build a top-K hub override placement on
        the current base policy, and reshard onto it. Returns the new
        placement."""
        base = (self.placement.base
                if isinstance(self.placement, SkewPlacement)
                else self.placement)
        new = SkewPlacement.from_loads(
            base, self.node_loads(),
            k=k if k is not None else self.cfg.shard.hot_k)
        self.reshard_to(new)
        return new


# ---------------------------------------------------------------------------
# Live resharding: re-bucket a resident window under a new placement
# ---------------------------------------------------------------------------


def _pad_shards(state: ShardedWindowState, num: int) -> ShardedWindowState:
    """Append ``num`` empty shard slices (same Δ, zeroed clock/counters).

    Host-side prep for a shard-count-increasing reshard: the exchange mesh
    spans max(D_old, D_new) devices, so a growing window first gains empty
    slices. Their t_now starts at 0 and is pmax-repaired on device.
    """
    w = state.window
    E = int(w.index.store.src.shape[1])
    nc = int(w.index.node_starts.shape[1]) - 1
    delta = int(np.asarray(w.window)[0])
    empty = init_window(E, nc, delta)
    pad = jax.tree.map(lambda x: jnp.broadcast_to(x, (num,) + x.shape),
                       empty)
    window = jax.tree.map(lambda a, p: jnp.concatenate([a, p]), w, pad)
    return ShardedWindowState(
        window=window,
        exchange_drops=jnp.concatenate(
            [state.exchange_drops, jnp.zeros((num,), jnp.int32)]))


@partial(jax.jit,
         static_argnames=("mesh", "axis_name", "placement", "bias_scale"))
def _reshard_impl(state: ShardedWindowState, *, mesh: Mesh, axis_name: str,
                  placement: Placement, bias_scale: float = 1.0
                  ) -> ShardedWindowState:
    """shard_map'd reshard body over a max(D_old, D_new)-device mesh.

    Each shard sends every resident edge to ``placement.owner(src)`` with
    per-(sender, dest) bucket capacity E — a sender holds at most E edges
    total, so the exchange itself can NEVER drop. The receiver re-merges
    by the canonical rule: received runs concatenated in old-shard-id
    order with sender-position preserved (``exchange_by_owner``'s order
    guarantee), one stable ts-argsort (ties therefore break by (old
    shard, position) — for edges of one source node that is their
    original relative order, which is all walk bit-identity needs), then
    an overflow clip keeping the NEWEST E edges (``_advance_store``'s
    rule) with the loss counted in ``exchange_drops``.

    Counters: per-shard ``ingested``/``late_drops``/``overflow_drops``/
    ``exchange_drops`` are psum'd onto shard 0 (zeros elsewhere), so their
    shard-sums — the quantities the identity tests compare against the
    single-device engine — survive any shard-count change.
    """
    Dm = mesh.devices.size
    nc = placement.node_capacity

    def shard_fn(state):
        wstate = jax.tree.map(lambda a: a[0], state.window)
        store = wstate.index.store
        E = store.capacity
        valid = jnp.arange(E, dtype=jnp.int32) < store.num_edges
        owner = placement.owner(store.src)
        (r_src, r_dst, r_ts), _, x_drop = exchange_by_owner(
            axis_name, Dm, E, owner, valid,
            (store.src, store.dst, store.ts), (nc, 0, TS_PAD))

        # canonical merge: stable ts sort over the [Dm*E] receive buffer
        # (TS_PAD rows sink to the back), then clip keeping the newest E
        order = jnp.argsort(r_ts).astype(jnp.int32)
        msrc, mdst, mts = r_src[order], r_dst[order], r_ts[order]
        cnt = jnp.sum((r_ts != TS_PAD).astype(jnp.int32))
        overflow = jnp.maximum(cnt - E, 0)
        idx2 = jnp.arange(E, dtype=jnp.int32) + overflow
        live2 = jnp.arange(E, dtype=jnp.int32) < jnp.minimum(cnt, E)
        gidx = jnp.clip(idx2, 0, Dm * E - 1)
        new_store = EdgeStore(
            src=jnp.where(live2, msrc[gidx], nc),
            dst=jnp.where(live2, mdst[gidx], 0),
            ts=jnp.where(live2, mts[gidx], TS_PAD),
            num_edges=jnp.minimum(cnt, E).astype(jnp.int32))
        index = build_index(new_store, nc, bias_scale)

        # clock: pmax repairs padded shards' zero t_now / Δ
        t_now = jax.lax.pmax(wstate.t_now, axis_name)
        delta = jax.lax.pmax(wstate.window, axis_name)

        # counters: global sums live on shard 0 after a reshard
        sid = jax.lax.axis_index(axis_name)
        on0 = lambda x: jnp.where(sid == 0, jax.lax.psum(x, axis_name), 0)
        new_w = WindowState(
            index=index, t_now=t_now, window=delta,
            ingested=on0(wstate.ingested),
            late_drops=on0(wstate.late_drops),
            overflow_drops=on0(wstate.overflow_drops))
        xd = on0(state.exchange_drops[0] + x_drop) + overflow
        return ShardedWindowState(
            window=jax.tree.map(lambda a: a[None], new_w),
            exchange_drops=xd[None])

    sharded = P(axis_name)
    state_spec = ShardedWindowState(
        window=jax.tree.map(lambda _: sharded, state.window),
        exchange_drops=sharded)
    fn = shard_map(shard_fn, mesh=mesh, in_specs=(state_spec,),
                   out_specs=state_spec, check_vma=False)
    return fn(state)


def reshard(state: ShardedWindowState, old_placement: Placement,
            new_placement: Placement, *, mesh: Optional[Mesh] = None,
            axis_name: str = WINDOW_AXIS, bias_scale: float = 1.0):
    """Re-bucket a resident sharded window from one placement to another.

    One all_to_all + per-shard canonical re-merge (see ``_reshard_impl``);
    handles shard-count changes in both directions by running the
    exchange over max(D_old, D_new) devices (growing windows are padded
    with empty slices first; shrinking ones are truncated after — shards
    ≥ D_new receive nothing by construction since owners are < D_new).
    Edge-preserving except for the counted per-shard capacity clip (a
    shard asked to own more than its E-capacity drops the oldest).

    Returns ``(new_state, new_mesh)`` with the state placed on a
    D_new-device mesh. This is the control-plane path behind
    ``DistributedStreamingEngine.reshard_to`` and the elastic checkpoint
    restore; a placement change recompiles downstream programs — the
    expected cost of a topology event.
    """
    D_old = int(state.exchange_drops.shape[0])
    D_new = new_placement.num_shards
    if old_placement.num_shards != D_old:
        raise ValueError(
            f"old placement covers {old_placement.num_shards} shards; "
            f"state has {D_old}")
    if old_placement.node_capacity != new_placement.node_capacity:
        raise ValueError("placements disagree on node_capacity")
    Dm = max(D_old, D_new)
    if mesh is None:
        mesh = window_mesh(Dm, axis_name=axis_name)
    elif mesh.devices.size != Dm:
        raise ValueError(
            f"reshard mesh must span max(D_old, D_new) = {Dm} devices "
            f"(got {mesh.devices.size})")
    if D_old < Dm:
        state = _pad_shards(state, Dm - D_old)
    state = jax.device_put(
        state, NamedSharding(mesh, P(axis_name)))
    new_state = _reshard_impl(state, mesh=mesh, axis_name=axis_name,
                              placement=new_placement,
                              bias_scale=bias_scale)
    if D_new < Dm:
        new_state = jax.device_get(new_state)
        new_state = jax.tree.map(lambda a: jnp.asarray(a[:D_new]), new_state)
    new_mesh = mesh if Dm == D_new else window_mesh(D_new,
                                                    axis_name=axis_name)
    new_state = jax.device_put(
        new_state, NamedSharding(new_mesh, P(axis_name)))
    return new_state, new_mesh


def reshard_host(state: ShardedWindowState, new_placement: Placement,
                 bias_scale: float = 1.0) -> ShardedWindowState:
    """Numpy mirror of ``reshard``'s canonical merge (no device mesh).

    The elastic checkpoint restore path (train/checkpoint.py): a window
    saved at 8 shards must restore on a 2-device host, where the
    max(D_old, D_new)-device exchange cannot run. Per new shard: old
    shards' owned edges concatenated in old-shard-id order (position
    preserved), one stable ts sort, clip keeping the newest E — the exact
    receiver rule of ``_reshard_impl``, so device and host reshards agree
    bitwise (tested in tests/test_reshard_checkpoint.py).
    """
    w = state.window
    src = np.asarray(w.index.store.src)      # [D_old, E]
    dst = np.asarray(w.index.store.dst)
    ts = np.asarray(w.index.store.ts)
    n = np.asarray(w.index.store.num_edges)  # [D_old]
    D_old, E = src.shape
    D_new = new_placement.num_shards
    nc = new_placement.node_capacity

    owners = [new_placement.owner_np(src[s][:n[s]]) for s in range(D_old)]
    windows, xdrops = [], np.zeros(D_new, np.int64)
    for d in range(D_new):
        parts = [(src[s][:n[s]][owners[s] == d],
                  dst[s][:n[s]][owners[s] == d],
                  ts[s][:n[s]][owners[s] == d]) for s in range(D_old)]
        csrc = np.concatenate([p[0] for p in parts])
        cdst = np.concatenate([p[1] for p in parts])
        cts = np.concatenate([p[2] for p in parts])
        order = np.argsort(cts, kind="stable")
        csrc, cdst, cts = csrc[order], cdst[order], cts[order]
        overflow = max(len(cts) - E, 0)
        xdrops[d] = overflow
        csrc, cdst, cts = csrc[overflow:], cdst[overflow:], cts[overflow:]
        cnt = len(cts)
        store = EdgeStore(
            src=jnp.asarray(np.pad(csrc, (0, E - cnt),
                                   constant_values=nc), jnp.int32),
            dst=jnp.asarray(np.pad(cdst, (0, E - cnt)), jnp.int32),
            ts=jnp.asarray(np.pad(cts, (0, E - cnt),
                                  constant_values=TS_PAD), jnp.int32),
            num_edges=jnp.asarray(cnt, jnp.int32))
        index = build_index(store, nc, bias_scale)
        t_now = jnp.asarray(int(np.asarray(w.t_now).max()), jnp.int32)
        delta = jnp.asarray(int(np.asarray(w.window).max()), jnp.int32)
        z = lambda v: jnp.asarray(v, jnp.int32)
        windows.append(WindowState(
            index=index, t_now=t_now, window=delta,
            ingested=z(int(np.asarray(w.ingested).sum()) if d == 0 else 0),
            late_drops=z(int(np.asarray(w.late_drops).sum())
                         if d == 0 else 0),
            overflow_drops=z(int(np.asarray(w.overflow_drops).sum())
                             if d == 0 else 0)))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *windows)
    old_x = int(np.asarray(state.exchange_drops).sum())
    xd = xdrops.astype(np.int64)
    xd[0] += old_x
    return ShardedWindowState(window=stacked,
                              exchange_drops=jnp.asarray(xd, jnp.int32))
