"""Snapshot double-buffer: serve walks against a consistent window while
the next ingest step builds (DESIGN.md §11).

The streaming engine's donating ``ingest`` consumes its input state — the
right call in a pure replay loop, and exactly wrong for serving, where
in-flight queries must keep reading the window they were admitted
against. The ``SnapshotManager`` therefore advances the window through
the **non-donating** merge ingest (``window.ingest_nodonate``, same math,
byte-identical output):

* ``current`` — the front buffer. Immutable from the service's point of
  view; every coalesced batch runs against it.
* ``begin_ingest(batch)`` — dispatches the merge ingest into the back
  buffer and returns immediately (JAX async dispatch): the device builds
  the next window while the host keeps coalescing and dispatching walk
  batches against ``current``.
* ``publish()`` — waits for the back buffer and swaps it in atomically.
  Queries admitted before the swap saw the old window; queries admitted
  after see the new one. No query ever observes a half-ingested state.

Two windows are alive at the swap point — the double-buffer's memory
cost — and the old one is released to the allocator as soon as the last
reference drops.

``ShardedSnapshotManager`` is the same protocol one level up (DESIGN.md
§13): the front buffer is a node-partitioned ``ShardedWindowState`` plus
the replicated ``TsView`` start directory, advanced together through the
non-donating sharded ingest (one all_to_all, pmax-agreed eviction
watermark) and the view merge. ``publish()`` swaps both atomically, so a
coalesced sharded batch never sees the per-shard windows and the start
directory at different versions — the cross-shard consistency the
watermark protocol guarantees within one version. Two sharded windows
(plus two 3-column views) are alive at the swap point.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax

from repro.configs.base import EngineConfig
from repro.core.edge_store import EdgeBatch
from repro.core.temporal_index import FULL_VIEWS, IndexViews
from repro.core.window import (
    TsView,
    WindowState,
    advance_view,
    fit_window,
    ingest_nodonate,
    init_view,
)
from repro.obs.registry import MetricsRegistry, get_registry


class PinnedSnapshot(NamedTuple):
    """A ``(window state, version)`` pair captured at dispatch time.

    The async runtime (DESIGN.md §18) keeps batches in flight across
    ``publish()`` calls: each in-flight batch holds one of these, so the
    device computation it enqueued keeps the exact buffers it launched
    against alive (functional JAX arrays — the swap can't mutate them)
    and its results report the version they were computed at, never the
    version current at harvest time.
    """

    state: WindowState
    version: int


class PinnedShardedSnapshot(NamedTuple):
    """Sharded twin of ``PinnedSnapshot``: the (sharded window, replicated
    ts-view) pair always belongs to ONE published version — pinning them
    together is what keeps an in-flight sharded batch from seeing the
    per-shard windows and the start directory at different versions."""

    state: object
    view: TsView
    version: int


class SnapshotManager:
    """Double-buffered ``WindowState`` for the serving layer.

    ``table`` (a ``core.alias.TableSpec``) opts the buffer into alias-
    table maintenance: every ``begin_ingest`` rebuilds only the nodes
    whose neighborhood region changed (DESIGN.md §17), so the published
    snapshot always carries tables consistent with its window and
    table-bias lane batches can draw O(1) against ``current.tables``.
    The spec must be fixed for the life of the manager — incremental
    maintenance is only valid against tables built under the same spec.

    ``views`` (``temporal_index.IndexViews``) are the optional index views
    every snapshot holds: the initial ``state`` is fitted to them and each
    ingest rebuilds them beside the core.
    """

    def __init__(self, state: WindowState, node_capacity: int,
                 registry: Optional[MetricsRegistry] = None,
                 table=None, views: IndexViews = FULL_VIEWS):
        self.current = fit_window(state, views)
        self.node_capacity = node_capacity
        self.table = table
        self.views = views
        self.registry = registry if registry is not None else get_registry()
        self.version = 0          # bumped at every publish
        self._next: Optional[WindowState] = None

    @property
    def ingest_in_flight(self) -> bool:
        return self._next is not None

    def begin_ingest(self, batch: EdgeBatch) -> None:
        """Start building the next window; ``current`` stays serveable."""
        if self._next is not None:
            raise RuntimeError("an ingest is already in flight; publish() "
                               "or discard() it first")
        self._next = ingest_nodonate(self.current, batch, self.node_capacity,
                                     table=self.table, views=self.views)

    def publish(self) -> WindowState:
        """Wait for the in-flight ingest and swap it in as ``current``."""
        if self._next is None:
            raise RuntimeError("no ingest in flight; call begin_ingest first")
        jax.block_until_ready(self._next.index.ns_order)
        self.current, self._next = self._next, None
        self.version += 1
        self.registry.inc("snapshot_publishes_total", 1,
                          help="serving snapshot buffer swaps")
        return self.current

    def discard(self) -> None:
        """Drop an in-flight ingest without publishing it."""
        self._next = None

    def acquire(self) -> PinnedSnapshot:
        """Pin the current (state, version) pair for an async dispatch."""
        return PinnedSnapshot(self.current, self.version)

    def ingest(self, batch: EdgeBatch) -> WindowState:
        """Synchronous convenience: begin + publish in one call."""
        self.begin_ingest(batch)
        return self.publish()


class ShardedSnapshotManager:
    """Double-buffered node-partitioned window + replicated ts-view.

    The serving front end for ``DistributedStreamingEngine``-style state:
    ``state`` (sharded window slices) and ``view`` (replicated global
    start directory) always belong to the same published version. Batches
    are split D-ways on the batch axis exactly like the engine's ingest;
    the next version builds through ``ingest_sharded_nodonate`` (per-shard
    merge against the pmax-agreed watermark) while the current one keeps
    serving coalesced lane batches.
    """

    def __init__(self, cfg: EngineConfig, batch_capacity: int = 8192, *,
                 mesh=None, num_shards: int = 0, placement=None,
                 registry: Optional[MetricsRegistry] = None):
        from repro.distributed.placement import make_placement
        from repro.distributed.streaming_shard import (
            init_sharded_window,
            window_mesh,
        )
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else window_mesh(
            num_shards or cfg.shard.num_shards)
        self.axis_name = self.mesh.axis_names[0]
        D = self.mesh.devices.size
        self.num_shards = D
        # one placement object routes ingest bucketing AND lane claims, so
        # the window layout and the serving claim rule can never diverge
        self.placement = placement if placement is not None else \
            make_placement(cfg.shard.placement, D, cfg.window.node_capacity,
                           hash_buckets=cfg.shard.hash_buckets)
        # per-shard batch slice: round the capacity up to a D multiple
        self.batch_slice = -(-batch_capacity // D)
        self.batch_capacity = self.batch_slice * D
        self.node_capacity = cfg.window.node_capacity
        self.state = init_sharded_window(
            D, cfg.shard.edge_capacity_per_shard, self.node_capacity,
            int(cfg.window.duration), mesh=self.mesh,
            axis_name=self.axis_name)
        self.view = init_view(cfg.window.edge_capacity, self.node_capacity,
                              int(cfg.window.duration))
        self.registry = registry if registry is not None else get_registry()
        self.version = 0          # bumped at every publish
        self._next: Optional[Tuple[object, TsView]] = None

    @property
    def ingest_in_flight(self) -> bool:
        return self._next is not None

    def begin_ingest(self, batch: EdgeBatch) -> None:
        """Start building the next (sharded window, view) pair; the
        current pair stays serveable until ``publish``."""
        from repro.distributed.streaming_shard import ingest_sharded_nodonate
        if self._next is not None:
            raise RuntimeError("an ingest is already in flight; publish() "
                               "or discard() it first")
        if batch.src.shape[0] != self.batch_capacity:
            raise ValueError(
                f"batch capacity {batch.src.shape[0]} != manager capacity "
                f"{self.batch_capacity} (must be the D-rounded capacity)")
        split = lambda a: a.reshape(self.num_shards, self.batch_slice)
        nstate = ingest_sharded_nodonate(
            self.state, split(batch.src), split(batch.dst), split(batch.ts),
            batch.count, mesh=self.mesh, axis_name=self.axis_name,
            node_capacity=self.node_capacity, shard_cfg=self.cfg.shard,
            placement=self.placement)
        nview = advance_view(self.view, batch, self.node_capacity)
        self._next = (nstate, nview)

    def publish(self):
        """Wait for the in-flight ingest and swap both buffers in."""
        if self._next is None:
            raise RuntimeError("no ingest in flight; call begin_ingest first")
        jax.block_until_ready(self._next[0].window.index.ns_order)
        jax.block_until_ready(self._next[1].store.ts)
        self.state, self.view = self._next
        self._next = None
        self.version += 1
        self.registry.inc("snapshot_publishes_total", 1,
                          help="serving snapshot buffer swaps")
        return self.state

    def discard(self) -> None:
        """Drop an in-flight ingest without publishing it."""
        self._next = None

    def acquire(self) -> PinnedShardedSnapshot:
        """Pin the current (state, view, version) triple for an async
        dispatch — both halves from the same published version."""
        return PinnedShardedSnapshot(self.state, self.view, self.version)

    def ingest(self, batch: EdgeBatch):
        """Synchronous convenience: begin + publish in one call."""
        self.begin_ingest(batch)
        return self.publish()
