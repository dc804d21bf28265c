"""Streaming ingestion and sliding-window management (paper §2.6).

The active window W(t) = {e : t − Δ ≤ t_e ≤ t}. Each incoming batch:

1. is ordered by timestamp (GPU radix sort in the paper),
2. advances t to max(t, batch max ts),
3. drops batch edges older than t − Δ ("too late", no retraction),
4. evicts the store prefix older than t − Δ (prefix drop — the payoff of the
   timestamp-sorted shared store),
5. merges the surviving store and the batch into the new store and
   bulk-rebuilds the dual index (paper: reconstruction over incremental
   mutation).

Steps (1)-(5) run as one stable sort of store ++ batch keyed by
timestamp, with evicted, late and padding edges keyed last (DESIGN.md §4):
store edges come first in the input, so equal timestamps keep the two-run
merge order — surviving store edges first, then batch edges in arrival
order. The seed path (sort the batch, shift out the evicted prefix, then a
global argsort plus gathers) is kept as ``ingest_sort``, the equivalence
reference; both produce byte-identical ``WindowState``s (tested in
tests/test_streaming_merge.py).

The public ``ingest`` donates the incoming ``WindowState`` (``jax.jit``
``donate_argnums``), so the window advances in place: XLA aliases the old
store/index buffers into the new ones instead of reallocating ~10 arrays of
edge capacity per batch. Callers must treat the passed-in state as consumed
(every in-repo caller already reassigns ``state = ingest(state, ...)``).

Everything is static-shape: the store is capacity-padded; on overflow the
*oldest* edges are dropped (the window semantics make this the only
reasonable degradation) and the event is counted in ``overflow_drops``.

The unjitted ``ingest_impl`` body is shard-reusable: the node-partitioned
sliding window (repro/distributed/streaming_shard.py, DESIGN.md §12) runs
it per shard under ``shard_map`` against each shard's slice of the store,
passing the globally agreed ``watermark`` so eviction stays causally
consistent across shards.

The advance is a store-level stage (``_advance_store``), so the same math
can advance a **bare store without a dual index**: ``TsView`` /
``advance_view`` keep a replicated timestamp-view of the *global* window —
just the (src, dst, ts) columns, byte-identical to the single-device store
— which the sharded serving layer (DESIGN.md §13) uses as its start
directory for global start-edge draws while the dual indexes stay
node-partitioned.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.alias import AliasTables, TableSpec, build_tables, update_tables
from repro.core.edge_store import TS_PAD, EdgeBatch, EdgeStore
from repro.core.temporal_index import (
    FULL_VIEWS,
    IndexViews,
    TemporalIndex,
    build_index,
    empty_index,
    fit_index,
)
from repro.obs.tracing import scope


class WindowState(NamedTuple):
    index: TemporalIndex
    t_now: jax.Array          # int32: max timestamp seen
    window: jax.Array         # int32: Δ
    ingested: jax.Array       # int64-ish running counters (int32 here)
    late_drops: jax.Array
    overflow_drops: jax.Array
    # alias/radix bias tables (DESIGN.md §17), carried beside pexp/plin and
    # maintained incrementally by ingest when a TableSpec is passed; None
    # (an empty pytree subtree) when table bias is off.
    tables: Optional[AliasTables] = None


@partial(jax.jit, static_argnames=("edge_capacity", "node_capacity",
                                   "window", "bias_scale", "table", "views"))
def init_window(edge_capacity: int, node_capacity: int, window: int,
                bias_scale: float = 1.0,
                table: Optional[TableSpec] = None,
                views: IndexViews = FULL_VIEWS) -> WindowState:
    """An empty window whose index holds ``views`` (the index of an empty
    store is written out, so no sort is compiled)."""
    index = empty_index(edge_capacity, node_capacity, views)
    tables = build_tables(index, table) if table is not None else None
    # distinct scalar buffers: donation (ingest donate_argnums) rejects a
    # state whose fields alias one another
    def z():
        return jnp.asarray(0, jnp.int32)
    return WindowState(index=index, t_now=z(),
                       window=jnp.asarray(window, jnp.int32),
                       ingested=z(), late_drops=z(), overflow_drops=z(),
                       tables=tables)


def fit_window(state: WindowState, views: IndexViews) -> WindowState:
    """``state`` with its index fitted to ``views`` (``fit_index``): what
    an engine does to a state handed to it before its programs see it."""
    return state._replace(index=fit_index(state.index, views))


# ---------------------------------------------------------------------------
# The window advance (steps 1-5 of the module docstring) on a bare store
# ---------------------------------------------------------------------------


class _Advance(NamedTuple):
    store: EdgeStore        # the advanced, capacity-clipped store
    t_now: jax.Array
    late: jax.Array         # batch edges dropped as older than t − Δ
    overflow: jax.Array     # oldest merged edges clipped to fit capacity
    evict_to: jax.Array     # the old store's prefix [0, evict_to) left
    bkeep: jax.Array        # bool[B]: batch edges that entered the window
    merged_src: jax.Array   # int32[E+B]: src of the merged run, pre-clip


def _advance_store(store: EdgeStore, t_prev, window, batch: EdgeBatch,
                   node_capacity: int, watermark=None) -> _Advance:
    """Advance a ts-sorted store by one batch.

    Steps (1)-(5) are one stable sort: store ++ batch, keyed by timestamp,
    with evicted store edges, late batch edges and padding keyed TS_PAD so
    they sort last. Store edges precede batch edges in the input, so ties
    within a timestamp keep the two-run merge rule — store first, then the
    batch in arrival order — and the first ``keep_n + bn`` positions are
    exactly that merge. The clip to capacity keeps the newest E of them.
    On a TPU this sort (src and dst ride as payloads) is far cheaper than
    rank searches and edge-capacity scatters: both move data with random
    single-element accesses, which the chip serves slowly.

    ``watermark`` (optional int32 scalar) is an externally agreed lower
    bound on the new ``t_now``. A node-partitioned window (DESIGN.md §12)
    passes the max batch timestamp *across all shards* here so every shard
    evicts against the same cutoff t − Δ even when the locally received
    batch slice is older than the global maximum — the eviction watermark
    protocol that keeps sharded windows causally consistent.

    Store-level on purpose (no ``WindowState``): the replicated ts-view
    advance (``advance_view``) runs it with no dual index.
    """
    E = store.capacity
    B = batch.src.shape[0]

    # (1)-(2) advance time to the newest valid batch edge
    bvalid = jnp.arange(B, dtype=jnp.int32) < batch.count
    t_now = jnp.maximum(t_prev, jnp.max(jnp.where(bvalid, batch.ts,
                                                  -TS_PAD)))
    if watermark is not None:
        t_now = jnp.maximum(t_now, watermark)
    cutoff = t_now - window

    # (3) late batch edges; (4) the store prefix older than the cutoff
    bkeep = bvalid & (batch.ts >= cutoff)
    late = jnp.sum((bvalid & ~bkeep).astype(jnp.int32))
    iota = jnp.arange(E, dtype=jnp.int32)
    skeep = (iota < store.num_edges) & (store.ts >= cutoff)
    keep_n = jnp.sum(skeep.astype(jnp.int32))
    bn = jnp.sum(bkeep.astype(jnp.int32))

    # (5) merge the survivors with the kept batch edges
    mts, msrc, mdst = jax.lax.sort(
        (jnp.concatenate([jnp.where(skeep, store.ts, TS_PAD),
                          jnp.where(bkeep, batch.ts, TS_PAD)]),
         jnp.concatenate([store.src, batch.src]),
         jnp.concatenate([store.dst, batch.dst])),
        num_keys=1, is_stable=True)

    # on overflow keep the NEWEST E edges: shift the run left by `overflow`
    total = keep_n + bn
    overflow = jnp.maximum(total - E, 0)
    n_after = jnp.minimum(total, E)
    live = iota < n_after

    def clip(x):
        return jax.lax.dynamic_slice(x, (overflow,), (E,))

    new_store = EdgeStore(
        src=jnp.where(live, clip(msrc), node_capacity),
        dst=jnp.where(live, clip(mdst), 0),
        ts=jnp.where(live, clip(mts), TS_PAD),
        num_edges=n_after.astype(jnp.int32))
    return _Advance(store=new_store, t_now=t_now, late=late,
                    overflow=overflow, evict_to=store.num_edges - keep_n,
                    bkeep=bkeep, merged_src=msrc)


def _finalize(state: WindowState, new_store: EdgeStore, t_now, late,
              overflow, batch_count, node_capacity: int,
              bias_scale: float, views: IndexViews) -> WindowState:
    """Rebuild the dual index's core and ``views`` over the advanced store;
    bump the counters."""
    index = build_index(new_store, node_capacity, bias_scale, views)
    return WindowState(
        index=index, t_now=t_now, window=state.window,
        ingested=state.ingested + batch_count,
        late_drops=state.late_drops + late,
        overflow_drops=state.overflow_drops + overflow,
    )


def _dirty_nodes(state: WindowState, batch: EdgeBatch, adv: _Advance,
                 node_capacity: int) -> jax.Array:
    """bool[N] mask of nodes whose neighborhood region changed this advance.

    Exactly three ways a node's region content can change (the stable
    merge + stable src sort keep every untouched node's region sequence
    identical, merely shifted): it gained a kept batch edge, it lost an
    edge to prefix eviction, or it lost an edge to the overflow clip of
    the merged run. The alias-table incremental update rebuilds precisely
    these nodes; tests/test_alias.py property-checks the rule against
    from-scratch rebuilds.
    """
    nc = node_capacity
    E = state.index.store.capacity
    dirty = jnp.zeros((nc,), bool)
    dirty = dirty.at[jnp.where(adv.bkeep, batch.src, nc)].set(
        True, mode="drop")

    old_src = state.index.store.src
    evicted = jnp.arange(E, dtype=jnp.int32) < adv.evict_to
    dirty = dirty.at[jnp.where(evicted, old_src, nc)].set(True, mode="drop")

    msrc = adv.merged_src
    clipped = jnp.arange(msrc.shape[0], dtype=jnp.int32) < adv.overflow
    dirty = dirty.at[jnp.where(clipped, msrc, nc)].set(True, mode="drop")
    return dirty


def ingest_impl(state: WindowState, batch: EdgeBatch, node_capacity: int,
                bias_scale: float = 1.0, watermark=None,
                table: Optional[TableSpec] = None,
                views: IndexViews = FULL_VIEWS) -> WindowState:
    """Window advance + index rebuild (unjitted body; see ``ingest``).

    ``watermark`` is the sharded-window eviction hook (see
    ``_advance_store``); single-device callers leave it ``None``.

    ``table`` (static TableSpec) switches on alias-table maintenance:
    only the dirty nodes (see ``_dirty_nodes``) are rebuilt against the
    new index; clean nodes copy their old table content positionally.
    The spec must be passed on *every* ingest of a table-carrying state —
    omitting it drops the tables from the returned state.

    ``views`` (static ``IndexViews``) names the optional index views the
    rebuild makes beside the core; the default is all of them.

    The store advance and the table update carry the ``advance`` scope,
    the rebuild ``index`` (obs/tracing.py).
    """
    with scope("advance"):
        adv = _advance_store(state.index.store, state.t_now, state.window,
                             batch, node_capacity, watermark=watermark)
    new = _finalize(state, adv.store, adv.t_now, adv.late, adv.overflow,
                    batch.count, node_capacity, bias_scale, views)
    if table is None:
        return new
    with scope("advance"):
        if state.tables is None:
            tables = build_tables(new.index, table)
        else:
            dirty = _dirty_nodes(state, batch, adv, node_capacity)
            tables = update_tables(new.index, table,
                                   old_starts=state.index.node_starts,
                                   old_tables=state.tables, dirty=dirty)
    return new._replace(tables=tables)


def _ingest_sort_impl(state: WindowState, batch: EdgeBatch, node_capacity: int,
                      bias_scale: float = 1.0,
                      views: IndexViews = FULL_VIEWS) -> WindowState:
    """Seed reference path, written independently of ``_advance_store``:
    sort the batch, drop late edges, shift out the evicted store prefix,
    then concat + global stable argsort + gathers, and clip to capacity."""
    store = state.index.store
    E = store.capacity
    B = batch.src.shape[0]

    bvalid = jnp.arange(B, dtype=jnp.int32) < batch.count
    bts = jnp.where(bvalid, batch.ts, TS_PAD)
    border = jnp.argsort(bts).astype(jnp.int32)
    bsrc, bdst, bts = batch.src[border], batch.dst[border], bts[border]
    last = jnp.where(batch.count > 0,
                     bts[jnp.clip(batch.count - 1, 0, B - 1)], -TS_PAD)
    t_now = jnp.maximum(state.t_now, last)
    cutoff = t_now - state.window

    blate = bvalid & (bts < cutoff)
    bkeep = bvalid & ~blate
    bperm = jnp.argsort(jnp.where(bkeep, 0, 1), stable=True).astype(jnp.int32)
    bsrc, bdst, bts = bsrc[bperm], bdst[bperm], bts[bperm]
    bn = jnp.sum(bkeep.astype(jnp.int32))
    bts = jnp.where(jnp.arange(B) < bn, bts, TS_PAD)

    evict_to = jnp.searchsorted(store.ts, cutoff, side="left").astype(jnp.int32)
    evict_to = jnp.minimum(evict_to, store.num_edges)
    keep_n = store.num_edges - evict_to
    idx = jnp.clip(jnp.arange(E, dtype=jnp.int32) + evict_to, 0, E - 1)
    live = jnp.arange(E, dtype=jnp.int32) < keep_n
    ssrc = jnp.where(live, store.src[idx], node_capacity)
    sdst = jnp.where(live, store.dst[idx], 0)
    sts = jnp.where(live, store.ts[idx], TS_PAD)

    msrc = jnp.concatenate([ssrc, bsrc])
    mdst = jnp.concatenate([sdst, bdst])
    mts = jnp.concatenate([sts, bts])
    morder = jnp.argsort(mts).astype(jnp.int32)
    msrc, mdst, mts = msrc[morder], mdst[morder], mts[morder]

    total = keep_n + bn
    overflow = jnp.maximum(total - E, 0)
    idx2 = jnp.clip(jnp.arange(E, dtype=jnp.int32) + overflow, 0, E + B - 1)
    live2 = jnp.arange(E, dtype=jnp.int32) < jnp.minimum(total, E)
    new_store = EdgeStore(
        src=jnp.where(live2, msrc[idx2], node_capacity),
        dst=jnp.where(live2, mdst[idx2], 0),
        ts=jnp.where(live2, mts[idx2], TS_PAD),
        num_edges=jnp.minimum(total, E).astype(jnp.int32))
    return _finalize(state, new_store, t_now, jnp.sum(blate.astype(jnp.int32)),
                     overflow, batch.count, node_capacity, bias_scale, views)


# Public entry points. ``ingest`` (merge path) donates the old WindowState so
# XLA advances the window without reallocating the edge store + index arrays;
# ``ingest_sort`` is the non-donating seed reference kept for equivalence
# tests and old-vs-new benchmarking.
ingest = partial(jax.jit,
                 static_argnames=("node_capacity", "bias_scale", "table",
                                  "views"),
                 donate_argnums=(0,))(ingest_impl)
ingest_merge = ingest
ingest_sort = partial(jax.jit,
                      static_argnames=("node_capacity", "bias_scale",
                                       "views"))(
    _ingest_sort_impl)

# Non-donating merge ingest for the serving snapshot double-buffer
# (serve/snapshot.py, DESIGN.md §11): the *old* WindowState must stay
# readable while walk queries run against it and the next window builds
# concurrently, so the input cannot be donated. Same math as ``ingest``,
# byte-identical output; costs one fresh store+index allocation per call.
ingest_nodonate = partial(
    jax.jit, static_argnames=("node_capacity", "bias_scale", "table",
                              "views"))(
    ingest_impl)


# ---------------------------------------------------------------------------
# Replicated timestamp-view: the global window's (src, dst, ts) columns
# without a dual index (sharded serving's start directory, DESIGN.md §13)
# ---------------------------------------------------------------------------


class TsView(NamedTuple):
    """A bare timestamp-sorted store plus the window clock — no dual index.

    Advanced through the exact single-device merge stages, so ``store`` is
    **byte-identical to the single-device window's store** for the same
    batch stream. The sharded serving layer replicates one of these next to
    the node-partitioned window: global start-edge draws (positions in the
    global ts view) resolve locally on every shard, while the ~10-array
    dual indexes — the expensive part — stay sharded. Memory cost is 3
    int32 columns of global edge capacity per replica.
    """

    store: EdgeStore
    t_now: jax.Array          # int32: max timestamp seen
    window: jax.Array         # int32: Δ


def init_view(edge_capacity: int, node_capacity: int, window: int) -> TsView:
    from repro.core.edge_store import empty_store
    return TsView(store=empty_store(edge_capacity, node_capacity),
                  t_now=jnp.asarray(0, jnp.int32),
                  window=jnp.asarray(window, jnp.int32))


def advance_view_impl(view: TsView, batch: EdgeBatch, node_capacity: int,
                      watermark=None) -> TsView:
    """Advance a ts-view by one batch: the window pipeline minus the index
    build. Bit-identical store/t_now trajectory to ``ingest_impl``."""
    adv = _advance_store(view.store, view.t_now, view.window, batch,
                         node_capacity, watermark=watermark)
    return TsView(store=adv.store, t_now=adv.t_now, window=view.window)


# Non-donating on purpose: the serving snapshot double-buffer keeps the old
# view readable while the next one builds (same reasoning as
# ``ingest_nodonate``).
advance_view = partial(jax.jit, static_argnames=("node_capacity",))(
    advance_view_impl)
