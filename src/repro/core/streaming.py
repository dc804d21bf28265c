"""Streaming drivers: chronological batch replay (paper §3.3 regime).

Two drivers over the same merge-based ingest (DESIGN.md §4):

* **Host loop** (`StreamingEngine.replay`) — one ingest dispatch + one walk
  dispatch per batch, with a `block_until_ready` after each so per-stage
  wall-clock timings can be recorded. This is the measurement driver
  (benchmarks Table 4 / Fig. 6 need per-batch stage latencies).

* **Device-resident scan** (`replay_scan` / `StreamingEngine.replay_device`)
  — all K batches are stacked into one device array and the whole
  ingest→rebuild→walk loop runs under a single `jax.lax.scan` with the
  window state donated into the jit. Per-batch statistics (active edges,
  drop counters, walk lengths) are accumulated on-device as scan outputs
  and materialized **once** at the end — zero host round-trips between
  batches. This is the throughput driver: dispatch overhead and host
  synchronization are off the critical path, so sustained ingest bandwidth
  is what the hardware allows.

`ingest_and_walk` is the shared fused step: one jitted program covering
merge-ingest + index rebuild + walk generation, donating the old state.
Each takes the static ``views`` (``temporal_index.IndexViews``) that the
rebuild makes beside the index's core; ``StreamingEngine`` derives them
from its sampler and path (``walk_engine.index_views``), so an index-mode
engine on the grouped path rebuilds the node-ts view alone (DESIGN.md §3).
`ingest_and_walk_donated` additionally consumes the previous round's walk
buffers, and `replay_scan` carries them through the scan, so steady-state
replay reallocates nothing on the walk side either (DESIGN.md §10).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Iterable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (
    EngineConfig,
    SamplerConfig,
    SchedulerConfig,
    WalkConfig,
)
from repro.core.edge_store import EdgeBatch, make_batch, stack_batches
from repro.core.temporal_index import FULL_VIEWS, IndexViews
from repro.core.walk_engine import (
    WalkBuffers,
    WalkResult,
    _generate_walks_impl,
    alloc_walk_buffers,
    generate_walks,
    generate_walks_donated,
    index_views,
)
from repro.core.window import (
    WindowState,
    fit_window,
    ingest,
    ingest_impl,
    ingest_sort,
    init_window,
)
from repro.obs.probes import (
    flush_replay_probes,
    replay_probe_update,
    replay_probe_zeros,
)
from repro.obs.registry import MetricsRegistry, get_registry
from repro.obs.tracing import scope, span


# sample_walks_sharded replicates the index per device; past this size a
# one-time warning points at the node-partitioned engine (DESIGN.md §12).
REPLICATED_INDEX_WARN_BYTES = 256 << 20


@dataclass
class StreamStats:
    ingest_s: List[float] = field(default_factory=list)
    sample_s: List[float] = field(default_factory=list)
    edges_active: List[int] = field(default_factory=list)

    @property
    def cumulative_ingest(self):
        return np.cumsum(self.ingest_s)

    @property
    def cumulative_sample(self):
        return np.cumsum(self.sample_s)


class ReplayStats(NamedTuple):
    """Per-batch statistics of a device-resident replay ([K] arrays).

    Gathered as `lax.scan` outputs — reading them costs one device->host
    transfer for the whole replay, not one per batch.
    """

    edges_active: jax.Array     # int32[K] store population after each batch
    t_now: jax.Array            # int32[K]
    ingested: jax.Array         # int32[K] cumulative counters after each batch
    late_drops: jax.Array       # int32[K]
    overflow_drops: jax.Array   # int32[K]
    mean_len: jax.Array         # float32[K] mean walk length per batch


def _ingest_and_walk_impl(state: WindowState, batch: EdgeBatch,
                          key: jax.Array, node_capacity: int,
                          wcfg: WalkConfig, scfg: SamplerConfig,
                          sched_cfg: SchedulerConfig,
                          bias_scale: float = 1.0,
                          walk_bufs: Optional[WalkBuffers] = None,
                          table=None, views: IndexViews = FULL_VIEWS):
    state = ingest_impl(state, batch, node_capacity, bias_scale,
                        table=table, views=views)
    res = _generate_walks_impl(state.index, key, wcfg, scfg, sched_cfg,
                               buffers=walk_bufs, tables=state.tables)
    return state, res


# Fused step: ingest + rebuild + walk in ONE jitted program, old state
# donated. One dispatch per batch instead of two, and XLA may overlap the
# index rebuild with the first hops of the walk scan. ``table`` (static
# TableSpec) switches on incremental alias-table maintenance + table-bias
# walks (DESIGN.md §17).
ingest_and_walk = partial(
    jax.jit,
    static_argnames=("node_capacity", "wcfg", "scfg", "sched_cfg",
                     "bias_scale", "table", "views"),
    donate_argnums=(0,),
)(_ingest_and_walk_impl)


def _ingest_and_walk_donated_impl(state: WindowState, batch: EdgeBatch,
                                  walk_bufs: WalkBuffers, key: jax.Array,
                                  node_capacity: int, wcfg: WalkConfig,
                                  scfg: SamplerConfig,
                                  sched_cfg: SchedulerConfig,
                                  bias_scale: float = 1.0, table=None,
                                  views: IndexViews = FULL_VIEWS):
    return _ingest_and_walk_impl(state, batch, key, node_capacity, wcfg,
                                 scfg, sched_cfg, bias_scale,
                                 walk_bufs=walk_bufs, table=table,
                                 views=views)


# Fully donated fused step (DESIGN.md §10): both the window state AND the
# previous round's walk buffers are consumed, so a steady-state host loop
# reallocates nothing per batch — chain with
# ``bufs = WalkBuffers(res.nodes, res.times)`` between calls.
ingest_and_walk_donated = partial(
    jax.jit,
    static_argnames=("node_capacity", "wcfg", "scfg", "sched_cfg",
                     "bias_scale", "table", "views"),
    donate_argnums=(0, 2),
)(_ingest_and_walk_donated_impl)


def _replay_scan_impl(state: WindowState, batches: EdgeBatch, key: jax.Array,
                      node_capacity: int, wcfg: WalkConfig,
                      scfg: SamplerConfig, sched_cfg: SchedulerConfig,
                      bias_scale: float = 1.0, with_probes: bool = False,
                      table=None, views: IndexViews = FULL_VIEWS):
    """Shared body of ``replay_scan`` / ``replay_scan_probed``.

    ``with_probes`` threads an obs probe vector (obs/probes.py) through
    the scan carry as an *extra* leaf: the walk/RNG dataflow is untouched
    (probe updates are pure ``at[].add`` on counters the stats already
    compute), and when False the traced program is exactly the historical
    one — no probe leaf exists to be DCE'd.

    The carried index holds the core and ``views`` and nothing else: the
    state is fitted to them first (``fit_window``; the engine has already
    done so, which makes this a no-op), so every batch rebuilds only them.
    """
    state = fit_window(state, views)

    def step(carry, batch):
        if with_probes:
            st, k, bufs, _, pv = carry
        else:
            st, k, bufs, _ = carry
        k, sub = jax.random.split(k)
        st2, res = _ingest_and_walk_impl(st, batch, sub, node_capacity,
                                         wcfg, scfg, sched_cfg, bias_scale,
                                         walk_bufs=bufs, table=table,
                                         views=views)
        stats = ReplayStats(
            edges_active=st2.index.num_edges,
            t_now=st2.t_now,
            ingested=st2.ingested,
            late_drops=st2.late_drops,
            overflow_drops=st2.overflow_drops,
            mean_len=jnp.mean(res.lengths.astype(jnp.float32)),
        )
        # walk buffers ride the scan carry: batch k+1's walks are written
        # into batch k's storage (DESIGN.md §10)
        nbufs = WalkBuffers(res.nodes, res.times)
        if with_probes:
            pv = replay_probe_update(
                pv,
                ingested_delta=st2.ingested - st.ingested,
                late_delta=st2.late_drops - st.late_drops,
                overflow_delta=st2.overflow_drops - st.overflow_drops,
                lengths=res.lengths, lane_steps=res.lane_steps,
                loop_steps=res.steps)
            return (st2, k, nbufs, res.lengths, pv), stats
        return (st2, k, nbufs, res.lengths), stats

    lengths0 = jnp.zeros((wcfg.num_walks,), jnp.int32)
    carry0 = [state, key, alloc_walk_buffers(wcfg), lengths0]
    if with_probes:
        carry0.append(replay_probe_zeros())
    with scope("replay"):
        carry, stats = jax.lax.scan(step, tuple(carry0), batches)
    walks = WalkResult(nodes=carry[2].nodes, times=carry[2].times,
                       lengths=carry[3], stats=None)
    if with_probes:
        return carry[0], stats, walks, carry[4]
    return carry[0], stats, walks


@partial(jax.jit,
         static_argnames=("node_capacity", "wcfg", "scfg", "sched_cfg",
                          "bias_scale", "table", "views"),
         donate_argnums=(0,))
def replay_scan(state: WindowState, batches: EdgeBatch, key: jax.Array,
                node_capacity: int, wcfg: WalkConfig, scfg: SamplerConfig,
                sched_cfg: SchedulerConfig, bias_scale: float = 1.0,
                table=None, views: IndexViews = FULL_VIEWS):
    """Replay K stacked batches fully on device under `jax.lax.scan`.

    ``batches`` holds [K, B_cap] arrays (see edge_store.stack_batches).
    Returns ``(final_state, ReplayStats, final_walks)`` — all still on
    device; the caller decides when to synchronize (a single
    block_until_ready at the end of the replay is the intended pattern).
    ``final_walks`` is the last batch's WalkResult, read straight out of
    the carried walk buffers — it is what the distributed replay
    (repro/distributed/streaming_shard.py, DESIGN.md §12) must reproduce
    bit-for-bit, and costs nothing to expose.
    """
    return _replay_scan_impl(state, batches, key, node_capacity, wcfg,
                             scfg, sched_cfg, bias_scale, with_probes=False,
                             table=table, views=views)


@partial(jax.jit,
         static_argnames=("node_capacity", "wcfg", "scfg", "sched_cfg",
                          "bias_scale", "table", "views"),
         donate_argnums=(0,))
def replay_scan_probed(state: WindowState, batches: EdgeBatch,
                       key: jax.Array, node_capacity: int, wcfg: WalkConfig,
                       scfg: SamplerConfig, sched_cfg: SchedulerConfig,
                       bias_scale: float = 1.0, table=None,
                       views: IndexViews = FULL_VIEWS):
    """``replay_scan`` plus an obs probe vector (DESIGN.md §16).

    Returns ``(final_state, ReplayStats, final_walks, probes)`` with
    ``probes`` an int32[NUM_REPLAY_PROBES] device vector accumulated
    across the scan — flush it with ``obs.flush_replay_probes`` at the
    same host sync that reads ``stats``. Walks and stats are bit-identical
    to ``replay_scan`` (pinned by tests/test_obs_probes.py); keeping this
    a separate jit entry point leaves the uninstrumented program
    byte-unchanged.
    """
    return _replay_scan_impl(state, batches, key, node_capacity, wcfg,
                             scfg, sched_cfg, bias_scale, with_probes=True,
                             table=table, views=views)


class StreamingEngine:
    """Tempest's end-to-end loop: ingest -> rebuild -> walk.

    ``ingest_impl`` selects the window-advance algorithm: ``"merge"`` (the
    rank-based two-run merge, default) or ``"sort"`` (the seed's global
    argsort, kept as the equivalence/benchmark reference).

    The window's index holds only the views the engine's walks can read
    (``walk_engine.index_views`` of its sampler and path). ``state`` may be
    assigned from outside (a bulk load built with every view, say): each
    entry point fits it to the engine's views before any program sees it.
    """

    def __init__(self, cfg: EngineConfig, batch_capacity: int,
                 ingest_impl: str = "merge",
                 registry: Optional[MetricsRegistry] = None,
                 probes: bool = True):
        if ingest_impl not in ("merge", "sort"):
            raise ValueError(f"unknown ingest_impl {ingest_impl!r}")
        self.cfg = cfg
        self.batch_capacity = batch_capacity
        self._ingest = ingest if ingest_impl == "merge" else ingest_sort
        # alias-table spec (DESIGN.md §17): bias='table' configs maintain
        # per-node alias tables incrementally through every ingest
        from repro.core.alias import spec_from_sampler
        self._table = spec_from_sampler(cfg.sampler)
        if self._table is not None and ingest_impl == "sort":
            raise ValueError(
                "alias-table maintenance (bias='table') requires the merge "
                "ingest path; the 'sort' reference path does not thread "
                "table state")
        self._views = index_views(cfg.sampler, cfg.scheduler.path)
        self.state: WindowState = init_window(
            cfg.window.edge_capacity, cfg.window.node_capacity,
            int(cfg.window.duration), table=self._table, views=self._views)
        self.key = jax.random.PRNGKey(cfg.seed)
        self.stats = StreamStats()
        # obs integration (DESIGN.md §16): every driver publishes into the
        # registry; ``probes=False`` pins replay_device to the historical
        # uninstrumented program (used by the byte-identity tests).
        self.registry = registry if registry is not None else get_registry()
        self.probes = probes
        # window-counter baselines: state counters are cumulative, the
        # registry wants monotonic deltas
        self._ingested_seen = 0
        self._late_seen = 0
        self._overflow_seen = 0
        self._rebuilt_seen = 0
        # walk-buffer pool for sample_walks_donated, keyed by (W, L)
        self._walk_bufs: dict = {}
        # sequence number of the last replay_device / sample_walks* call,
        # carried by each stage span's profiler annotation
        self._seq = 0
        self._warned_replicated_index = False

    def _fit_state(self) -> WindowState:
        """Fit ``state`` to the engine's index views (``fit_window``): free
        when it already holds them, as every state the engine made does."""
        self.state = fit_window(self.state, self._views)
        return self.state

    def _publish_index_columns(self) -> None:
        self.registry.set_gauge(
            "index_edge_columns", self.state.index.views.edge_columns,
            help="edge-sized columns the window's index rebuild writes")

    def _publish_window(self) -> None:
        """Refresh window gauges + drop deltas from the synced state."""
        from repro.obs.registry import count_drop
        reg = self.registry
        num_edges = int(self.state.index.num_edges)
        reg.set_gauge("window_edges_active", num_edges,
                      help="edges resident in the temporal window")
        self._publish_index_columns()
        reg.set_gauge("window_t_now", int(self.state.t_now),
                      help="watermark timestamp of the window")
        reg.set_gauge("window_occupancy",
                      num_edges / self.cfg.window.edge_capacity,
                      help="window fill fraction (edges_active / capacity)")
        ingested = int(self.state.ingested)
        late = int(self.state.late_drops)
        overflow = int(self.state.overflow_drops)
        reg.inc("stream_edges_ingested_total",
                max(0, ingested - self._ingested_seen),
                labels={"driver": "host"},
                help="edges delivered into the window")
        count_drop(reg, "ingest_late", max(0, late - self._late_seen))
        count_drop(reg, "window_overflow",
                   max(0, overflow - self._overflow_seen))
        self._ingested_seen = ingested
        self._late_seen = late
        self._overflow_seen = overflow
        self._publish_tables()

    def _publish_tables(self) -> None:
        """Alias-table maintenance counters (DESIGN.md §17): how many node
        rebuilds the incremental update actually performed — the work a
        full per-batch rebuild would multiply by the window's node count."""
        if self.state.tables is None:
            return
        rebuilt = int(self.state.tables.rebuilt)
        self.registry.inc("alias_nodes_rebuilt_total",
                          max(0, rebuilt - self._rebuilt_seen),
                          help="alias-table node rebuilds performed by "
                               "incremental window maintenance")
        self._rebuilt_seen = rebuilt

    def _publish_window_from_replay(self, stats: ReplayStats) -> None:
        """Window gauges after a device replay; drop/ingest counters were
        already published from the probe vector, so only the cumulative
        baselines advance here."""
        last = np.asarray(stats.edges_active)
        if last.size == 0:
            return
        reg = self.registry
        edges = int(last[-1])
        reg.set_gauge("window_edges_active", edges,
                      help="edges resident in the temporal window")
        self._publish_index_columns()
        reg.set_gauge("window_t_now", int(np.asarray(stats.t_now)[-1]),
                      help="watermark timestamp of the window")
        reg.set_gauge("window_occupancy",
                      edges / self.cfg.window.edge_capacity,
                      help="window fill fraction (edges_active / capacity)")
        self._ingested_seen = int(np.asarray(stats.ingested)[-1])
        self._late_seen = int(np.asarray(stats.late_drops)[-1])
        self._overflow_seen = int(np.asarray(stats.overflow_drops)[-1])
        self._publish_tables()

    def ingest_batch(self, src, dst, ts) -> None:
        batch = make_batch(src, dst, ts, capacity=self.batch_capacity)
        t0 = time.perf_counter()
        with span("ingest_merge", self.registry):
            state = self._fit_state()
            if self._table is not None:
                self.state = self._ingest(state, batch,
                                          self.cfg.window.node_capacity,
                                          table=self._table,
                                          views=self._views)
            else:
                self.state = self._ingest(state, batch,
                                          self.cfg.window.node_capacity,
                                          views=self._views)
            jax.block_until_ready(self.state.index.ns_order)
        self.stats.ingest_s.append(time.perf_counter() - t0)
        self.stats.edges_active.append(int(self.state.index.num_edges))
        self.registry.inc("stream_batches_total", 1,
                          labels={"driver": "host"},
                          help="batches replayed through the streaming "
                               "drivers")
        self._publish_window()

    def _call_args(self) -> dict:
        """Profiler-annotation arguments of the next call's stage spans."""
        self._seq += 1
        return {"seq": self._seq}

    def sample_walks(self, wcfg: WalkConfig,
                     collect_stats: bool = False):
        args = self._call_args()
        with span("walks.dispatch", self.registry, args=args):
            self.key, sub = jax.random.split(self.key)
            t0 = time.perf_counter()
            res = generate_walks(self._fit_state().index, sub, wcfg,
                                 self.cfg.sampler, self.cfg.scheduler,
                                 collect_stats=collect_stats,
                                 tables=self.state.tables)
        self._finish_sample(res, t0, path="host", args=args)
        return res

    def sample_walks_donated(self, wcfg: WalkConfig):
        """Like ``sample_walks`` but reuses a per-shape walk-buffer pool
        through ``generate_walks_donated`` (DESIGN.md §10): steady-state
        sampling allocates nothing on the walk side.

        Caveat: the *previous* WalkResult returned for the same
        (num_walks, max_length) shape is consumed by this call — copy it
        (``np.asarray``) first if it must outlive the next round.
        """
        shape_key = (wcfg.num_walks, wcfg.max_length)
        args = self._call_args()
        with span("walks.dispatch", self.registry, args=args):
            bufs = self._walk_bufs.pop(shape_key, None)
            if bufs is None:
                bufs = alloc_walk_buffers(wcfg)
            self.key, sub = jax.random.split(self.key)
            t0 = time.perf_counter()
            res = generate_walks_donated(self._fit_state().index, sub, bufs,
                                         wcfg,
                                         self.cfg.sampler,
                                         self.cfg.scheduler,
                                         tables=self.state.tables)
        self._finish_sample(res, t0, path="donated", args=args)
        self._walk_bufs[shape_key] = WalkBuffers(res.nodes, res.times)
        return res

    def sample_walks_sharded(self, wcfg: WalkConfig, mesh=None):
        """Device-parallel sampling: the walk axis sharded over the mesh
        (defaults to all devices) against the replicated window index —
        see repro.distributed.walks (DESIGN.md §10).

        Memory cost: the **full dual index is replicated onto every
        device** of the mesh — a D-device mesh holds D copies of the
        store + index arrays (~10 arrays of edge capacity each), so total
        index memory is D× the single-device footprint and the window must
        still fit on ONE chip. That is the right trade only while it does;
        once the index passes ``REPLICATED_INDEX_WARN_BYTES`` a one-time
        warning points at the node-partitioned alternative
        (``repro.distributed.streaming_shard.DistributedStreamingEngine``,
        DESIGN.md §12), which shards the window itself so per-device memory
        *falls* with device count instead of staying flat.
        """
        from repro.distributed.walks import generate_walks_sharded
        self._warn_replicated_index()
        args = self._call_args()
        with span("walks.dispatch", self.registry, args=args):
            self.key, sub = jax.random.split(self.key)
            t0 = time.perf_counter()
            res = generate_walks_sharded(self._fit_state().index, sub, wcfg,
                                         self.cfg.sampler,
                                         self.cfg.scheduler, mesh=mesh)
        self._finish_sample(res, t0, path="sharded", args=args)
        return res

    def _warn_replicated_index(self) -> None:
        """One-time warning when the replicated-index sharding strategy is
        used with an index too large to replicate comfortably."""
        if self._warned_replicated_index:
            return
        nbytes = sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(self.state.index))
        if nbytes > REPLICATED_INDEX_WARN_BYTES:
            import warnings
            warnings.warn(
                f"sample_walks_sharded replicates the full window index "
                f"(~{nbytes / 2**20:.0f} MiB) onto every device of the "
                f"mesh; for windows of this size consider the "
                f"node-partitioned "
                f"repro.distributed.streaming_shard.DistributedStreaming"
                f"Engine (DESIGN.md §12), which shards the window itself.",
                stacklevel=3)
            self._warned_replicated_index = True

    def _finish_sample(self, res, t0: float, path: str,
                       args: dict) -> float:
        """Shared stats tail of every sample_walks* entry point: sync,
        fetch the lengths and the hop loop's iterations and lane-steps in
        one transfer, record wall time, publish into the registry, return the
        elapsed seconds."""
        reg = self.registry
        with span("walks.sync", reg, args=args):
            jax.block_until_ready(res.nodes)
        elapsed = time.perf_counter() - t0
        with span("walks.fetch", reg, args=args):
            lengths, steps, lane_steps = jax.device_get(
                (res.lengths, res.steps, res.lane_steps))
        with span("walks.publish", reg, args=args):
            self.stats.sample_s.append(elapsed)
            emitted = int(np.sum(lengths >= 2))
            reg.inc("walks_dispatched_total", int(lengths.size),
                    labels={"path": path},
                    help="walk slots dispatched, by sampling path")
            reg.inc("walks_emitted_total", emitted,
                    labels={"driver": "host"},
                    help="walks with at least one hop")
            reg.inc("walk_hops_total",
                    int(np.sum(np.maximum(lengths.astype(np.int64) - 1, 0))),
                    labels={"source": path}, help="hop cells executed")
            if lane_steps is not None:
                reg.inc("walk_lane_steps_total", int(lane_steps),
                        labels={"source": path},
                        help="lanes processed by the hop loop, live or not")
            if steps is not None:
                reg.observe("walk_loop_steps", int(steps),
                            help="hop-loop iterations per walk call")
        return elapsed

    def replay(self, batches: Iterable, wcfg: WalkConfig,
               on_batch: Optional[Callable] = None):
        """Host-loop driver: per-batch dispatch + sync (stage timings)."""
        for bs, bd, bt in batches:
            self.ingest_batch(bs, bd, bt)
            res = self.sample_walks(wcfg)
            if on_batch is not None:
                on_batch(self, res)
        return self.stats

    def replay_device(self, batches: Iterable, wcfg: WalkConfig,
                      return_walks: bool = False):
        """Device-resident driver: one `lax.scan` over all batches, one
        host sync at the end. Returns (ReplayStats on host, wall seconds),
        or (stats, final-batch WalkResult, seconds) with ``return_walks``
        — the reference trajectory the sharded replay
        (DistributedStreamingEngine) is tested bit-identical against.
        """
        reg = self.registry
        args = self._call_args()
        with span("replay.stage", reg, args=args):
            # stacked on the host, sent in one transfer
            stacked = stack_batches(batches, self.batch_capacity)
        with span("replay.dispatch", reg, args=args):
            self.key, sub = jax.random.split(self.key)
            t0 = time.perf_counter()
            state = self._fit_state()
            if self.probes:
                self.state, stats, walks, pv = replay_scan_probed(
                    state, stacked, sub, self.cfg.window.node_capacity,
                    wcfg, self.cfg.sampler, self.cfg.scheduler,
                    table=self._table, views=self._views)
            else:
                self.state, stats, walks = replay_scan(
                    state, stacked, sub, self.cfg.window.node_capacity,
                    wcfg, self.cfg.sampler, self.cfg.scheduler,
                    table=self._table, views=self._views)
                pv = None
        with span("replay.sync", reg, args=args):
            # the single sync point — probes ride the same materialization
            jax.block_until_ready((stats, pv))
        elapsed = time.perf_counter() - t0
        with span("replay.fetch", reg, args=args):
            host_stats, pv, host_walks = jax.device_get(
                (stats, pv, walks if return_walks else None))
        with span("replay.publish", reg, args=args):
            if self.probes:
                flush_replay_probes(reg, pv, driver="device")
                reg.observe("replay_seconds", elapsed,
                            labels={"driver": "device"},
                            help="wall time per replay_device call")
                self._publish_window_from_replay(host_stats)
        # NOTE: self.stats is left untouched — StreamStats' lists are
        # parallel per host-loop batch, and this driver has no per-batch
        # host timings to pair with. Everything lives in the return value.
        if return_walks:
            return host_stats, host_walks, elapsed
        return host_stats, elapsed
