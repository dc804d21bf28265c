"""Multi-tenant walk-query service over the streaming engine (DESIGN.md §11).

``WalkService`` is the front door the ROADMAP's "serve heavy traffic"
goal needs: many callers submit small heterogeneous ``WalkQuery``s; the
service queues them (fixed capacity, backpressure by drop + accounting),
coalesces compatible queries into one fixed-shape ``generate_walk_lanes``
dispatch per ``step()``, slices each tenant's rows back out, and tracks
p50/p99 submit→complete latency plus walks/s throughput.

Coalescing policy: head-of-line grouping — the head query (oldest under
FIFO admission, earliest-deadline under EDF) fixes the group key, then
same-group queries fold in along the admission order until the first one
that does not fit the lane budget seals the scan (the *prefix rule*).
Because the scan never skips a non-fitting query to admit a younger one,
**no query is ever overtaken by a younger same-group query** — the
fairness property tests/test_serve.py pins with hypothesis. A lone query
still rides a right-sized (small) bucket instead of the mega-batch shape.

**Async continuous-batching runtime** (DESIGN.md §18): dispatches no
longer block. A sealed batch launches on JAX async dispatch and joins a
bounded ring of in-flight futures, each pinned to the snapshot version it
launched against; ``pump()`` harvests completions (oldest first) at the
caller's pace, and ``tick()`` is the one-call event loop (evict expired →
harvest ready → seal + launch while the ring has room). A
partially-filled batch *lingers* up to ``ServeConfig.linger_s`` so
late-arriving same-group queries are admitted into it before it seals —
safe because the coalescer only decides *where* a lane sits, never *what*
it computes. ``step()`` keeps the historical synchronous semantics
(force-seal one batch, block until every in-flight batch is harvested),
which is also the bit-identity baseline the async path is tested against.

Determinism: results are bit-identical to running each query solo
(``run_query_solo``) because lane RNG folds by (query seed, walk id,
step) and the per-lane bias/length dispatch is pure per lane — the
coalescer only decides *where* a lane sits, never *what* it computes.

**Sharded serving** (DESIGN.md §13): with ``ServeConfig.num_shards > 0``
(or an explicit ``mesh``/``num_shards``), the same service runs against a
node-partitioned window: snapshots double-buffer a
``ShardedWindowState`` + replicated ts-view pair, and each coalesced
batch dispatches through ``serve_lanes_sharded`` — start lanes claimed by
their owner shards, per-hop owner migration, one psum trace reassembly —
with the *same* bit-identity guarantee against single-device solo runs.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs.base import EngineConfig, ServeConfig, WalkConfig
from repro.core.alias import spec_from_sampler
from repro.core.edge_store import make_batch
from repro.core.walk_engine import (
    LaneFeatures,
    LaneParams,
    check_capabilities,
    generate_walk_lanes,
    index_views,
)
from repro.core.window import WindowState, init_window
from repro.serve.coalescer import (
    bucketize,
    group_key,
    pack_queries,
    result_arrays,
    slice_result,
)
from repro.obs.probes import flush_serve_probes
from repro.obs.registry import (
    RESERVOIR_SIZE,
    MetricsRegistry,
    Reservoir,
    count_drop,
    get_registry,
)
from repro.obs.tracing import span
from repro.serve.query import QueryResult, WalkQuery
from repro.serve.snapshot import ShardedSnapshotManager, SnapshotManager


class QueueFull(RuntimeError):
    """Raised by ``submit(..., strict=True)`` when the queue is at capacity."""


class OversizeQuery(ValueError):
    """Raised by ``submit`` for a query exceeding the largest shape bucket
    when the service is configured (or asked) not to drop it silently —
    ``strict=True``, or ``ServeConfig.drop_oversize=False``. Unlike
    ``QueueFull`` this can never succeed on retry: the query needs a
    bigger bucket, not a quieter moment."""


@dataclass(frozen=True)
class _Pending:
    """One queued query: ticket, arrival clock, absolute deadline."""

    ticket: int
    arrival: float                   # time.perf_counter() at submit
    query: WalkQuery
    deadline: Optional[float] = None  # absolute perf_counter time, or None


@dataclass
class _InFlight:
    """One dispatched-but-unharvested batch in the async ring.

    ``raw`` holds the un-materialized device outputs (a ``WalkResult`` on
    the single-device path, the ``serve_lanes_sharded`` output tuple on
    the sharded path) — touching them would force a host sync, so only
    ``pump`` does. ``version`` is the snapshot version the batch was
    pinned to at launch; results report it even when ``publish()`` ran
    while the batch was in flight."""

    raw: object
    probe: object                    # one device array to poll readiness on
    taken: List[_Pending]
    slices: List[object]
    lane_bucket: int
    lanes: int
    version: int
    t0: float                        # launch clock


# percentile window: counters are lifetime totals, but the latency/batch
# samples backing p50/p99 are a bounded ring-buffer reservoir (the obs
# histogram backing store, obs/registry.py) so a long-running service
# neither grows without bound nor pays O(history) per stat read
STATS_WINDOW = RESERVOIR_SIZE


@dataclass
class ServeStats:
    """Serving counters + latency/throughput accounting."""

    submitted: int = 0
    completed: int = 0
    dropped_backpressure: int = 0   # queue at capacity
    dropped_oversize: int = 0       # exceeds the largest shape bucket
    #   (counts silent drops AND the typed refusals drop_oversize=False
    #   raises on non-strict submits — both are shed work; strict raises
    #   are the caller's own error handling and are not counted)
    dropped_deadline: int = 0       # queued past deadline_s -> evicted
    batches: int = 0                # coalesced dispatches
    lanes_dispatched: int = 0       # incl. bucket padding
    lanes_live: int = 0             # real query lanes
    walks: int = 0                  # walks returned to callers
    hops: int = 0                   # edges traversed in returned walks
    solo_queries: int = 0           # run_query_solo dispatches (accounted
    #   into walks/hops/busy_s like served traffic, so mixed solo+served
    #   workloads report true throughput)
    busy_s: float = 0.0             # total launch->harvest wall time; with
    #   overlapped dispatch (max_inflight > 1) in-flight intervals overlap
    #   so busy_s can exceed wall time and walks_per_s under-reports the
    #   overlapped rate — wall-clock goodput lives in the SLO harness
    shard_walk_drops: int = 0       # sharded serving: capacity-overflow lanes
    exchange_drops: int = 0         # sharded serving: ingest-exchange drops
    # ^ cumulative over the service lifetime; BOTH refresh per dispatch
    #   (and exchange_drops additionally at publish()), so they advance in
    #   lockstep — the old asymmetry where exchange_drops lagged until the
    #   next snapshot publish is gone. The §13 bit-identity guarantee
    #   needs BOTH at zero: walk drops lose lanes, exchange drops lose
    #   window edges.
    lanes_by_shard: Dict[int, int] = field(default_factory=dict)
    # ^ sharded batches, BOTH start modes: start lanes claimed per owner
    #   shard, counted on device inside ``serve_lanes_sharded`` (the
    #   walk_slots provisioning signal and the placement-imbalance gauge
    #   that ``SkewPlacement.from_loads`` consumes, DESIGN.md §15)
    latencies_s: Reservoir = field(
        default_factory=lambda: Reservoir(STATS_WINDOW))
    sample_s: Reservoir = field(
        default_factory=lambda: Reservoir(STATS_WINDOW))

    @property
    def dropped(self) -> int:
        return (self.dropped_backpressure + self.dropped_oversize
                + self.dropped_deadline)

    def latency_percentile(self, q: float) -> float:
        """q-th percentile of submit→complete latency over the bounded
        reservoir, in seconds. Contract (tested in tests/test_obs.py):
        empty reservoir -> nan for every q; a single sample -> that sample
        for every q; q outside [0, 100] -> ValueError."""
        return self.latencies_s.percentile(q)

    @property
    def p50_ms(self) -> float:
        return 1e3 * self.latency_percentile(50)

    @property
    def p99_ms(self) -> float:
        return 1e3 * self.latency_percentile(99)

    @property
    def walks_per_s(self) -> float:
        return self.walks / self.busy_s if self.busy_s > 0 else 0.0

    @property
    def steps_per_s(self) -> float:
        return self.hops / self.busy_s if self.busy_s > 0 else 0.0

    @property
    def lane_occupancy(self) -> float:
        """Live fraction of dispatched lanes (bucket-padding overhead)."""
        return (self.lanes_live / self.lanes_dispatched
                if self.lanes_dispatched else 0.0)


class WalkService:
    """Walk-query serving over a snapshot double-buffered window.

    The service owns a ``SnapshotManager`` (feed it edges via ``ingest`` /
    ``begin_ingest`` + ``publish``) and a fixed-capacity FIFO of pending
    queries. ``submit`` enqueues (or drops, under backpressure);
    ``step`` serves one coalesced batch; ``drain`` loops until empty.
    """

    def __init__(self, cfg: EngineConfig,
                 serve_cfg: ServeConfig = ServeConfig(),
                 state: Optional[WindowState] = None,
                 batch_capacity: int = 8192, *,
                 mesh=None, num_shards: int = 0, placement=None,
                 registry: Optional[MetricsRegistry] = None,
                 probes: bool = True):
        if list(serve_cfg.lane_buckets) != sorted(serve_cfg.lane_buckets) \
                or list(serve_cfg.length_buckets) != sorted(
                    serve_cfg.length_buckets):
            raise ValueError("ServeConfig buckets must be sorted ascending")
        if serve_cfg.max_inflight < 1:
            raise ValueError("ServeConfig.max_inflight must be >= 1 "
                             f"(got {serve_cfg.max_inflight})")
        if serve_cfg.linger_s < 0:
            raise ValueError("ServeConfig.linger_s must be >= 0 "
                             f"(got {serve_cfg.linger_s})")
        if serve_cfg.admission not in ("fifo", "edf"):
            raise ValueError("ServeConfig.admission must be 'fifo'|'edf' "
                             f"(got {serve_cfg.admission!r})")
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        # the tiled kernel compiles one bias per dispatch; serve on the
        # grouped path instead (same walks — tested path equivalence).
        # The fused kernel dispatches per-lane bias codes, so path="fused"
        # passes through and serves heterogeneous batches in-kernel.
        self.sched_cfg = (dataclasses.replace(cfg.scheduler, path="grouped")
                         if cfg.scheduler.path == "tiled" else cfg.scheduler)
        # bias='table' (or an explicit table_weight) opts the snapshot
        # buffers into alias-table maintenance (core/alias.py, §17)
        self._table = spec_from_sampler(cfg.sampler)
        self._rebuilt_seen = 0
        # every serving dispatch is a per-lane batch, so validate the
        # config against lane capabilities up front — the single
        # chokepoint (walk_engine.check_capabilities) refuses mode !=
        # 'index', config-level node2vec, and sharded table bias here
        # instead of mid-batch
        check_capabilities(
            cfg.sampler, self.sched_cfg.path, LaneFeatures(),
            sharded=mesh is not None or (num_shards
                                         or serve_cfg.num_shards) > 0,
            have_tables=self._table is not None)
        # obs integration (DESIGN.md §16); ``probes=False`` pins the
        # sharded dispatch to the historical uninstrumented program
        self.registry = registry if registry is not None else get_registry()
        self.probes = probes
        ns = num_shards or serve_cfg.num_shards
        self.sharded = mesh is not None or ns > 0
        if self.sharded:
            if state is not None:
                raise ValueError(
                    "sharded serving builds its own node-partitioned "
                    "window; the state= override is single-device only")
            self.snapshots = ShardedSnapshotManager(
                cfg, batch_capacity, mesh=mesh, num_shards=ns,
                placement=placement, registry=self.registry)
            self.batch_capacity = self.snapshots.batch_capacity
            self.num_shards = self.snapshots.num_shards
        else:
            if placement is not None:
                raise ValueError("placement= requires sharded serving "
                                 "(num_shards > 0 or mesh=)")
            self.batch_capacity = batch_capacity
            self.num_shards = 0
            # any query may carry second-order lanes, so every snapshot
            # holds the adjacency view their β probe reads
            views = index_views(cfg.sampler, self.sched_cfg.path,
                                second_order=True)
            self.snapshots = SnapshotManager(
                state if state is not None else init_window(
                    cfg.window.edge_capacity, cfg.window.node_capacity,
                    int(cfg.window.duration), table=self._table,
                    views=views),
                cfg.window.node_capacity, registry=self.registry,
                table=self._table, views=views)
        # NOT split per call: lane RNG identity lives in (seed, walk, step)
        # folds, and solo/coalesced bit-equality needs a stable base.
        self.base_key = jax.random.PRNGKey(cfg.seed)
        self.stats = ServeStats()
        # drop-delta baseline: stats.exchange_drops is cumulative and may
        # be reset by callers, the registry needs monotonic deltas
        self._exchange_drops_seen = 0
        self._last_shard_claims: Optional[np.ndarray] = None
        self.placement = (self.snapshots.placement if self.sharded
                          else None)
        self._pending: Deque[_Pending] = deque()
        self._inflight: Deque[_InFlight] = deque()
        self._results: Dict[int, QueryResult] = {}
        self._next_ticket = 0
        # when a drain() is active, tickets harvested during it land here
        # so the drain returns exactly the results it produced
        self._harvest_log: Optional[List[int]] = None

    # ------------------------------------------------------------------
    # Ingest side (snapshot double-buffer)
    # ------------------------------------------------------------------

    def ingest(self, src, dst, ts) -> None:
        """Advance the window synchronously (begin + publish)."""
        self.begin_ingest(src, dst, ts)
        self.publish()

    def begin_ingest(self, src, dst, ts) -> None:
        """Start building the next window; serving continues against the
        current snapshot until ``publish``."""
        batch = make_batch(src, dst, ts, capacity=self.batch_capacity)
        with span("ingest_merge", self.registry):
            self.snapshots.begin_ingest(batch)

    def publish(self) -> None:
        with span("snapshot_publish", self.registry):
            self.snapshots.publish()
        self.registry.set_gauge("snapshot_version", self.snapshots.version,
                                help="published serving snapshot version")
        if self.sharded:
            self._refresh_exchange_drops()
            return
        self.registry.set_gauge(
            "index_edge_columns",
            self.snapshots.current.index.views.edge_columns,
            help="edge-sized columns the window's index rebuild writes")
        if self.snapshots.current.tables is not None:
            # same counter the streaming engine publishes (§17): incremental
            # maintenance work per advance, against a full-rebuild baseline
            rebuilt = int(self.snapshots.current.tables.rebuilt)
            self.registry.inc("alias_nodes_rebuilt_total",
                              max(0, rebuilt - self._rebuilt_seen),
                              help="alias-table node rebuilds performed by "
                                   "incremental window maintenance")
            self._rebuilt_seen = rebuilt

    def _refresh_exchange_drops(self) -> None:
        """Pull the sharded ingest's cumulative exchange-drop counter into
        the stats view + registry. Called per dispatch AND per publish, so
        ``exchange_drops`` advances in lockstep with ``shard_walk_drops``
        (sharded ingest drops edges — not lanes — on exchange overflow;
        they break bit-identity just like walk drops)."""
        total = int(np.asarray(self.snapshots.state.exchange_drops).sum())
        self.stats.exchange_drops = total
        count_drop(self.registry, "exchange_clip",
                   max(0, total - self._exchange_drops_seen))
        self._exchange_drops_seen = max(total, self._exchange_drops_seen)

    # ------------------------------------------------------------------
    # Query side
    # ------------------------------------------------------------------

    def _oversize(self, query: WalkQuery) -> bool:
        return (bucketize(query.num_lanes, self.serve_cfg.lane_buckets)
                is None
                or bucketize(query.max_length, self.serve_cfg.length_buckets)
                is None)

    def submit(self, query: WalkQuery, strict: bool = False) -> Optional[int]:
        """Enqueue a query; returns its ticket, or None when dropped.

        Oversize contract (all four ``strict`` × ``drop_oversize`` cells,
        tested in tests/test_serve.py):

        * ``strict=False, drop_oversize=True`` — silent drop: returns
          None, counted (``stats.dropped_oversize`` + the ``oversize``
          drop kind).
        * ``strict=False, drop_oversize=False`` — typed refusal: raises
          ``OversizeQuery``; still counted as shed work, because the
          service refused traffic mid-stream.
        * ``strict=True`` (either ``drop_oversize``) — raises
          ``OversizeQuery``, NOT counted: like a strict ``QueueFull``,
          the raise is the caller's own error handling, not a drop.

        Backpressure (queue at capacity) drops with ``strict=False`` and
        raises ``QueueFull`` with ``strict=True``. Queued queries whose
        ``deadline_s`` has expired are evicted first (counted as
        ``deadline_expired``), so a full queue of dead queries never
        causes spurious backpressure.

        Table-bias and second-order (node2vec) queries are validated
        against the service's capabilities here — always a raise, never a
        drop: unlike backpressure these can never succeed on retry.
        """
        if query.bias == "table" or query.second_order:
            check_capabilities(
                self.cfg.sampler, self.sched_cfg.path,
                LaneFeatures(table=query.bias == "table",
                             second_order=query.second_order),
                sharded=self.sharded,
                have_tables=(not self.sharded
                             and self.snapshots.current.tables is not None))
        now = time.perf_counter()
        self._evict_expired(now)
        if self._oversize(query):
            msg = (f"query needs {query.num_lanes} lanes × "
                   f"{query.max_length} hops; largest bucket is "
                   f"{self.serve_cfg.lane_buckets[-1]} × "
                   f"{self.serve_cfg.length_buckets[-1]}")
            if strict:
                raise OversizeQuery(msg)
            self.stats.dropped_oversize += 1
            count_drop(self.registry, "oversize")
            if not self.serve_cfg.drop_oversize:
                raise OversizeQuery(
                    msg + " (drop_oversize=False: refusing instead of "
                          "silently dropping)")
            return None
        if len(self._pending) >= self.serve_cfg.queue_capacity:
            if strict:
                raise QueueFull(
                    f"{len(self._pending)} queries pending "
                    f"(capacity {self.serve_cfg.queue_capacity})")
            self.stats.dropped_backpressure += 1
            count_drop(self.registry, "queue_backpressure")
            return None
        ticket = self._next_ticket
        self._next_ticket += 1
        deadline = (now + query.deadline_s
                    if query.deadline_s is not None else None)
        self._pending.append(_Pending(ticket, now, query, deadline))
        self.stats.submitted += 1
        self.registry.inc("serve_submitted_total", 1,
                          help="queries accepted into the serving queue")
        self.registry.set_gauge("serve_queue_depth", len(self._pending),
                                help="queries pending in the serving queue")
        return ticket

    def _evict_expired(self, now: float) -> int:
        """Evict queued queries past their deadline (DESIGN.md §18).

        Only *queued* queries are evicted — once sealed into a batch a
        query always completes (eviction is an admission decision, not a
        cancellation of in-flight device work)."""
        if not any(e.deadline is not None for e in self._pending):
            return 0
        kept: Deque[_Pending] = deque()
        evicted = 0
        for e in self._pending:
            if e.deadline is not None and now > e.deadline:
                evicted += 1
            else:
                kept.append(e)
        if evicted:
            self._pending = kept
            self.stats.dropped_deadline += evicted
            count_drop(self.registry, "deadline_expired", evicted)
            self.registry.set_gauge("serve_queue_depth", len(self._pending))
        return evicted

    def poll(self, ticket: int) -> Optional[QueryResult]:
        """Fetch (and forget) a completed query's result."""
        return self._results.pop(ticket, None)

    @property
    def pending_count(self) -> int:
        return len(self._pending)

    def _group_key(self, query: WalkQuery):
        return group_key(query, self.serve_cfg.length_buckets)

    def _admission_order(self) -> List[_Pending]:
        """Queue view in head-of-line order: arrival order under FIFO,
        (deadline, ticket) under EDF — deadline-free queries sort last and
        keep FIFO order among themselves."""
        if self.serve_cfg.admission == "fifo":
            return list(self._pending)
        return sorted(self._pending,
                      key=lambda e: (e.deadline if e.deadline is not None
                                     else math.inf, e.ticket))

    def _scan_group(self, order: Sequence[_Pending]):
        """The head query fixes the group key; same-group queries fold in
        along the admission order until the first one that does not fit
        the lane budget seals the scan (the prefix rule). Never skipping a
        non-fitting query to admit a later one is what makes the fairness
        claim true: a query can never be overtaken by a younger same-group
        query (property-tested in tests/test_serve.py)."""
        head_key = self._group_key(order[0].query)
        budget = self.serve_cfg.lane_buckets[-1]
        take: List[_Pending] = []
        lanes, sealed = 0, False
        for e in order:
            if self._group_key(e.query) != head_key:
                continue
            if lanes + e.query.num_lanes > budget:
                sealed = True
                break
            take.append(e)
            lanes += e.query.num_lanes
        return head_key, take, lanes, sealed

    def _form_batch(self, now: float, force: bool):
        """Seal one batch if the linger rule allows; returns ``(group
        key, taken, lanes)`` (and removes the taken queries from the
        queue) or None when the head batch should keep lingering.

        Seal rule (DESIGN.md §18): dispatch when the batch cannot grow —
        the scan hit a non-fitting same-group query or filled the lane
        budget exactly — or when the head query has lingered
        ``linger_s`` (0 = seal immediately), or when forced
        (``step``/``drain``)."""
        if not self._pending:
            return None
        order = self._admission_order()
        head_key, take, lanes, sealed = self._scan_group(order)
        budget = self.serve_cfg.lane_buckets[-1]
        if not (force or sealed or lanes >= budget
                or now - take[0].arrival >= self.serve_cfg.linger_s):
            return None
        taken_tickets = {e.ticket for e in take}
        self._pending = deque(e for e in self._pending
                              if e.ticket not in taken_tickets)
        return head_key, take, lanes

    def _take_batch(self):
        """Force-seal one batch now (the synchronous entry point)."""
        head_key, take, lanes = self._form_batch(time.perf_counter(),
                                                 force=True)
        return head_key, take, lanes

    def _launch_lanes(self, params: LaneParams, wcfg: WalkConfig, pin,
                      use_tables: bool = False, second_order: bool = False):
        """Enqueue one packed lane batch on the device WITHOUT waiting;
        returns the raw device outputs (a ``WalkResult`` single-device, the
        ``serve_lanes_sharded`` tuple sharded) against the pinned snapshot.

        ``use_tables`` / ``second_order`` flag whether any lane in the
        batch carries a table bias code / a non-trivial (p, q) pair —
        submit-time validation guarantees both are False on the sharded
        path. Passing tables to a batch with no table lanes (or compiling
        the second-order machinery for an all-first-order batch) would be
        harmless for correctness — the overlay selects per lane — but
        keeping the flags per batch pins the common case to the exact
        historical program."""
        if self.sharded:
            from repro.distributed.streaming_shard import serve_lanes_sharded
            snap = self.snapshots
            return serve_lanes_sharded(
                pin.state, pin.view, self.base_key, params,
                mesh=snap.mesh, axis_name=snap.axis_name,
                node_capacity=self.cfg.window.node_capacity, wcfg=wcfg,
                scfg=self.cfg.sampler, shard_cfg=self.cfg.shard,
                placement=snap.placement, with_probes=self.probes)
        snap = pin.state
        return generate_walk_lanes(snap.index, self.base_key, params, wcfg,
                                   self.cfg.sampler, self.sched_cfg,
                                   tables=snap.tables if use_tables else None,
                                   second_order=second_order)

    def _materialize(self, raw):
        """Block on one launched batch and bring it to host: (nodes,
        times, lengths) arrays, plus the sharded drop/claim/probe
        bookkeeping at this (the batch's only) host sync point.
        Sharded psum-reassembled leaves are replicated, so row 0 is the
        batch result (DESIGN.md §13)."""
        if self.sharded:
            if self.probes:
                nodes, times, lengths, drops, claims, sp = raw
            else:
                nodes, times, lengths, drops, claims = raw
            jax.block_until_ready(lengths)
            self.stats.shard_walk_drops += int(np.asarray(drops).sum())
            self._last_shard_claims = np.asarray(claims)
            if self.probes:
                # flushed at the batch's existing sync; the exchange
                # refresh keeps both sharded drop counters per-harvest
                flush_serve_probes(self.registry, np.asarray(sp))
                self._refresh_exchange_drops()
            # device-side per-shard claim counters (serve_lanes_sharded):
            # unlike the old host-side owner fold this covers edges-mode
            # batches too, whose owners are data-dependent
            for d, n in enumerate(self._last_shard_claims):
                if n:
                    self.stats.lanes_by_shard[int(d)] = \
                        self.stats.lanes_by_shard.get(int(d), 0) + int(n)
            return (np.asarray(nodes)[0], np.asarray(times)[0],
                    np.asarray(lengths)[0])
        jax.block_until_ready(raw.nodes)
        return result_arrays(raw)

    def _dispatch_lanes(self, params: LaneParams, wcfg: WalkConfig,
                        use_tables: bool = False,
                        second_order: bool = False):
        """Blocking convenience (the reference/solo path): launch one lane
        batch against the current snapshot and wait for it."""
        raw = self._launch_lanes(params, wcfg, self.snapshots.acquire(),
                                 use_tables=use_tables,
                                 second_order=second_order)
        return self._materialize(raw)

    # ------------------------------------------------------------------
    # Async runtime: launch ring + pump loop (DESIGN.md §18)
    # ------------------------------------------------------------------

    def _launch(self, batch) -> int:
        """Pack a sealed batch and enqueue it on the device; the batch
        joins the in-flight ring pinned to the current snapshot version.
        Returns the number of queries admitted into it."""
        reg = self.registry
        (start_mode, len_bucket), taken, lanes = batch
        with span("coalesce", reg):
            lane_bucket = bucketize(lanes, self.serve_cfg.lane_buckets)
            queries = [e.query for e in taken]
            params, slices = pack_queries(queries, lane_bucket, len_bucket)
        wcfg = WalkConfig(num_walks=lane_bucket, max_length=len_bucket,
                          start_mode=start_mode)
        pin = self.snapshots.acquire()
        t0 = time.perf_counter()
        with span("dispatch", reg):
            raw = self._launch_lanes(
                params, wcfg, pin,
                use_tables=any(q.bias == "table" for q in queries),
                second_order=any(q.second_order for q in queries))
        probe = raw[2] if self.sharded else raw.lengths
        self._inflight.append(_InFlight(
            raw=raw, probe=probe, taken=list(taken), slices=list(slices),
            lane_bucket=lane_bucket, lanes=lanes, version=pin.version,
            t0=t0))
        self.stats.batches += 1
        self.stats.lanes_dispatched += lane_bucket
        self.stats.lanes_live += lanes
        reg.inc("serve_batches_total", 1,
                help="coalesced serving dispatches")
        reg.inc("walks_dispatched_total", lane_bucket,
                labels={"path": "serve"},
                help="walk slots dispatched, by sampling path")
        reg.set_gauge("serve_lane_occupancy", self.stats.lane_occupancy,
                      help="live fraction of dispatched lanes")
        reg.set_gauge("serve_queue_depth", len(self._pending))
        reg.set_gauge("serve_inflight_depth", len(self._inflight),
                      help="dispatched batches not yet harvested")
        return len(taken)

    @staticmethod
    def _batch_ready(fl: _InFlight) -> bool:
        """Non-blocking readiness probe on one in-flight batch."""
        return bool(fl.probe.is_ready())

    def _harvest(self, fl: _InFlight) -> int:
        """Materialize one in-flight batch and deliver its results."""
        reg = self.registry
        nodes, times, lengths = self._materialize(fl.raw)
        done_t = time.perf_counter()
        elapsed = done_t - fl.t0
        self.stats.sample_s.append(elapsed)
        self.stats.busy_s += elapsed
        reg.observe("serve_batch_seconds", elapsed,
                    help="launch -> harvest wall time per coalesced batch")
        with span("result_slice", reg):
            for e, sl in zip(fl.taken, fl.slices):
                qn, qt, ql = slice_result(nodes, times, lengths, sl, e.query)
                self._results[e.ticket] = QueryResult(
                    ticket=e.ticket, query=e.query, nodes=qn, times=qt,
                    lengths=ql, latency_s=done_t - e.arrival,
                    snapshot_version=fl.version)
                if self._harvest_log is not None:
                    self._harvest_log.append(e.ticket)
                self.stats.completed += 1
                self.stats.walks += e.query.num_lanes
                self.stats.hops += int(np.sum(np.clip(ql - 1, 0, None)))
                self.stats.latencies_s.append(done_t - e.arrival)
                reg.observe("serve_latency_seconds", done_t - e.arrival,
                            help="submit -> complete latency per query")
        reg.inc("serve_completed_total", len(fl.taken),
                help="queries completed")
        reg.set_gauge("serve_inflight_depth", len(self._inflight))
        return len(fl.taken)

    @property
    def inflight_count(self) -> int:
        return len(self._inflight)

    def pump(self, block: bool = False) -> int:
        """Harvest completed in-flight batches, oldest first; returns the
        number of queries completed. ``block=False`` stops at the first
        batch whose device work is still running; ``block=True`` waits for
        the whole ring (the sync point ``step``/``drain`` use)."""
        done = 0
        while self._inflight:
            if not block and not self._batch_ready(self._inflight[0]):
                break
            done += self._harvest(self._inflight.popleft())
        return done

    def tick(self, now: Optional[float] = None) -> int:
        """One turn of the async event loop: evict expired queries,
        harvest every ready batch, then seal + launch batches while the
        in-flight ring has room and the linger rule allows. Never blocks.
        Returns the number of queries completed this tick.

        The open-loop caller pattern (benchmarks/serving_load.py)::

            while traffic or svc.pending_count or svc.inflight_count:
                svc.submit(...)     # as arrivals come in
                svc.tick()
            svc.pump(block=True)    # final sync
        """
        if now is None:
            now = time.perf_counter()
        self._evict_expired(now)
        done = self.pump(block=False)
        while (self._pending
               and len(self._inflight) < self.serve_cfg.max_inflight):
            batch = self._form_batch(now, force=False)
            if batch is None:
                break                      # head batch keeps lingering
            self._launch(batch)
        return done

    def step(self) -> int:
        """Serve one coalesced batch synchronously; returns the number of
        queries in it. Force-seals (ignores the linger deadline), then
        blocks until every in-flight batch — including any launched by
        earlier ``tick`` calls — is harvested. With ``max_inflight=1``
        and no ``tick``/``pump`` use this is exactly the historical
        blocking FIFO loop, which is the bit-identity baseline the async
        path is regression-tested against."""
        self._evict_expired(time.perf_counter())
        if not self._pending:
            self.pump(block=True)
            return 0
        if len(self._inflight) >= self.serve_cfg.max_inflight:
            self.pump(block=True)
        n = self._launch(self._take_batch())
        self.pump(block=True)
        return n

    def drain(self) -> List[QueryResult]:
        """Serve until the queue and the in-flight ring are empty; return
        the results of exactly the queries completed during THIS drain.

        Results completed by earlier ``step``/``tick`` calls stay in the
        poll buffer — their tickets remain ``poll``-able after the drain
        (the poll-after-drain contract, regression-tested in
        tests/test_serve.py). The returned results are popped: their
        tickets are delivered, not double-pollable."""
        log: List[int] = []
        outer = self._harvest_log
        self._harvest_log = log
        try:
            while self._pending or self._inflight:
                self._evict_expired(time.perf_counter())
                if (self._pending
                        and len(self._inflight)
                        < self.serve_cfg.max_inflight):
                    batch = self._form_batch(time.perf_counter(),
                                             force=True)
                    if batch is not None:
                        self._launch(batch)
                        continue
                self.pump(block=True)
        finally:
            self._harvest_log = outer
        if outer is not None:
            outer.extend(log)
        return [self._results.pop(t) for t in log if t in self._results]

    # ------------------------------------------------------------------
    # Reference path
    # ------------------------------------------------------------------

    def run_query_solo(self, query: WalkQuery):
        """Run one query alone at its exact shape (no coalescing, no
        bucketing) against the current snapshot. The per-lane RNG makes
        this bit-identical to the same query served coalesced — the
        equivalence the tests pin down (and, for a sharded service, also
        bit-identical to the single-device service's solo run).

        Solo runs ARE accounted: ``stats.solo_queries`` plus the shared
        walks / hops / busy_s totals and the ``path="solo"`` dispatch
        counter, so a mixed solo+served workload reports true throughput
        instead of silently attributing solo device time to nothing.
        They do not touch the queue/latency accounting (nothing was
        queued) or ``completed`` (no ticket is issued).
        """
        params, (sl,) = pack_queries([query], query.num_lanes,
                                     query.max_length)
        wcfg = WalkConfig(num_walks=query.num_lanes,
                          max_length=query.max_length,
                          start_mode=query.start_mode)
        t0 = time.perf_counter()
        out = slice_result(
            *self._dispatch_lanes(params, wcfg,
                                  use_tables=query.bias == "table",
                                  second_order=query.second_order),
            sl, query)
        elapsed = time.perf_counter() - t0
        self.stats.solo_queries += 1
        self.stats.walks += query.num_lanes
        self.stats.hops += int(np.sum(np.clip(out[2] - 1, 0, None)))
        self.stats.busy_s += elapsed
        self.stats.sample_s.append(elapsed)
        self.registry.inc("walks_dispatched_total", query.num_lanes,
                          labels={"path": "solo"},
                          help="walk slots dispatched, by sampling path")
        return out
