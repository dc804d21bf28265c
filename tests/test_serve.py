"""Serving subsystem (DESIGN.md §11): coalesced == solo bit-identity,
shape buckets, queue backpressure, snapshot double-buffer."""
import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import (
    EngineConfig,
    SamplerConfig,
    SchedulerConfig,
    ServeConfig,
    WalkConfig,
    WindowConfig,
)
from repro.core.edge_store import make_batch
from repro.core.validation import validate_walks_np
from repro.core.walk_engine import NODE_PAD, generate_walk_lanes
from repro.core.window import ingest, init_window
from repro.data.synthetic import chronological_batches, powerlaw_temporal_graph
from repro.serve import (
    WalkQuery,
    WalkService,
    bucketize,
    pack_queries,
    slice_result,
)

NC = 128


def _engine_cfg(**sched_kw):
    return EngineConfig(
        window=WindowConfig(duration=4000, edge_capacity=4096,
                            node_capacity=NC),
        sampler=SamplerConfig(mode="index"),
        scheduler=SchedulerConfig(path="grouped", **sched_kw))


def _serve_cfg(**kw):
    kw.setdefault("lane_buckets", (8, 16, 64))
    kw.setdefault("length_buckets", (4, 8))
    return ServeConfig(**kw)


# module-level cache rather than a fixture: the property test below must
# not take fixture arguments (the hypothesis fallback shim presents a
# zero-argument signature), so both share this helper.
_SERVICE_CACHE = {}


def _loaded_service():
    if not _SERVICE_CACHE:
        g = powerlaw_temporal_graph(100, 3000, seed=11)
        svc = WalkService(_engine_cfg(), _serve_cfg())
        for bs, bd, bt in chronological_batches(g, 3):
            svc.ingest(bs, bd, bt)
        _SERVICE_CACHE["svc"] = (g, svc)
    return _SERVICE_CACHE["svc"]


@pytest.fixture(scope="module")
def loaded_service():
    return _loaded_service()


BIASES = ("uniform", "linear", "exponential")


def _query(bias_i, edges_mode, n_lanes, max_length, seed, node0):
    if edges_mode:
        return WalkQuery(num_walks=n_lanes, start_mode="edges",
                         bias=BIASES[bias_i],
                         start_bias=BIASES[(bias_i + 1) % 3],
                         max_length=max_length, seed=seed)
    starts = tuple((node0 + 7 * i) % NC for i in range(n_lanes))
    return WalkQuery(start_nodes=starts, bias=BIASES[bias_i],
                     max_length=max_length, seed=seed)


def _assert_solo_equals_coalesced(svc, queries):
    tickets = [svc.submit(q, strict=True) for q in queries]
    while svc.pending_count:
        svc.step()
    for t, q in zip(tickets, queries):
        r = svc.poll(t)
        assert r is not None
        sn, st_, sl = svc.run_query_solo(q)
        assert np.array_equal(r.nodes, sn), q
        assert np.array_equal(r.times, st_), q
        assert np.array_equal(r.lengths, sl), q


def test_mixed_bias_equivalence_full_grid(loaded_service):
    """Acceptance: a coalesced heterogeneous batch is bit-identical to
    per-query solo runs — all three biases × both start modes."""
    _, svc = loaded_service
    queries = []
    for i, bias in enumerate(BIASES):
        queries.append(WalkQuery(start_nodes=(1 + i, 30 + i, 60 + i),
                                 bias=bias, max_length=5 + i,
                                 seed=100 + i))
        queries.append(WalkQuery(num_walks=3, start_mode="edges", bias=bias,
                                 start_bias=BIASES[(i + 1) % 3],
                                 max_length=4 + i, seed=200 + i))
    _assert_solo_equals_coalesced(svc, queries)


@settings(max_examples=12, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 2), st.booleans(),
                          st.integers(1, 4), st.integers(2, 8),
                          st.integers(0, 10_000), st.integers(0, NC - 1)),
                min_size=1, max_size=6))
def test_mixed_bias_equivalence_property(descriptors):
    """Property: any mix of (bias, start mode, lanes, length, seed) packs
    into coalesced batches bit-identical to each query alone."""
    _, svc = _loaded_service()
    queries = [_query(*d) for d in descriptors]
    _assert_solo_equals_coalesced(svc, queries)


def test_served_walks_are_causal(loaded_service):
    """Coalesced answers are real temporal walks (hop-valid on the graph)."""
    g, svc = loaded_service
    queries = [WalkQuery(start_nodes=tuple(range(40)), bias=b, max_length=8,
                         seed=i) for i, b in enumerate(BIASES)]
    tickets = [svc.submit(q, strict=True) for q in queries]
    while svc.pending_count:
        svc.step()
    for t in tickets:
        r = svc.poll(t)
        hv, _ = validate_walks_np((g.src, g.dst, g.ts), r.nodes, r.times,
                                  r.lengths)
        assert hv == 1.0
        # rows past a lane's length are PAD; lengths respect max_length+1
        assert r.lengths.max() <= r.query.max_length + 1
        for w in range(r.nodes.shape[0]):
            assert np.all(r.nodes[w, r.lengths[w]:] == NODE_PAD)


def test_lane_paths_equivalent(loaded_service):
    """fullwalk / grouped-bucket / grouped-lexsort serve identical walks."""
    _, svc = loaded_service
    q = WalkQuery(start_nodes=tuple(range(24)), bias="exponential",
                  max_length=8, seed=9)
    ref = None
    for path, regroup in (("fullwalk", "bucket"), ("grouped", "bucket"),
                          ("grouped", "lexsort")):
        svc2 = WalkService(_engine_cfg(regroup=regroup), _serve_cfg(),
                           state=svc.snapshots.current)
        svc2.sched_cfg = dataclasses.replace(svc2.sched_cfg, path=path,
                                             regroup=regroup)
        got = svc2.run_query_solo(q)
        if ref is None:
            ref = got
        else:
            for a, b in zip(ref, got):
                assert np.array_equal(a, b), (path, regroup)


def test_queue_backpressure_and_drop_accounting():
    svc = WalkService(_engine_cfg(), _serve_cfg(queue_capacity=3))
    g = powerlaw_temporal_graph(100, 500, seed=2)
    svc.ingest(g.src, g.dst, g.ts)
    qs = [WalkQuery(start_nodes=(i % NC,), max_length=4, seed=i)
          for i in range(5)]
    tickets = [svc.submit(q) for q in qs]
    assert tickets[:3] == [0, 1, 2] and tickets[3:] == [None, None]
    assert svc.stats.dropped_backpressure == 2
    assert svc.stats.submitted == 3
    with pytest.raises(Exception):
        svc.submit(qs[0], strict=True)
    served = svc.drain()
    assert len(served) == 3
    # queue drained: submits accepted again
    assert svc.submit(qs[3]) is not None


def test_queue_full_strict_raises_queuefull():
    """strict=True backpressure raises the typed QueueFull, and the queue
    recovers exactly: drop accounting never double-counts strict raises."""
    from repro.serve import QueueFull
    svc = WalkService(_engine_cfg(), _serve_cfg(queue_capacity=2))
    g = powerlaw_temporal_graph(100, 400, seed=4)
    svc.ingest(g.src, g.dst, g.ts)
    q = WalkQuery(start_nodes=(1,), max_length=4, seed=0)
    assert svc.submit(q) is not None and svc.submit(q) is not None
    with pytest.raises(QueueFull, match="capacity 2"):
        svc.submit(q, strict=True)
    # a strict raise is not a drop; a non-strict overflow is
    assert svc.stats.dropped_backpressure == 0
    assert svc.submit(q) is None
    assert svc.stats.dropped_backpressure == 1
    svc.drain()
    assert svc.submit(q, strict=True) is not None


def test_latency_percentile_degenerate_histories():
    """Empty history -> NaN (not a crash); one sample -> that sample at
    every percentile; counters stay zero-safe."""
    import math
    from repro.serve import ServeStats
    s = ServeStats()
    assert math.isnan(s.latency_percentile(50))
    assert math.isnan(s.p50_ms) and math.isnan(s.p99_ms)
    assert s.walks_per_s == 0.0 and s.lane_occupancy == 0.0
    s.latencies_s.append(0.25)
    for q in (0, 50, 99, 100):
        assert s.latency_percentile(q) == pytest.approx(0.25)
    assert s.p99_ms == pytest.approx(250.0)


def test_oversize_query_dropped_or_rejected():
    svc = WalkService(_engine_cfg(), _serve_cfg())
    big = WalkQuery(start_nodes=tuple(range(65)), max_length=4)   # > 64 lanes
    long = WalkQuery(start_nodes=(1,), max_length=9)              # > 8 hops
    assert svc.submit(big) is None and svc.submit(long) is None
    assert svc.stats.dropped_oversize == 2
    with pytest.raises(ValueError):
        svc.submit(big, strict=True)


def test_oversize_contract_matrix():
    """All four strict × drop_oversize cells of the submit contract:
    silent drop / typed refusal / strict raise — and exactly the first
    two count as shed work (stats + the canonical drop taxonomy)."""
    from repro.obs.registry import DropCounters, MetricsRegistry
    from repro.serve import OversizeQuery
    big = WalkQuery(start_nodes=tuple(range(65)), max_length=4)
    assert issubclass(OversizeQuery, ValueError)   # older callers' catches

    # drop_oversize=True: non-strict drops silently (counted) ...
    reg = MetricsRegistry()
    svc = WalkService(_engine_cfg(), _serve_cfg(drop_oversize=True),
                      registry=reg)
    assert svc.submit(big) is None
    assert svc.stats.dropped_oversize == 1
    assert DropCounters.from_registry(reg).oversize == 1
    # ... strict raises, NOT counted (the raise is the caller's handling)
    with pytest.raises(OversizeQuery, match="largest bucket"):
        svc.submit(big, strict=True)
    assert svc.stats.dropped_oversize == 1
    assert DropCounters.from_registry(reg).oversize == 1

    # drop_oversize=False: non-strict raises the typed refusal (counted —
    # the service shed traffic mid-stream) ...
    reg2 = MetricsRegistry()
    svc2 = WalkService(_engine_cfg(), _serve_cfg(drop_oversize=False),
                       registry=reg2)
    with pytest.raises(OversizeQuery, match="refusing"):
        svc2.submit(big)
    assert svc2.stats.dropped_oversize == 1
    assert DropCounters.from_registry(reg2).oversize == 1
    # ... strict raises identically but stays uncounted
    with pytest.raises(OversizeQuery):
        svc2.submit(big, strict=True)
    assert svc2.stats.dropped_oversize == 1
    assert DropCounters.from_registry(reg2).oversize == 1
    # rightsized traffic is unaffected in both configs
    assert svc2.submit(WalkQuery(start_nodes=(1,), max_length=4)) is not None


def test_drain_scoped_poll_after_drain(loaded_service):
    """drain() returns exactly the queries it completed; results from
    earlier step()/tick() calls stay poll-able afterwards (the regression:
    drain used to destroy them), and drained tickets are delivered —
    popped, not double-pollable."""
    _, svc = loaded_service
    ta = svc.submit(WalkQuery(start_nodes=(1, 2), max_length=4, seed=77),
                    strict=True)
    svc.step()                     # completes ta into the poll buffer
    tb = svc.submit(WalkQuery(start_nodes=(3,), max_length=4, seed=78),
                    strict=True)
    tc = svc.submit(WalkQuery(num_walks=2, start_mode="edges", max_length=4,
                              seed=79), strict=True)
    drained = svc.drain()
    assert {r.ticket for r in drained} == {tb, tc}
    ra = svc.poll(ta)
    assert ra is not None and ra.ticket == ta
    assert svc.poll(tb) is None and svc.poll(tc) is None
    assert svc.drain() == []       # empty drain is a no-op


def test_solo_runs_are_accounted():
    """run_query_solo participates in throughput accounting: walks, hops,
    device busy time, and the path="solo" dispatch counter — without
    touching the queue/latency stats (nothing was queued)."""
    from repro.obs.registry import MetricsRegistry
    g = powerlaw_temporal_graph(100, 500, seed=3)
    reg = MetricsRegistry()
    svc = WalkService(_engine_cfg(), _serve_cfg(), registry=reg)
    svc.ingest(g.src, g.dst, g.ts)
    q = WalkQuery(start_nodes=(1, 2, 3), max_length=4, seed=5)
    _, _, lengths = svc.run_query_solo(q)
    assert svc.stats.solo_queries == 1
    assert svc.stats.walks == 3
    assert svc.stats.hops == int(np.sum(np.clip(lengths - 1, 0, None)))
    assert svc.stats.busy_s > 0.0
    assert len(svc.stats.sample_s) == 1
    assert reg.value("walks_dispatched_total", labels={"path": "solo"}) == 3
    # not "served" traffic: no ticket, no completion, no latency sample
    assert svc.stats.completed == 0 and svc.stats.submitted == 0
    assert len(svc.stats.latencies_s) == 0


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.booleans(), st.integers(1, 24),
                          st.integers(1, 8), st.integers(0, 999)),
                min_size=1, max_size=10))
def test_take_batch_fairness_property(descs):
    """Property (the docstring's no-overtaking claim): every sealed batch
    is single-group, fits the lane budget, starts at the oldest pending
    query, and takes exactly a PREFIX of its group in admission order —
    so no query is ever overtaken by a younger same-group query."""
    from repro.serve import group_key
    _, svc = _loaded_service()
    assert svc.pending_count == 0 and svc.inflight_count == 0
    for edges_mode, lanes, length, seed in descs:
        if edges_mode:
            q = WalkQuery(num_walks=lanes, start_mode="edges",
                          max_length=length, seed=seed)
        else:
            q = WalkQuery(start_nodes=tuple(range(lanes)),
                          max_length=length, seed=seed)
        assert svc.submit(q, strict=True) is not None
    budget = svc.serve_cfg.lane_buckets[-1]
    lb = svc.serve_cfg.length_buckets
    while svc.pending_count:
        before = list(svc._pending)
        key, take, lanes = svc._take_batch()
        assert take and lanes == sum(e.query.num_lanes for e in take)
        assert lanes <= budget
        assert all(group_key(e.query, lb) == key for e in take)
        # head-of-line: the batch's group is the oldest query's group,
        # and that query leads the batch
        assert take[0].ticket == before[0].ticket
        assert group_key(before[0].query, lb) == key
        # prefix rule == zero same-group overtaking: the taken tickets
        # are exactly the first len(take) same-group tickets
        same = [e.ticket for e in before if group_key(e.query, lb) == key]
        assert [e.ticket for e in take] == same[:len(take)]
        # progress: taken queries actually left the queue
        assert len(svc._pending) == len(before) - len(take)


PACK_BUCKETS = (8, 16, 64)


@settings(max_examples=40, deadline=None)
@given(st.booleans(),
       st.lists(st.tuples(st.integers(1, 24), st.integers(1, 8),
                          st.integers(0, 10_000)),
                min_size=0, max_size=6))
def test_pack_queries_roundtrip_property(edges_mode, descs):
    """Property: any same-mode query mix either exceeds every lane bucket
    (refused) or packs back-to-back with exact per-lane params — and every
    admitted query's result slice round-trips losslessly."""
    queries = []
    for lanes, length, seed in descs:
        if edges_mode:
            queries.append(WalkQuery(num_walks=lanes, start_mode="edges",
                                     max_length=length, seed=seed))
        else:
            queries.append(WalkQuery(start_nodes=tuple(range(lanes)),
                                     max_length=length, seed=seed))
    total = sum(q.num_lanes for q in queries)
    len_bucket = bucketize(max((q.max_length for q in queries), default=1),
                           (4, 8))
    bucket = bucketize(total, PACK_BUCKETS)
    if bucket is None:
        assert total > PACK_BUCKETS[-1]
        with pytest.raises(ValueError, match="exceed"):
            pack_queries(queries, PACK_BUCKETS[-1], len_bucket)
        return
    # smallest-bucket property, incl. the exact-boundary case
    assert total <= bucket
    assert all(b < total for b in PACK_BUCKETS if b < bucket)
    params, slices = pack_queries(queries, bucket, len_bucket)

    off = 0
    rid = np.asarray(params.rid)
    wid = np.asarray(params.wid)
    ml = np.asarray(params.max_len)
    active = np.asarray(params.active)
    for q, sl in zip(queries, slices):
        assert sl.offset == off and sl.count == q.num_lanes
        rows = slice(sl.offset, sl.offset + sl.count)
        assert (rid[rows] == np.int32(q.seed)).all()
        assert (wid[rows] == np.arange(q.num_lanes)).all()
        assert (ml[rows] == q.max_length).all()
        if not edges_mode:
            assert tuple(np.asarray(params.start_node)[rows]) == q.start_nodes
        off += q.num_lanes
    assert off == total
    assert active[:total].all() and not active[total:].any()

    # lossless slice round-trip: every batch cell is unique, so equality
    # proves each query got exactly its own rows/columns back
    L1 = len_bucket + 1
    nodes = np.arange(bucket * L1, dtype=np.int32).reshape(bucket, L1)
    times = nodes + 1_000_000
    lengths = np.arange(bucket, dtype=np.int32)
    for q, sl in zip(queries, slices):
        qn, qt, ql = slice_result(nodes, times, lengths, sl, q)
        rows = slice(sl.offset, sl.offset + sl.count)
        assert qn.shape == (q.num_lanes, q.max_length + 1)
        np.testing.assert_array_equal(qn, nodes[rows, :q.max_length + 1])
        np.testing.assert_array_equal(qt, times[rows, :q.max_length + 1])
        np.testing.assert_array_equal(ql, lengths[rows])


def test_pack_queries_edge_cases():
    """Zero-walk batches, exact-boundary full-capacity packs, one-over
    refusals, and over-length refusals."""
    params, slices = pack_queries([], 8, 4)
    assert slices == []
    assert not np.asarray(params.active).any()
    qs = [WalkQuery(start_nodes=tuple(range(5)), max_length=4),
          WalkQuery(start_nodes=tuple(range(3)), max_length=4)]
    params, slices = pack_queries(qs, 8, 4)       # full capacity: 5 + 3 == 8
    assert np.asarray(params.active).all()
    assert [(s.offset, s.count) for s in slices] == [(0, 5), (5, 3)]
    with pytest.raises(ValueError, match="exceed"):
        pack_queries(qs + [WalkQuery(start_nodes=(1,), max_length=4)], 8, 4)
    with pytest.raises(ValueError, match="length"):
        pack_queries([WalkQuery(start_nodes=(1,), max_length=5)], 8, 4)


def test_lane_owners_routing():
    """Host-side owner routing matches the device claim rule; padding
    lanes map to -1."""
    from repro.distributed.placement import make_placement
    from repro.serve import lane_owners
    params, _ = pack_queries(
        [WalkQuery(start_nodes=(0, 63, 64, 127), max_length=4)], 8, 4)
    own = lane_owners(params, make_placement("range", 2, 128))
    assert own.tolist() == [0, 0, 1, 1, -1, -1, -1, -1]
    own1 = lane_owners(params, make_placement("range", 1, 128))
    assert own1.tolist() == [0, 0, 0, 0] + [-1] * 4
    # hash policy routes through the same host mirror; still -1 on padding
    hown = lane_owners(params, make_placement("hash", 2, 128))
    assert (hown[:4] >= 0).all() and (hown[:4] <= 1).all()
    assert hown[4:].tolist() == [-1] * 4


def test_shape_buckets():
    assert bucketize(1, (8, 16)) == 8
    assert bucketize(8, (8, 16)) == 8
    assert bucketize(9, (8, 16)) == 16
    assert bucketize(17, (8, 16)) is None
    params, slices = pack_queries(
        [WalkQuery(start_nodes=(1, 2), max_length=3),
         WalkQuery(num_walks=3, start_mode="edges", max_length=4)], 8, 4)
    assert params.start_node.shape == (8,)
    assert [(s.offset, s.count) for s in slices] == [(0, 2), (2, 3)]
    assert np.asarray(params.active).tolist() == [True] * 5 + [False] * 3
    with pytest.raises(ValueError):
        pack_queries([WalkQuery(start_nodes=tuple(range(9)))], 8, 16)


def test_snapshot_double_buffer_consistency():
    """begin_ingest keeps the current snapshot serveable; publish swaps in
    a window byte-identical to the donating ingest path building the same
    index views."""
    g = powerlaw_temporal_graph(100, 2000, seed=5)
    batches = list(chronological_batches(g, 4))
    svc = WalkService(_engine_cfg(), _serve_cfg())
    views = svc.snapshots.views
    ref = init_window(4096, NC, 4000, views=views)
    for bs, bd, bt in batches[:-1]:
        svc.ingest(bs, bd, bt)
        ref = ingest(ref, make_batch(bs, bd, bt, capacity=svc.batch_capacity),
                     NC, views=views)
    bs, bd, bt = batches[-1]
    svc.begin_ingest(bs, bd, bt)
    assert svc.snapshots.ingest_in_flight
    v0 = svc.snapshots.version
    # the front buffer still serves while the back buffer builds
    t = svc.submit(WalkQuery(start_nodes=(1, 2, 3), max_length=4, seed=1),
                   strict=True)
    svc.step()
    r_old = svc.poll(t)
    assert r_old is not None
    before = [np.asarray(x) for x in jax.tree.leaves(svc.snapshots.current)]
    svc.publish()
    assert svc.snapshots.version == v0 + 1
    ref = ingest(ref, make_batch(bs, bd, bt, capacity=svc.batch_capacity), NC,
                 views=views)
    after = jax.tree.leaves(svc.snapshots.current)
    assert len(after) == len(jax.tree.leaves(ref))
    for got, want in zip(after, jax.tree.leaves(ref)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    # the published window is a different state than the served snapshot
    changed = any(not np.array_equal(a, np.asarray(b))
                  for a, b in zip(before, after))
    assert changed
    with pytest.raises(RuntimeError):
        svc.publish()                      # nothing in flight anymore


def test_serving_rejects_unsupported_configs():
    with pytest.raises(ValueError):
        WalkService(dataclasses.replace(
            _engine_cfg(), sampler=SamplerConfig(mode="weight")))
    with pytest.raises(ValueError):
        WalkService(dataclasses.replace(
            _engine_cfg(), sampler=SamplerConfig(mode="index",
                                                 node2vec_p=2.0)))
    # tiled scheduler silently falls back to the (equivalent) grouped path
    svc = WalkService(dataclasses.replace(
        _engine_cfg(), scheduler=SchedulerConfig(path="tiled")))
    assert svc.sched_cfg.path == "grouped"
    # the engine itself refuses a tiled lane batch
    g = powerlaw_temporal_graph(50, 500, seed=1)
    svc2 = WalkService(_engine_cfg(), _serve_cfg())
    svc2.ingest(g.src, g.dst, g.ts)
    params, _ = pack_queries([WalkQuery(start_nodes=(1,), max_length=4)],
                             8, 4)
    with pytest.raises(ValueError):
        generate_walk_lanes(
            svc2.snapshots.current.index, svc2.base_key, params,
            WalkConfig(num_walks=8, max_length=4, start_mode="nodes"),
            SamplerConfig(mode="index"), SchedulerConfig(path="tiled"))


def test_query_validation():
    with pytest.raises(ValueError):
        WalkQuery(start_nodes=(), start_mode="nodes")
    with pytest.raises(ValueError):
        WalkQuery(start_nodes=(1,), bias="gaussian")
    with pytest.raises(ValueError):
        WalkQuery(start_nodes=(1,), max_length=0)
    with pytest.raises(ValueError):
        WalkQuery(start_mode="edges", num_walks=0)
    with pytest.raises(ValueError):
        WalkQuery(start_nodes=(1,), seed=1 << 31)        # int32 round-trip
    with pytest.raises(ValueError):
        WalkQuery(start_nodes=(1 << 31,))
    assert WalkQuery(start_nodes=(1, 2)).num_lanes == 2
    assert WalkQuery(start_mode="edges", num_walks=5).num_lanes == 5


# ---------------------------------------------------------------------------
# Alias-table and second-order (node2vec) query lanes (DESIGN.md §17)
# ---------------------------------------------------------------------------


def _loaded_table_service():
    """Service whose window carries alias tables (table_weight set) but
    whose config bias stays a closed form — queries opt into the table."""
    if "tsvc" not in _SERVICE_CACHE:
        g, _ = _loaded_service()
        cfg = dataclasses.replace(
            _engine_cfg(),
            sampler=SamplerConfig(mode="index", table_weight="exponential"))
        tsvc = WalkService(cfg, _serve_cfg())
        for bs, bd, bt in chronological_batches(g, 3):
            tsvc.ingest(bs, bd, bt)
        _SERVICE_CACHE["tsvc"] = tsvc
    return _SERVICE_CACHE["svc"][0], _SERVICE_CACHE["tsvc"]


def test_table_and_node2vec_mixed_equivalence():
    """Acceptance: coalesced lanes with table-bias and node2vec codes are
    bit-identical to solo runs, mixed with plain closed-form queries."""
    _, tsvc = _loaded_table_service()
    queries = [
        WalkQuery(start_nodes=(2, 31, 63), bias="table", max_length=5,
                  seed=301),
        WalkQuery(start_nodes=(4, 40), bias="uniform", n2v_p=0.5,
                  n2v_q=2.0, max_length=6, seed=302),
        WalkQuery(start_nodes=(5, 50, 77), bias="table", n2v_p=2.0,
                  n2v_q=0.25, max_length=4, seed=303),
        WalkQuery(num_walks=3, start_mode="edges", bias="table",
                  start_bias="linear", max_length=5, seed=304),
        WalkQuery(start_nodes=(8, 16), bias="linear", max_length=7,
                  seed=305),
    ]
    _assert_solo_equals_coalesced(tsvc, queries)


def test_plain_queries_unaffected_by_tables():
    """Queries not coded table/second-order are bit-identical between a
    table-carrying service and a plain one over the same stream."""
    _, svc = _loaded_service()
    _, tsvc = _loaded_table_service()
    for q in (WalkQuery(start_nodes=(3, 33, 93), bias="exponential",
                        max_length=6, seed=400),
              WalkQuery(num_walks=4, start_mode="edges", bias="uniform",
                        start_bias="exponential", max_length=5, seed=401)):
        n0, t0, l0 = svc.run_query_solo(q)
        n1, t1, l1 = tsvc.run_query_solo(q)
        assert np.array_equal(n0, n1) and np.array_equal(t0, t1)
        assert np.array_equal(l0, l1)


def test_submit_refuses_table_queries_without_tables():
    """A service whose window has no alias tables refuses table-coded
    queries at submit time through the capability chokepoint."""
    _, svc = _loaded_service()
    with pytest.raises(ValueError, match="table"):
        svc.submit(WalkQuery(start_nodes=(1,), bias="table", max_length=4),
                   strict=True)
    # second-order queries need no tables; grouped serving accepts them
    t = svc.submit(WalkQuery(start_nodes=(1,), n2v_p=2.0, max_length=4),
                   strict=True)
    while svc.pending_count:
        svc.step()
    assert svc.poll(t) is not None


def test_second_order_query_validation():
    with pytest.raises(ValueError, match="positive"):
        WalkQuery(start_nodes=(1,), n2v_p=0.0)
    with pytest.raises(ValueError, match="positive"):
        WalkQuery(start_nodes=(1,), n2v_q=-1.0)
    with pytest.raises(ValueError, match="start_bias"):
        WalkQuery(start_nodes=(1,), start_bias="table")
    assert WalkQuery(start_nodes=(1,)).second_order is False
    assert WalkQuery(start_nodes=(1,), n2v_q=2.0).second_order is True
