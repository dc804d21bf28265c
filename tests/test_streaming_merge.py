"""Merge-based ingest and device-resident replay (DESIGN.md §4).

The merge path must be *byte-identical* to the seed sort path — same store
contents, same counters, same index arrays — and the `lax.scan` replay
driver must reproduce the host-loop driver's window trajectory exactly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (
    EngineConfig,
    SamplerConfig,
    SchedulerConfig,
    WalkConfig,
    WindowConfig,
)
from repro.core.edge_store import make_batch, stack_batches
from repro.core.streaming import (
    ReplayStats,
    StreamingEngine,
    ingest_and_walk,
    ingest_and_walk_donated,
    replay_scan,
)
from repro.core.walk_engine import (
    WalkBuffers,
    alloc_walk_buffers,
    generate_walks,
)
from repro.core.window import ingest, ingest_sort, init_window
from repro.data.synthetic import chronological_batches, powerlaw_temporal_graph
from repro.obs import new_registry


def _assert_states_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Merge == sort equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_merge_matches_sort_randomized(seed):
    """Randomized streams with ties, late edges, and overflow: the merge
    path and the seed argsort path produce identical WindowStates after
    every batch."""
    rng = np.random.default_rng(seed)
    sm = init_window(edge_capacity=128, node_capacity=16, window=300)
    ss = init_window(edge_capacity=128, node_capacity=16, window=300)
    t = 0
    for _ in range(10):
        n = int(rng.integers(1, 60))
        # heavy timestamp ties + out-of-window stragglers + bursts
        ts = rng.integers(t - 150, t + 200, n).astype(np.int32) // 3 * 3
        t = max(t, int(ts.max()))
        src = rng.integers(0, 16, n)
        dst = rng.integers(0, 16, n)
        batch = make_batch(src, dst, ts, capacity=64)
        sm = ingest(sm, batch, 16)
        ss = ingest_sort(ss, batch, 16)
        _assert_states_equal(sm, ss)


def test_merge_matches_sort_on_graph_stream():
    g = powerlaw_temporal_graph(64, 4000, seed=11)
    sm = init_window(edge_capacity=2048, node_capacity=64, window=2000)
    ss = init_window(edge_capacity=2048, node_capacity=64, window=2000)
    for bs, bd, bt in chronological_batches(g, 8):
        batch = make_batch(bs, bd, bt, capacity=768)
        sm = ingest(sm, batch, 64)
        ss = ingest_sort(ss, batch, 64)
    _assert_states_equal(sm, ss)


def test_merge_empty_batch_and_empty_store():
    """Degenerate runs: empty batch into empty store, then a real batch,
    then another empty batch."""
    sm = init_window(edge_capacity=32, node_capacity=4, window=100)
    ss = init_window(edge_capacity=32, node_capacity=4, window=100)
    empty = make_batch([], [], [], capacity=8)
    full = make_batch([0, 1, 2], [1, 2, 3], [5, 5, 9], capacity=8)
    for batch in (empty, full, empty):
        sm = ingest(sm, batch, 4)
        ss = ingest_sort(ss, batch, 4)
        _assert_states_equal(sm, ss)


# ---------------------------------------------------------------------------
# Device-resident replay
# ---------------------------------------------------------------------------


def _engine(num_nodes=128, edge_capacity=4096, duration=2000, seed=0):
    cfg = EngineConfig(
        window=WindowConfig(duration=duration, edge_capacity=edge_capacity,
                            node_capacity=num_nodes),
        sampler=SamplerConfig(bias="exponential", mode="index"),
        scheduler=SchedulerConfig(path="grouped"),
        seed=seed,
    )
    return StreamingEngine(cfg, batch_capacity=1024,
                           registry=new_registry())


def test_replay_scan_matches_host_loop():
    """The scan driver's window trajectory == the host loop's, batch for
    batch, and the final states are identical."""
    g = powerlaw_temporal_graph(128, 6000, seed=21)
    wcfg = WalkConfig(num_walks=128, max_length=6, start_mode="nodes")

    host = _engine()
    host.replay(chronological_batches(g, 6), wcfg)

    dev = _engine()
    stats, elapsed = dev.replay_device(chronological_batches(g, 6), wcfg)

    assert isinstance(stats, ReplayStats)
    assert stats.edges_active.shape == (6,)
    assert stats.edges_active.tolist() == host.stats.edges_active
    assert int(stats.ingested[-1]) == 6000
    assert elapsed > 0
    _assert_states_equal(host.state, dev.state)


def test_replay_scan_stats_on_device_until_read():
    """replay_scan itself returns device arrays (no per-batch host sync):
    the single materialization point is the caller's block_until_ready."""
    g = powerlaw_temporal_graph(64, 2000, seed=5)
    eng = _engine(num_nodes=64, edge_capacity=2048)
    stacked = stack_batches(chronological_batches(g, 4), 1024)
    wcfg = WalkConfig(num_walks=64, max_length=4, start_mode="nodes")
    state, stats, walks = replay_scan(
        eng.state, stacked, jax.random.PRNGKey(0),
        eng.cfg.window.node_capacity, wcfg, eng.cfg.sampler,
        eng.cfg.scheduler)
    for leaf in jax.tree_util.tree_leaves((state, stats, walks)):
        assert isinstance(leaf, jax.Array)
    assert walks.nodes.shape == (64, 5)
    jax.block_until_ready(stats)
    assert int(stats.ingested[-1]) == 2000


def test_ingest_and_walk_fused_step_matches_separate_dispatches():
    """The fused (donating) step == ingest followed by generate_walks with
    the same key: identical window state AND identical walks."""
    g = powerlaw_temporal_graph(64, 1000, seed=13)
    scfg = SamplerConfig(bias="exponential", mode="index")
    sched = SchedulerConfig(path="grouped")
    wcfg = WalkConfig(num_walks=64, max_length=4, start_mode="nodes")
    key = jax.random.PRNGKey(7)
    batch = make_batch(g.src, g.dst, g.ts, capacity=1024)

    ref = init_window(edge_capacity=2048, node_capacity=64, window=10_000)
    ref = ingest_sort(ref, batch, 64)
    ref_walks = generate_walks(ref.index, key, wcfg, scfg, sched)

    fused_in = init_window(edge_capacity=2048, node_capacity=64,
                           window=10_000)
    fused, walks = ingest_and_walk(fused_in, batch, key, 64, wcfg, scfg,
                                   sched)
    _assert_states_equal(ref, fused)
    np.testing.assert_array_equal(np.asarray(ref_walks.nodes),
                                  np.asarray(walks.nodes))
    np.testing.assert_array_equal(np.asarray(ref_walks.lengths),
                                  np.asarray(walks.lengths))
    # donation consumed the input state
    with pytest.raises(Exception):
        np.asarray(fused_in.index.store.ts)


def test_ingest_and_walk_donated_chain_matches_separate_dispatches():
    """The fully donated fused step (state + walk buffers consumed) equals
    the non-donating path batch for batch when chained through
    ``WalkBuffers(res.nodes, res.times)`` (DESIGN.md §10)."""
    g = powerlaw_temporal_graph(64, 2000, seed=17)
    scfg = SamplerConfig(bias="exponential", mode="weight")
    sched = SchedulerConfig(path="grouped")
    wcfg = WalkConfig(num_walks=64, max_length=6, start_mode="nodes")
    batches = [make_batch(bs, bd, bt, capacity=1024)
               for bs, bd, bt in chronological_batches(g, 3)]

    ref_state = init_window(edge_capacity=2048, node_capacity=64,
                            window=10_000)
    don_state = init_window(edge_capacity=2048, node_capacity=64,
                            window=10_000)
    bufs = alloc_walk_buffers(wcfg)
    prev_res = None
    for i, batch in enumerate(batches):
        key = jax.random.PRNGKey(100 + i)
        ref_state = ingest_sort(ref_state, batch, 64)
        ref_walks = generate_walks(ref_state.index, key, wcfg, scfg, sched)
        don_state, res = ingest_and_walk_donated(
            don_state, batch, bufs, key, 64, wcfg, scfg, sched)
        np.testing.assert_array_equal(np.asarray(ref_walks.nodes),
                                      np.asarray(res.nodes))
        np.testing.assert_array_equal(np.asarray(ref_walks.lengths),
                                      np.asarray(res.lengths))
        if prev_res is not None:
            with pytest.raises(Exception):       # consumed by this round
                np.asarray(prev_res.nodes)
        bufs = WalkBuffers(res.nodes, res.times)
        prev_res = res
    _assert_states_equal(ref_state, don_state)


def test_engine_sample_walks_donated_pool():
    """StreamingEngine.sample_walks_donated: identical walks to
    sample_walks for the same seed, per-shape buffer reuse (the previous
    same-shape result is consumed), and the emitted-walk count recorded
    once per call."""
    g = powerlaw_temporal_graph(64, 3000, seed=9)
    wcfg = WalkConfig(num_walks=128, max_length=6, start_mode="nodes")
    plain = _engine(num_nodes=64, edge_capacity=4096, duration=100_000)
    pool = _engine(num_nodes=64, edge_capacity=4096, duration=100_000)
    plain.ingest_batch(g.src[:1000], g.dst[:1000], g.ts[:1000])
    pool.ingest_batch(g.src[:1000], g.dst[:1000], g.ts[:1000])

    a1 = plain.sample_walks(wcfg)
    b1 = pool.sample_walks_donated(wcfg)
    np.testing.assert_array_equal(np.asarray(a1.nodes),
                                  np.asarray(b1.nodes))
    a2 = plain.sample_walks(wcfg)
    b2 = pool.sample_walks_donated(wcfg)      # consumes b1's buffers
    np.testing.assert_array_equal(np.asarray(a2.nodes),
                                  np.asarray(b2.nodes))
    with pytest.raises(Exception):
        np.asarray(b1.nodes)
    reg = pool.registry
    assert reg.histogram("stage_seconds",
                         labels={"stage": "walks.publish"}).count == 2
    emitted = sum(int(np.sum(np.asarray(a.lengths) >= 2)) for a in (a1, a2))
    assert 0 < emitted <= 2 * wcfg.num_walks
    assert reg.value("walks_emitted_total",
                     labels={"driver": "host"}) == emitted


def test_engine_sample_walks_sharded():
    from repro.core.validation import validate_walks
    g = powerlaw_temporal_graph(64, 3000, seed=9)
    eng = _engine(num_nodes=64, edge_capacity=4096, duration=100_000)
    eng.ingest_batch(g.src[:1000], g.dst[:1000], g.ts[:1000])
    wcfg = WalkConfig(num_walks=128, max_length=6, start_mode="nodes")
    res = eng.sample_walks_sharded(wcfg)
    assert res.nodes.shape == (128, 7)
    rep = validate_walks(eng.state.index, res)
    assert float(rep.walk_valid_frac) == 1.0
    assert eng.registry.histogram(
        "stage_seconds", labels={"stage": "walks.publish"}).count == 1
    assert eng.registry.value("walks_emitted_total",
                              labels={"driver": "host"}) == int(
        np.sum(np.asarray(res.lengths) >= 2))


def test_replay_scan_walk_lengths_sane():
    g = powerlaw_temporal_graph(64, 3000, seed=8)
    eng = _engine(num_nodes=64, edge_capacity=4096, duration=10_000)
    wcfg = WalkConfig(num_walks=256, max_length=8, start_mode="nodes")
    stats, _ = eng.replay_device(chronological_batches(g, 5), wcfg)
    # every batch generated walks; mean length in [1, max_length+1]
    assert np.all(stats.mean_len >= 1.0)
    assert np.all(stats.mean_len <= wcfg.max_length + 1)


# ---------------------------------------------------------------------------
# Counter accounting across multi-batch replays (late / overflow / ingested)
# ---------------------------------------------------------------------------


def test_counters_multibatch_accounting():
    """ingested / late_drops / overflow_drops tally exactly across a
    multi-batch replay, including an overflow batch larger than the
    remaining capacity."""
    cap = 16
    st = init_window(edge_capacity=cap, node_capacity=8, window=1000)

    # batch 1: 10 edges, fits
    st = ingest(st, make_batch(np.zeros(10, np.int32), np.ones(10, np.int32),
                               np.arange(10, dtype=np.int32),
                               capacity=32), 8)
    assert int(st.ingested) == 10
    assert int(st.late_drops) == 0
    assert int(st.overflow_drops) == 0
    assert int(st.index.store.num_edges) == 10

    # batch 2: 12 more live edges with only 6 slots free -> 6 oldest drop
    ts2 = np.arange(10, 22, dtype=np.int32)
    st = ingest(st, make_batch(np.zeros(12, np.int32), np.ones(12, np.int32),
                               ts2, capacity=32), 8)
    assert int(st.ingested) == 22
    assert int(st.overflow_drops) == 6
    assert int(st.index.store.num_edges) == cap
    kept = np.asarray(st.index.store.ts)[:cap]
    assert kept.tolist() == list(range(6, 22))   # newest 16 survive

    # batch 3: 2 late edges (t_now=21, window=1000 -> nothing late yet at
    # these times), so push t_now forward first with one fresh edge ...
    st = ingest(st, make_batch([3], [4], [2000], capacity=32), 8)
    # ... then: ts 900 < 2000-1000 is late; ts 1500 is kept
    st = ingest(st, make_batch([1, 2], [2, 3], [900, 1500], capacity=32), 8)
    assert int(st.ingested) == 25
    assert int(st.late_drops) == 1
    # store: everything older than 1000 evicted; only ts 1500 and 2000 left
    n = int(st.index.store.num_edges)
    assert np.asarray(st.index.store.ts)[:n].tolist() == [1500, 2000]
    # overflow counter untouched by eviction/late paths
    assert int(st.overflow_drops) == 6


def test_counters_overflow_exceeds_remaining_capacity_scan_driver():
    """Same accounting via the device-resident driver: cumulative counters
    reported per batch match a brute-force host simulation."""
    cap = 64
    rng = np.random.default_rng(42)
    batches = []
    t = 0
    for _ in range(6):
        n = int(rng.integers(20, 60))        # overflows a 64-slot store fast
        ts = np.sort(rng.integers(t, t + 50, n)).astype(np.int32)
        t = int(ts.max())
        batches.append((rng.integers(0, 8, n).astype(np.int32),
                        rng.integers(0, 8, n).astype(np.int32), ts))

    cfg = EngineConfig(
        window=WindowConfig(duration=10_000, edge_capacity=cap,
                            node_capacity=8),
        sampler=SamplerConfig(bias="uniform", mode="index"),
        scheduler=SchedulerConfig(path="grouped"),
    )
    eng = StreamingEngine(cfg, batch_capacity=64)
    wcfg = WalkConfig(num_walks=32, max_length=4, start_mode="nodes")
    stats, _ = eng.replay_device(batches, wcfg)

    # brute-force per-batch expectation (window never evicts here)
    total, live, overflow = 0, 0, []
    for _, _, ts in batches:
        total += len(ts)
        live = min(live + len(ts), cap)
        overflow.append(total - live)
    assert int(stats.ingested[-1]) == total
    assert stats.overflow_drops.tolist() == overflow
    assert stats.late_drops.tolist() == [0] * len(batches)
    assert stats.edges_active.tolist() == [min(cap, c) for c in
                                           np.cumsum([len(b[2]) for b in
                                                      batches]).tolist()]
