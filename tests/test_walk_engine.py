"""Walk-engine invariants: causality, path equivalence, start modes,
node2vec second-order law."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (
    EngineConfig,
    SamplerConfig,
    SchedulerConfig,
    ServeConfig,
    WalkConfig,
    WindowConfig,
)
from repro.core.alias import build_tables, spec_from_sampler
from repro.core.edge_store import stack_batches, store_from_arrays
from repro.core.streaming import StreamingEngine, replay_scan
from repro.core.temporal_index import (
    FULL_VIEWS,
    IndexViews,
    build_index,
    fit_index,
)
from repro.core.validation import validate_walks, validate_walks_np
from repro.core.walk_engine import (
    NODE_PAD,
    generate_walk_lanes,
    generate_walks,
    index_views,
)
from repro.core.window import WindowState, init_window
from repro.data.synthetic import chronological_batches
from repro.obs.registry import MetricsRegistry
from repro.serve import WalkQuery, WalkService, pack_queries

ALL_PATHS = ("fullwalk", "grouped", "tiled")
BIAS_MODES = [("uniform", "index"), ("linear", "index"),
              ("exponential", "index"), ("uniform", "weight"),
              ("linear", "weight"), ("exponential", "weight")]


@pytest.mark.parametrize("bias,mode", BIAS_MODES)
def test_walks_causal(small_index, bias, mode, key):
    wcfg = WalkConfig(num_walks=512, max_length=16, start_mode="nodes")
    scfg = SamplerConfig(bias=bias, mode=mode)
    res = generate_walks(small_index, key, wcfg, scfg, SchedulerConfig())
    rep = validate_walks(small_index, res)
    assert float(rep.hop_valid_frac) == 1.0
    assert float(rep.walk_valid_frac) == 1.0


@pytest.mark.parametrize("regroup", ("bucket", "lexsort"))
@pytest.mark.parametrize("path", ALL_PATHS[1:])
def test_path_equivalence(small_index, path, regroup, key):
    """Grouped and tiled layouts emit identical walks to fullwalk, under
    both the O(W) bucket regroup (carried permutation) and the lexsort
    reference (DESIGN.md §10)."""
    wcfg = WalkConfig(num_walks=512, max_length=12, start_mode="nodes")
    scfg = SamplerConfig(bias="exponential", mode="weight")
    ref = generate_walks(small_index, key, wcfg, scfg,
                         SchedulerConfig(path="fullwalk"))
    got = generate_walks(small_index, key, wcfg, scfg,
                         SchedulerConfig(path=path, regroup=regroup,
                                         tile_walks=128, tile_edges=512))
    assert jnp.array_equal(ref.nodes, got.nodes)
    assert jnp.array_equal(ref.times, got.times)
    assert jnp.array_equal(ref.lengths, got.lengths)


def test_path_equivalence_hub_graph(hub_index, key):
    """Equivalence must hold under mega-hub skew (oversize fallback path)."""
    wcfg = WalkConfig(num_walks=1024, max_length=10, start_mode="nodes")
    scfg = SamplerConfig(bias="exponential", mode="index")
    ref = generate_walks(hub_index, key, wcfg, scfg,
                         SchedulerConfig(path="fullwalk"))
    for path in ("grouped", "tiled"):
        for regroup in ("bucket", "lexsort"):
            got = generate_walks(hub_index, key, wcfg, scfg,
                                 SchedulerConfig(path=path, regroup=regroup,
                                                 tile_walks=256,
                                                 tile_edges=1024))
            assert jnp.array_equal(ref.nodes, got.nodes), (path, regroup)


def test_regroup_time_subsort_off_equivalence(small_index, key):
    """Node-only bucketing (no time subsort) is still byte-equivalent —
    grouping is purely an execution layout."""
    wcfg = WalkConfig(num_walks=512, max_length=10, start_mode="nodes")
    scfg = SamplerConfig(bias="linear", mode="weight")
    ref = generate_walks(small_index, key, wcfg, scfg,
                         SchedulerConfig(path="fullwalk"))
    got = generate_walks(small_index, key, wcfg, scfg,
                         SchedulerConfig(path="grouped", regroup="bucket",
                                         regroup_time=False))
    assert jnp.array_equal(ref.nodes, got.nodes)
    assert jnp.array_equal(ref.lengths, got.lengths)


def test_generate_walks_donated_matches_and_consumes(small_index, key):
    """Donated entry point: byte-identical results, buffers consumed, and
    chaining the previous result's arrays works (DESIGN.md §10)."""
    from repro.core.walk_engine import (WalkBuffers, alloc_walk_buffers,
                                        generate_walks_donated)
    wcfg = WalkConfig(num_walks=256, max_length=10, start_mode="nodes")
    scfg = SamplerConfig(bias="exponential", mode="weight")
    cfg = SchedulerConfig(path="grouped", regroup="bucket")
    ref = generate_walks(small_index, key, wcfg, scfg, cfg)
    bufs = alloc_walk_buffers(wcfg)
    got = generate_walks_donated(small_index, key, bufs, wcfg, scfg, cfg)
    assert jnp.array_equal(ref.nodes, got.nodes)
    assert jnp.array_equal(ref.times, got.times)
    assert jnp.array_equal(ref.lengths, got.lengths)
    with pytest.raises(Exception):
        np.asarray(bufs.nodes)          # storage was donated
    # round 2 reuses round 1's result arrays as buffers
    key2 = jax.random.PRNGKey(99)
    ref2 = generate_walks(small_index, key2, wcfg, scfg, cfg)
    got2 = generate_walks_donated(small_index, key2,
                                  WalkBuffers(got.nodes, got.times),
                                  wcfg, scfg, cfg)
    assert jnp.array_equal(ref2.nodes, got2.nodes)
    assert jnp.array_equal(ref2.lengths, got2.lengths)


def test_edges_start_mode(small_index, key):
    wcfg = WalkConfig(num_walks=256, max_length=8, start_mode="edges")
    scfg = SamplerConfig(start_bias="linear")
    res = generate_walks(small_index, key, wcfg, scfg, SchedulerConfig())
    rep = validate_walks(small_index, res)
    assert float(rep.hop_valid_frac) == 1.0
    # edges mode records (src, dst) of the start edge
    lengths = np.asarray(res.lengths)
    assert lengths.min() >= 2


def test_all_nodes_start_mode(small_index, key):
    wcfg = WalkConfig(num_walks=512, max_length=8, start_mode="all_nodes")
    res = generate_walks(small_index, key, wcfg, SamplerConfig(),
                         SchedulerConfig())
    nodes0 = np.asarray(res.nodes[:, 0])
    live = nodes0 != NODE_PAD
    # walk w starts at node w % node_capacity when that node is active
    expect = np.arange(512) % 256
    assert np.all(nodes0[live] == expect[live])


def test_walk_buffer_padding(small_index, key):
    res = generate_walks(small_index, key,
                         WalkConfig(num_walks=128, max_length=12,
                                    start_mode="nodes"),
                         SamplerConfig(), SchedulerConfig())
    nodes = np.asarray(res.nodes)
    lengths = np.asarray(res.lengths)
    for w in range(128):
        assert np.all(nodes[w, lengths[w]:] == NODE_PAD)
        assert np.all(nodes[w, :lengths[w]] != NODE_PAD)


def test_validator_detects_corruption(small_index, key):
    res = generate_walks(small_index, key,
                         WalkConfig(num_walks=128, max_length=12,
                                    start_mode="nodes"),
                         SamplerConfig(), SchedulerConfig())
    rep0 = validate_walks(small_index, res)
    assert float(rep0.walk_valid_frac) == 1.0
    # corrupt: swap a hop's timestamps to violate monotonicity
    lengths = np.asarray(res.lengths)
    w = int(np.argmax(lengths >= 3))
    if lengths[w] >= 3:
        times = res.times.at[w, 1].set(res.times[w, 2] + 1)
        bad = res._replace(times=times)
        rep = validate_walks(small_index, bad)
        assert float(rep.walk_valid_frac) < 1.0


def test_node2vec_second_order_law(key):
    """With q -> inf, non-returning non-common hops are suppressed."""
    # triangle u->v at t=1, v->u at t=2, v->w at t=2, u->w edge absent
    src = np.asarray([0, 1, 1], np.int32)
    dst = np.asarray([1, 0, 2], np.int32)
    ts = np.asarray([1, 2, 2], np.int32)
    store = store_from_arrays(src, dst, ts, edge_capacity=8, node_capacity=4)
    idx = build_index(store, 4)
    wcfg = WalkConfig(num_walks=4096, max_length=3, start_mode="all_nodes")
    # p=inf suppresses return (1->0); q=1 keeps out. Start at node 0 only.
    scfg = SamplerConfig(bias="uniform", mode="index",
                         node2vec_p=1e9, node2vec_q=1.0)
    res = generate_walks(idx, key, wcfg, scfg, SchedulerConfig(path="fullwalk"))
    nodes = np.asarray(res.nodes)
    started_at_0 = nodes[:, 0] == 0
    two_hops = np.asarray(res.lengths) >= 3
    sel = started_at_0 & two_hops
    # from 0 -> 1 at t=1 the second hop is 1->0 (return, suppressed by p)
    # or 1->2; returns should be rare (8 rejection rounds each 1/2 proposal:
    # residual fallback keeps a tiny fraction)
    second = nodes[sel, 2]
    frac_return = np.mean(second == 0) if sel.sum() else 0.0
    assert frac_return < 0.02


def test_np_validator_agrees(small_index, small_graph, key):
    res = generate_walks(small_index, key,
                         WalkConfig(num_walks=256, max_length=10,
                                    start_mode="nodes"),
                         SamplerConfig(), SchedulerConfig())
    rep = validate_walks(small_index, res)
    hv, wv = validate_walks_np(
        (small_graph.src, small_graph.dst, small_graph.ts),
        np.asarray(res.nodes), np.asarray(res.times),
        np.asarray(res.lengths))
    assert abs(float(rep.hop_valid_frac) - hv) < 1e-6
    assert abs(float(rep.walk_valid_frac) - wv) < 1e-6


def test_stats_collection(small_index, key):
    from repro.core import scheduler as sched
    res = generate_walks(small_index, key,
                         WalkConfig(num_walks=512, max_length=8,
                                    start_mode="nodes"),
                         SamplerConfig(), SchedulerConfig(),
                         collect_stats=True)
    stats = np.asarray(res.stats)
    assert stats.shape == (8, sched.NUM_STATS)
    # alive counts decrease monotonically
    alive = stats[:, sched.STAT_ALIVE]
    assert np.all(np.diff(alive) <= 0)
    # grouped modeled bytes never exceed fullwalk modeled bytes
    assert np.all(stats[:, sched.STAT_BYTES_GROUPED]
                  <= stats[:, sched.STAT_BYTES_FULLWALK] + 1e-6)


# ---------------------------------------------------------------------------
# Index view sets (DESIGN.md §3): an engine builds only the views its walks
# read, and walks, replays and validation read the same from them as from
# the full build
# ---------------------------------------------------------------------------

# (sampler, path) of each supported mode; the view set each derives
MODE_CASES = {
    "index_linear": (SamplerConfig(bias="linear", mode="index"), "grouped"),
    "index_exponential": (SamplerConfig(bias="exponential", mode="index"),
                          "grouped"),
    "weight": (SamplerConfig(bias="linear", mode="weight",
                             start_bias="exponential"), "grouped"),
    "node2vec": (SamplerConfig(bias="exponential", mode="index",
                               node2vec_p=0.5, node2vec_q=2.0), "grouped"),
    "table": (SamplerConfig(bias="table", mode="index",
                            table_weight="exponential"), "grouped"),
}
VIEW_CASES = {
    "index_linear": IndexViews(prefixes=False, adjacency=False),
    "index_exponential": IndexViews(prefixes=False, adjacency=False),
    "weight": IndexViews(prefixes=True, adjacency=False),
    "node2vec": IndexViews(prefixes=False, adjacency=True),
    "table": IndexViews(prefixes=False, adjacency=False),
}


def _same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


def _assert_same_tree(got, want):
    g, w = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(g) == len(w)
    assert all(_same_bits(x, y) for x, y in zip(g, w))


def _tables(scfg, index):
    spec = spec_from_sampler(scfg)
    return spec, (build_tables(index, spec) if spec is not None else None)


@pytest.mark.parametrize("case", sorted(MODE_CASES))
def test_walks_from_narrowed_index_match_full(small_index, case, key):
    scfg, path = MODE_CASES[case]
    views = index_views(scfg, path)
    assert views == VIEW_CASES[case]
    narrow = fit_index(small_index, views)
    assert narrow.views == views
    _, tables = _tables(scfg, small_index)
    wcfg = WalkConfig(num_walks=256, max_length=12, start_mode="edges")
    sched_cfg = SchedulerConfig(path=path)
    want = generate_walks(small_index, key, wcfg, scfg, sched_cfg,
                          tables=tables)
    got = generate_walks(narrow, key, wcfg, scfg, sched_cfg, tables=tables)
    _assert_same_tree(got, want)
    assert int(jnp.sum(got.lengths >= 2)) > 0


@pytest.mark.parametrize("case", sorted(MODE_CASES))
def test_replay_scan_with_derived_views_matches_full(small_graph, case, key):
    """The replay carries only the derived views and rebuilds only them;
    its stats, walks and every index field it holds equal the full
    build's, batch after batch."""
    scfg, path = MODE_CASES[case]
    views = index_views(scfg, path)
    spec = spec_from_sampler(scfg)
    g = small_graph
    wcfg = WalkConfig(num_walks=256, max_length=10, start_mode="nodes")
    sched_cfg = SchedulerConfig(path=path)
    out = {}
    for name, v in (("narrow", views), ("full", FULL_VIEWS)):
        state = init_window(2048, 256, 600, table=spec, views=v)
        stacked = stack_batches(chronological_batches(g, 4), 1024)
        out[name] = replay_scan(state, stacked, key, 256, wcfg, scfg,
                                sched_cfg, table=spec, views=v)
    (ns, nstats, nwalks), (fs, fstats, fwalks) = out["narrow"], out["full"]
    _assert_same_tree(nstats, fstats)
    _assert_same_tree(nwalks, fwalks)
    assert ns.index.views == views
    _assert_same_tree(ns.index, fit_index(fs.index, views))
    _assert_same_tree(ns.tables, fs.tables)
    assert int(nstats.ingested[-1]) == g.src.shape[0]


def _bulk_state(g, n, delta):
    """A window state built the way the benchmark's bulk load builds one:
    a bare ``build_index`` (every view) handed over as a ``WindowState``."""
    store = store_from_arrays(g.src[:n], g.dst[:n], g.ts[:n],
                              edge_capacity=2048, node_capacity=256)
    i32 = partial(jnp.asarray, dtype=jnp.int32)
    return WindowState(index=build_index(store, 256),
                       t_now=i32(int(g.ts[n - 1])), window=i32(delta),
                       ingested=i32(n), late_drops=i32(0),
                       overflow_drops=i32(0))


def test_engine_fits_a_full_bulk_state(small_graph):
    """An engine handed a full-built state runs replay_device and
    sample_walks_donated, narrows the state to its views, and returns
    what the full-view programs return from the same state and keys."""
    g = small_graph
    n, delta = 1500, 600
    scfg, path = MODE_CASES["index_exponential"]
    cfg = EngineConfig(
        window=WindowConfig(edge_capacity=2048, node_capacity=256,
                            duration=delta),
        sampler=scfg, scheduler=SchedulerConfig(path=path), seed=5)
    wcfg = WalkConfig(num_walks=256, max_length=10, start_mode="nodes")
    rest = [(g.src[s:s + 500], g.dst[s:s + 500], g.ts[s:s + 500])
            for s in range(n, g.src.shape[0], 500)]

    eng = StreamingEngine(cfg, batch_capacity=512, registry=MetricsRegistry())
    eng.state = _bulk_state(g, n, delta)
    stats, walks, _ = eng.replay_device(rest, wcfg, return_walks=True)
    assert eng.state.index.views == IndexViews(False, False)
    assert eng.registry.value("index_edge_columns") == 4
    res = eng.sample_walks_donated(wcfg)

    k0, k1 = jax.random.split(jax.random.PRNGKey(5))
    _, k2 = jax.random.split(k0)
    fs, fstats, fwalks = replay_scan(
        _bulk_state(g, n, delta), stack_batches(rest, 512), k1, 256, wcfg,
        scfg, cfg.scheduler)
    assert fs.index.views == FULL_VIEWS
    _assert_same_tree(tuple(stats), tuple(fstats))
    _assert_same_tree(walks, fwalks)
    want = generate_walks(fs.index, k2, wcfg, scfg, cfg.scheduler)
    _assert_same_tree(res, want)

    # the walks cell: sample_walks_donated straight from a bulk load
    eng2 = StreamingEngine(cfg, batch_capacity=512,
                           registry=MetricsRegistry())
    eng2.state = _bulk_state(g, n, delta)
    wcfg_e = WalkConfig(num_walks=256, max_length=10, start_mode="edges")
    res2 = eng2.sample_walks_donated(wcfg_e)
    assert eng2.state.index.views == IndexViews(False, False)
    _assert_same_tree(res2, generate_walks(_bulk_state(g, n, delta).index,
                                           k1, wcfg_e, scfg, cfg.scheduler))


def test_walk_service_answers_second_order_query(small_graph):
    """A service's snapshots keep the adjacency view, so a second-order
    query is answered, coalesced as solo, and as from a full index."""
    g = small_graph
    cfg = EngineConfig(
        window=WindowConfig(edge_capacity=4096, node_capacity=256,
                            duration=4000),
        sampler=SamplerConfig(mode="index"),
        scheduler=SchedulerConfig(path="grouped"))
    svc = WalkService(cfg, ServeConfig(lane_buckets=(8, 16),
                                       length_buckets=(8,)),
                      registry=MetricsRegistry())
    for bs, bd, bt in chronological_batches(g, 3):
        svc.ingest(bs, bd, bt)
    index = svc.snapshots.current.index
    assert index.views == IndexViews(prefixes=False, adjacency=True)
    assert svc.registry.value("index_edge_columns") == 6
    q = WalkQuery(start_nodes=(1, 7, 30, 99), bias="linear", n2v_p=0.5,
                  n2v_q=2.0, max_length=8, seed=17)
    t = svc.submit(q, strict=True)
    while svc.pending_count:
        svc.step()
    res = svc.poll(t)
    solo = svc.run_query_solo(q)
    assert np.array_equal(res.nodes, solo[0])
    assert np.array_equal(res.lengths, solo[2])
    assert int(np.max(res.lengths)) >= 3
    params, (sl,) = pack_queries([q], q.num_lanes, q.max_length)
    wcfg = WalkConfig(num_walks=q.num_lanes, max_length=q.max_length)
    full = generate_walk_lanes(fit_index(index, FULL_VIEWS), svc.base_key,
                               params, wcfg, cfg.sampler, cfg.scheduler,
                               second_order=True)
    assert np.array_equal(np.asarray(full.nodes)[:len(q.start_nodes)],
                          solo[0])


def test_validate_walks_same_on_narrowed_index(small_index, key):
    """The validator builds its own adjacency view for an index that has
    none, and reports what it reports on the full index, a corrupted
    walk included."""
    narrow = fit_index(small_index, IndexViews(False, False))
    res = generate_walks(narrow, key,
                         WalkConfig(num_walks=128, max_length=12,
                                    start_mode="nodes"),
                         SamplerConfig(), SchedulerConfig())
    lengths = np.asarray(res.lengths)
    w = int(np.argmax(lengths >= 3))
    bad = res._replace(nodes=res.nodes.at[w, 2].set(
        (res.nodes[w, 2] + 1) % 256))
    for r in (res, bad):
        _assert_same_tree(validate_walks(narrow, r),
                          validate_walks(small_index, r))
    assert float(validate_walks(narrow, res).walk_valid_frac) == 1.0
    assert float(validate_walks(narrow, bad).walk_valid_frac) < 1.0


def test_walks_refuse_an_index_without_their_views(small_index, key):
    narrow = fit_index(small_index, IndexViews(False, False))
    for case in ("weight", "node2vec"):
        scfg, path = MODE_CASES[case]
        with pytest.raises(ValueError, match="index views"):
            generate_walks(narrow, key,
                           WalkConfig(num_walks=128, max_length=4),
                           scfg, SchedulerConfig(path=path))
