"""Dual-index invariants (paper §2.3): both views index the same edge
multiset; node regions and temporal cutoffs match a numpy oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.edge_store import TS_PAD, store_from_arrays
from repro.core.temporal_index import (
    FULL_VIEWS,
    IndexViews,
    TemporalIndex,
    adjacency_contains,
    adjacency_view,
    build_index,
    empty_index,
    fit_index,
    node_range,
    ranged_search,
    temporal_cutoff,
)


def test_views_same_multiset(small_index, small_graph):
    idx = small_index
    n = int(idx.num_edges)
    store_triples = sorted(zip(np.asarray(idx.store.src)[:n].tolist(),
                               np.asarray(idx.store.dst)[:n].tolist(),
                               np.asarray(idx.store.ts)[:n].tolist()))
    ns_triples = sorted(zip(np.asarray(idx.ns_src)[:n].tolist(),
                            np.asarray(idx.ns_dst)[:n].tolist(),
                            np.asarray(idx.ns_ts)[:n].tolist()))
    raw = sorted(zip(small_graph.src.tolist(), small_graph.dst.tolist(),
                     small_graph.ts.tolist()))
    assert store_triples == raw == ns_triples


def test_store_is_ts_sorted(small_index):
    ts = np.asarray(small_index.store.ts)
    assert np.all(np.diff(ts.astype(np.int64)) >= 0)


def test_ns_view_sorted_by_node_then_ts(small_index):
    idx = small_index
    n = int(idx.num_edges)
    src = np.asarray(idx.ns_src)[:n].astype(np.int64)
    ts = np.asarray(idx.ns_ts)[:n].astype(np.int64)
    key = src * (1 << 32) + ts
    assert np.all(np.diff(key) >= 0)


def test_adjacency_view_is_stable_src_dst_ts_order():
    """adj_order is the stable (src, dst, ts) sort of the store, ties (equal
    triples) in store order — on a store dense in repeated edges."""
    rng = np.random.default_rng(3)
    n = 3000
    src = rng.integers(0, 12, n)
    dst = rng.integers(0, 12, n)
    ts = rng.integers(0, 40, n)
    store = store_from_arrays(src, dst, ts, edge_capacity=4096,
                              node_capacity=16)
    idx = build_index(store, 16)
    s_src, s_dst, s_ts = (np.asarray(a) for a in (store.src, store.dst,
                                                  store.ts))
    want = np.lexsort((s_ts, s_dst, s_src))       # numpy's is stable
    np.testing.assert_array_equal(np.asarray(idx.adj_order), want)
    np.testing.assert_array_equal(np.asarray(idx.adj_dst), s_dst[want])


def test_node_ranges_match_numpy(small_index, small_graph):
    idx = small_index
    g = small_graph
    for v in [0, 1, 5, 50, 199, 255]:
        a, b = node_range(idx, jnp.asarray(v))
        expected = int(np.sum(g.src == v))
        assert int(b) - int(a) == expected


def test_temporal_cutoff_matches_numpy(small_index, small_graph):
    idx = small_index
    g = small_graph
    rng = np.random.default_rng(0)
    nodes = rng.integers(0, 200, 64)
    times = rng.integers(0, 10_000, 64)
    a, b = node_range(idx, jnp.asarray(nodes, jnp.int32))
    c = temporal_cutoff(idx, a, b, jnp.asarray(times, jnp.int32))
    for i, (v, t) in enumerate(zip(nodes, times)):
        mask = g.src == v
        expected = int(np.sum(g.ts[mask] > t))
        assert int(b[i]) - int(c[i]) == expected, (v, t)


def test_group_counts_match_numpy(small_index, small_graph):
    idx = small_index
    g = small_graph
    counts = np.asarray(idx.node_group_counts)
    for v in [0, 1, 2, 10, 100, 199]:
        expected = len(np.unique(g.ts[g.src == v]))
        assert counts[v] == expected


def test_adjacency_contains(small_index, small_graph):
    idx = small_index
    g = small_graph
    u0, w0 = int(g.src[0]), int(g.dst[0])
    assert bool(adjacency_contains(idx, jnp.asarray(u0), jnp.asarray(w0)))
    # a non-edge: find a pair not present
    pairs = set(zip(g.src.tolist(), g.dst.tolist()))
    for w in range(200):
        if (u0, w) not in pairs:
            assert not bool(adjacency_contains(idx, jnp.asarray(u0),
                                               jnp.asarray(w)))
            break


def test_prefix_arrays_monotone(small_index):
    pexp = np.asarray(small_index.pexp)
    plin = np.asarray(small_index.plin)
    assert np.all(np.diff(pexp) >= 0)
    assert np.all(np.diff(plin) >= 0)
    assert pexp[0] == 0 and plin[0] == 0


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 1000), min_size=1, max_size=200),
       st.integers(-5, 1005))
def test_ranged_search_is_searchsorted(values, target):
    arr = np.sort(np.asarray(values, np.int32))
    pad = np.full(256 - len(arr), TS_PAD, np.int32)
    arr_p = jnp.asarray(np.concatenate([arr, pad]))
    lo = jnp.asarray([0], jnp.int32)
    hi = jnp.asarray([len(arr)], jnp.int32)
    t = jnp.asarray([target], jnp.int32)
    got_strict = int(ranged_search(arr_p, lo, hi, t, strict=True)[0])
    got_ge = int(ranged_search(arr_p, lo, hi, t, strict=False)[0])
    assert got_strict == int(np.searchsorted(arr, target, side="right"))
    assert got_ge == int(np.searchsorted(arr, target, side="left"))


@pytest.mark.parametrize("n", [1, 127, 129, 16_384, 16_513, (1 << 21) + 5])
def test_ranged_search_on_region_sorted_array(n):
    """Arbitrary sub-ranges of an array sorted only within regions, at
    lengths that give the row search one to four levels, against numpy."""
    rng = np.random.default_rng(n)
    cuts = np.sort(rng.choice(np.arange(1, n + 1), min(n, 40), replace=False))
    bounds = np.unique(np.concatenate([[0], cuts, [n]]))
    arr = np.concatenate([np.sort(rng.integers(0, 500, b - a))
                          for a, b in zip(bounds[:-1], bounds[1:])])
    reg = rng.integers(0, len(bounds) - 1, 2000)
    lo = bounds[reg] + (rng.random(2000) * (bounds[reg + 1] - bounds[reg])
                        * (rng.random(2000) < 0.3)).astype(np.int64)
    hi = np.maximum(lo, bounds[reg + 1]
                    - (rng.random(2000) * (bounds[reg + 1] - lo)
                       * (rng.random(2000) < 0.3)).astype(np.int64))
    t = rng.integers(-2, 503, 2000)
    for strict in (True, False):
        got = np.asarray(ranged_search(
            jnp.asarray(arr, jnp.int32), jnp.asarray(lo, jnp.int32),
            jnp.asarray(hi, jnp.int32), jnp.asarray(t, jnp.int32),
            strict=strict))
        side = "right" if strict else "left"
        want = [a + np.searchsorted(arr[a:b], x, side=side)
                for a, b, x in zip(lo, hi, t)]
        np.testing.assert_array_equal(got, want)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 2), st.integers(1, 100))
def test_build_index_arbitrary_ts(base_ts, n):
    """Index build is robust to arbitrary timestamp magnitudes."""
    rng = np.random.default_rng(n)
    src = rng.integers(0, 8, n).astype(np.int32)
    dst = rng.integers(0, 8, n).astype(np.int32)
    span = min(1000, 2**31 - 2 - base_ts)
    ts = (base_ts + rng.integers(0, span + 1, n)).astype(np.int32)
    store = store_from_arrays(src, dst, ts, edge_capacity=128,
                              node_capacity=8)
    idx = build_index(store, 8)
    assert int(idx.num_edges) == n
    assert np.all(np.isfinite(np.asarray(idx.pexp)))


# ---------------------------------------------------------------------------
# View sets: the core alone, or with the prefixes, the adjacency view, both
# ---------------------------------------------------------------------------

VIEW_SETS = {
    "core": IndexViews(prefixes=False, adjacency=False),
    "prefixes": IndexViews(prefixes=True, adjacency=False),
    "adjacency": IndexViews(prefixes=False, adjacency=True),
    "full": FULL_VIEWS,
}
_GROUPS = {"prefixes": ("pexp", "plin", "pexp_store", "plin_store"),
           "adjacency": ("adj_order", "adj_dst")}


def _same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


def assert_index_matches_full(index, full, views):
    """Every field ``index`` holds is ``full``'s bit for bit; it holds
    exactly the core and ``views``."""
    assert index.views == views
    for name in TemporalIndex._fields:
        got, want = getattr(index, name), getattr(full, name)
        group = next((g for g, fs in _GROUPS.items() if name in fs), None)
        if group is not None and not getattr(views, group):
            assert got is None, name
            continue
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert _same_bits(g, w), name
    E = index.edge_capacity
    edge_sized = [f for f in TemporalIndex._fields if f != "store"
                  and getattr(index, f) is not None
                  and getattr(index, f).shape[0] >= E]
    assert len(edge_sized) == views.edge_columns


@pytest.fixture(scope="module")
def small_store(small_graph):
    g = small_graph
    return store_from_arrays(g.src, g.dst, g.ts, edge_capacity=4096,
                             node_capacity=256)


@pytest.mark.parametrize("name", sorted(VIEW_SETS))
def test_view_set_build_matches_full_build(small_store, name):
    views = VIEW_SETS[name]
    full = build_index(small_store, 256)
    assert full.views == FULL_VIEWS and full.views.edge_columns == 10
    assert_index_matches_full(build_index(small_store, 256, views=views),
                              full, views)


@pytest.mark.parametrize("name", sorted(VIEW_SETS))
def test_fit_index_narrows_and_completes(small_store, name):
    """Fitting a full index drops views; fitting a core index builds them,
    bit for bit as the full build does; fitting to its own views is the
    identity."""
    views = VIEW_SETS[name]
    full = build_index(small_store, 256)
    core = build_index(small_store, 256, views=VIEW_SETS["core"])
    assert_index_matches_full(fit_index(full, views), full, views)
    assert_index_matches_full(fit_index(core, views), full, views)
    part = build_index(small_store, 256, views=views)
    assert fit_index(part, views) is part


@pytest.mark.parametrize("name", sorted(VIEW_SETS))
def test_empty_index_view_sets(name):
    views = VIEW_SETS[name]
    assert_index_matches_full(empty_index(512, 16, views),
                              empty_index(512, 16), views)


def test_adjacency_view_of_core_index(small_store):
    full = build_index(small_store, 256)
    core = build_index(small_store, 256, views=VIEW_SETS["core"])
    adj_order, adj_dst = adjacency_view(core)
    assert _same_bits(adj_order, full.adj_order)
    assert _same_bits(adj_dst, full.adj_dst)
