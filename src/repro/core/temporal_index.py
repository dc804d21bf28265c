"""Dual-index organization (paper §2.3) — bulk reconstruction, O(m).

Two logical views over the shared edge store, plus one auxiliary view:

* **Timestamp-grouped view** — the physical store itself (timestamp-sorted).
  The paper materializes a per-timestamp-group offset array; because ties are
  contiguous runs of a sorted array, group boundaries are implicit and every
  operation the paper performs on the offset array (bias -> group -> slice)
  is a binary search over the sorted ``ts`` array here. Same asymptotics
  (O(log E) vs O(log G)); zero extra memory. Recorded as an adaptation in
  DESIGN.md §9.

* **Node-and-timestamp-grouped view** — permutation ``ns_order`` sorting
  edges by (src, ts); ``node_starts[v]`` locates node v's edge region
  [a, b) in O(1); a ranged binary search inside [a, b) locates the temporal
  cutoff c so that Γ_t(v) = [c, b). ``ns_ts`` / ``ns_dst`` are gathered
  copies so hop lookups touch contiguous memory (the GPU version reads
  through the permutation; on TPU a materialized gather at build time buys
  sequential HBM access per node region — build is O(m), amortized over K
  walks, paper §2.7).

* **Adjacency view** (addition) — permutation sorting edges by
  (src, dst, ts). Used by (a) temporal node2vec's β(u,w) rejection test
  (the paper needs the same adjacency probe; mechanism unspecified there)
  and (b) the causality validator (paper §3.10).

Weight-based sampling support (paper §2.5 + Table 4 "weight" stage):
per-element weights are accumulated into **global prefix-sum arrays** whose
per-node-segment differences give neighborhood cumulative weights for *any*
hop suffix [c, b):

* exponential: w_i = exp(s · (ts_i − t_ref[src_i])), t_ref = node's max ts
  so exponents ≤ 0 (numerically safe). exp(t_i − t_min) of the paper equals
  this up to a positive factor that cancels in the normalized CDF.
* linear: elem_i = ts_i − t_base[src_i] + 1, t_base = node's min ts. The
  neighborhood weight w_i = ts_i − ts_c + 1 = elem_i − δ with
  δ = ts_c − t_base[v]; cumulative S[k] = (P[k+1] − P[c]) − (k+1−c)·δ is
  O(1) per probe, so inverse-CDF stays a binary search.

Which views are built when (``IndexViews``, DESIGN.md §3). The **core** —
the store, the node-ts view and the node-sized ``node_starts``,
``node_group_counts``, ``node_tref``, ``node_tbase`` — is always built:
every walk, the alias tables and the dispatch stats read only it. The
**weight prefixes** (``pexp``, ``plin``, ``pexp_store``, ``plin_store``:
two ``exp`` passes, two spreads, four cumsums) and the **adjacency view**
(``adj_order``, ``adj_dst``: the second sort) are optional, ``None`` when
unbuilt. A bare ``build_index(store, node_capacity)`` builds all of them;
an engine builds only what its walks can read
(``walk_engine.index_views``): the prefixes for ``mode="weight"`` or a
Pallas path, the adjacency view where a second-order draw can reach the
index. ``fit_index`` narrows an index to a view set for free and builds a
missing view from the core, bit for bit as ``build_index`` would; the
validator builds its own adjacency view (``adjacency_view``) when the
index has none.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.edge_store import EdgeStore
from repro.obs.tracing import scope


class IndexViews(NamedTuple):
    """The optional views an index holds beside its core (module docstring).

    Hashable, so it travels as a static argument: ``build_index``,
    ``init_window``, the ingests and the replay drivers compile one program
    per value. The default is every view.
    """

    prefixes: bool = True     # pexp, plin, pexp_store, plin_store
    adjacency: bool = True    # adj_order, adj_dst

    @property
    def edge_columns(self) -> int:
        """Edge-sized columns an index of these views holds, and so writes
        at every rebuild: the node-ts view's four, four prefixes, two
        adjacency columns."""
        return 4 + 4 * self.prefixes + 2 * self.adjacency

    def holds(self, other: "IndexViews") -> bool:
        """Whether an index of these views holds every view of ``other``."""
        return ((self.prefixes or not other.prefixes)
                and (self.adjacency or not other.adjacency))


FULL_VIEWS = IndexViews()
_PREFIX_FIELDS = ("pexp", "plin", "pexp_store", "plin_store")
_ADJACENCY_FIELDS = ("adj_order", "adj_dst")


class TemporalIndex(NamedTuple):
    # ---- core: always built ----
    # shared edge store (timestamp-grouped view == physical layout)
    store: EdgeStore
    # node-and-timestamp-grouped view
    ns_order: jax.Array      # int32[E] permutation: position -> store index
    ns_src: jax.Array        # int32[E] src gathered through ns_order
    ns_dst: jax.Array        # int32[E]
    ns_ts: jax.Array         # int32[E]
    node_starts: jax.Array   # int32[N+2] region of node v = [ns[v], ns[v+1])
    node_group_counts: jax.Array  # int32[N] distinct-timestamp count (the G axis)
    node_tref: jax.Array     # int32[N] max ts per node (exp reference)
    node_tbase: jax.Array    # int32[N] min ts per node (linear reference)
    # ---- optional: weight prefixes (IndexViews.prefixes), else None ----
    # weight-sampler prefix arrays over the node-ts view (exclusive; E+1)
    pexp: Optional[jax.Array] = None        # float32[E+1]
    plin: Optional[jax.Array] = None        # float32[E+1]
    # store-level prefixes for start-edge selection over the timestamp view
    pexp_store: Optional[jax.Array] = None  # float32[E+1]
    plin_store: Optional[jax.Array] = None  # float32[E+1]
    # ---- optional: adjacency view (IndexViews.adjacency), else None ----
    # node2vec β probe + validation
    adj_order: Optional[jax.Array] = None   # int32[E] sorted by (src, dst, ts)
    adj_dst: Optional[jax.Array] = None     # int32[E]

    @property
    def num_edges(self) -> jax.Array:
        return self.store.num_edges

    @property
    def node_capacity(self) -> int:
        return self.node_starts.shape[0] - 2

    @property
    def edge_capacity(self) -> int:
        return self.ns_order.shape[0]

    @property
    def views(self) -> IndexViews:
        """The optional views this index holds."""
        return IndexViews(prefixes=self.pexp is not None,
                          adjacency=self.adj_order is not None)


def _spread(node_vals: jax.Array, lo: jax.Array, nonempty: jax.Array,
            E: int) -> jax.Array:
    """int32[E]: each nonempty node's value over its region [lo, hi) of the
    node-ts view (other positions: unspecified). Regions are contiguous and
    in node order, so the value is a running sum of per-region steps placed
    at the region starts — one node-sized scatter and an edge-sized cumsum
    instead of an edge-sized gather (int32 wraparound keeps it exact)."""
    V = node_vals.shape[0]
    last = jax.lax.cummax(jnp.where(nonempty, jnp.arange(V, dtype=jnp.int32),
                                    -1))
    prev_node = jnp.concatenate([jnp.full((1,), -1, jnp.int32), last[:-1]])
    prev = jnp.where(prev_node >= 0, node_vals[jnp.maximum(prev_node, 0)], 0)
    steps = jnp.where(nonempty, node_vals - prev, 0)
    return jnp.cumsum(jnp.zeros((E,), jnp.int32).at[lo].add(steps))


def _prefix_views(index: TemporalIndex, bias_scale: float) -> dict:
    """The four weight prefixes (linear passes over the core)."""
    store, ns_src, ns_ts = index.store, index.ns_src, index.ns_ts
    E, nc = index.edge_capacity, index.node_capacity
    n_valid = store.num_edges
    valid = jnp.arange(E, dtype=jnp.int32) < n_valid
    lo = index.node_starts[:nc]
    nonempty = index.node_starts[1:nc + 1] > lo

    in_range = ns_src < nc
    dt_exp = (ns_ts - _spread(index.node_tref, lo, nonempty, E)).astype(
        jnp.float32)
    w_exp = jnp.where(in_range, jnp.exp(bias_scale * dt_exp), 0.0)
    elem_lin = (ns_ts - _spread(index.node_tbase, lo, nonempty, E) + 1
                ).astype(jnp.float32)
    w_lin = jnp.where(in_range, elem_lin, 0.0)
    zero = jnp.zeros((1,), jnp.float32)
    pexp = jnp.concatenate([zero, jnp.cumsum(w_exp)])
    plin = jnp.concatenate([zero, jnp.cumsum(w_lin)])

    # store-level prefixes (start-edge selection over the whole window)
    t_hi = jnp.where(n_valid > 0, store.ts[jnp.maximum(n_valid - 1, 0)], 0)
    t_lo = store.ts[0]
    w_exp_s = jnp.where(valid, jnp.exp(
        bias_scale * (store.ts - t_hi).astype(jnp.float32)), 0.0)
    w_lin_s = jnp.where(valid, (store.ts - t_lo + 1).astype(jnp.float32),
                        0.0)
    return dict(pexp=pexp, plin=plin,
                pexp_store=jnp.concatenate([zero, jnp.cumsum(w_exp_s)]),
                plin_store=jnp.concatenate([zero, jnp.cumsum(w_lin_s)]))


def _adjacency_view_impl(index: TemporalIndex):
    """Sort 2: (adj_order, adj_dst), the (src, dst, ts) adjacency view.

    A stable (src, dst) sort of the ns view: equal (src, dst) runs keep the
    ns view's (ts, store position) order, so this is the stable (src, dst,
    ts) order of the store. Two keys, not three: a three-key sort costs the
    TPU compiler ~1 min more per program that rebuilds the index."""
    _, adj_dst, adj_order = jax.lax.sort(
        (index.ns_src, index.ns_dst, index.ns_order), num_keys=2,
        is_stable=True)
    return adj_order, adj_dst


def _build_index_impl(store: EdgeStore, node_capacity: int,
                      bias_scale: float = 1.0,
                      views: IndexViews = FULL_VIEWS) -> TemporalIndex:
    """Bulk dual-index reconstruction (paper §2.6: a sort per view + linear
    passes) of the core and ``views``; its device work carries the
    ``index`` scope."""
    with scope("index"):
        E = store.capacity
        iota = jnp.arange(E, dtype=jnp.int32)

        # ---- sort 1: (src, ts) — the node-and-timestamp-grouped view ----
        # The store is ts-sorted, so a stable sort by src alone yields the
        # stable (src, ts) order. The view's columns ride the sort as payloads
        # instead of being gathered through the permutation: on a TPU a sort
        # moves them far faster than edge-capacity random gathers would.
        # Padding edges have src == node_capacity, ts == TS_PAD -> sort last.
        ns_src, ns_dst, ns_ts, ns_order = jax.lax.sort(
            (store.src, store.dst, store.ts, iota), num_keys=1, is_stable=True)

        # node regions: node_starts[v] = first position with ns_src >= v.
        # one extra bucket (node_capacity) holds the padding edges.
        nodes = jnp.arange(node_capacity + 2, dtype=jnp.int32)
        node_starts = ranged_search(ns_src, jnp.zeros_like(nodes),
                                    jnp.full_like(nodes, E), nodes,
                                    strict=False)
        lo = node_starts[:node_capacity]
        hi = node_starts[1:node_capacity + 1]

        # G axis: distinct timestamps per node region. A timestamp group starts
        # wherever either the src or the ts changes in the (src, ts)-sorted
        # order; a region's count is a difference of the running group count.
        prev_src = jnp.concatenate([jnp.full((1,), -1, jnp.int32),
                                    ns_src[:-1]])
        prev_ts = jnp.concatenate([jnp.full((1,), -1, jnp.int32), ns_ts[:-1]])
        group_start = (ns_src != prev_src) | (ns_ts != prev_ts)
        groups = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(
            (group_start & (ns_src < node_capacity)).astype(jnp.int32))])
        node_group_counts = groups[hi] - groups[lo]

        # per-node ts extrema (references for stable weights): a region is
        # ts-sorted, so they are its first and last timestamps; 0 when empty
        nonempty = hi > lo
        node_tbase = jnp.where(nonempty, ns_ts[jnp.clip(lo, 0, E - 1)], 0)
        node_tref = jnp.where(nonempty, ns_ts[jnp.clip(hi - 1, 0, E - 1)], 0)

        index = TemporalIndex(
            store=store,
            ns_order=ns_order, ns_src=ns_src, ns_dst=ns_dst, ns_ts=ns_ts,
            node_starts=node_starts, node_group_counts=node_group_counts,
            node_tref=node_tref, node_tbase=node_tbase)
        return _with_views(index, views, bias_scale)


def _with_views(index: TemporalIndex, views: IndexViews,
                bias_scale: float) -> TemporalIndex:
    """``index`` plus the views of ``views`` it lacks, built from its core."""
    if views.prefixes and index.pexp is None:
        index = index._replace(**_prefix_views(index, bias_scale))
    if views.adjacency and index.adj_order is None:
        adj_order, adj_dst = _adjacency_view_impl(index)
        index = index._replace(adj_order=adj_order, adj_dst=adj_dst)
    return index


build_index = partial(jax.jit, static_argnames=("node_capacity",
                                                "bias_scale", "views"))(
    _build_index_impl)

# the adjacency view of an index that has none (the validator's)
adjacency_view = jax.jit(_adjacency_view_impl)


@partial(jax.jit, static_argnames=("views", "bias_scale"))
def _complete(index: TemporalIndex, views: IndexViews,
              bias_scale: float) -> TemporalIndex:
    with scope("index"):
        return _with_views(index, views, bias_scale)


def fit_index(index: TemporalIndex, views: IndexViews,
              bias_scale: float = 1.0) -> TemporalIndex:
    """``index`` holding exactly the optional ``views``.

    Views it holds beyond them are dropped, at no cost: their arrays are
    simply not passed on. Views it lacks are built once from its core, bit
    for bit as ``build_index(..., views=)`` builds them. A jitted program
    whose arguments or carry hold an index compiles for one view set, so
    the engines fit a state handed to them (a bulk load, say) before any of
    their programs sees it.
    """
    have = index.views
    if have == views:
        return index
    drop = ((() if views.prefixes else _PREFIX_FIELDS)
            + (() if views.adjacency else _ADJACENCY_FIELDS))
    index = index._replace(**{f: None for f in drop})
    if not have.holds(views):
        index = _complete(index, views, bias_scale)
    return index


def empty_index(edge_capacity: int, node_capacity: int,
                views: IndexViews = FULL_VIEWS) -> TemporalIndex:
    """``build_index`` of an empty store, written out: every edge slot is
    padding, so each view is the identity order and every prefix is 0. No
    sort is compiled (at a 2^27-edge capacity they take a v5e's compiler
    about a minute)."""
    from repro.core.edge_store import empty_store

    E, nc = edge_capacity, node_capacity
    iota = jnp.arange(E, dtype=jnp.int32)
    store = empty_store(E, nc)
    zeros_v = jnp.zeros((nc,), jnp.int32)
    index = TemporalIndex(
        store=store, ns_order=iota, ns_src=store.src, ns_dst=store.dst,
        ns_ts=store.ts,
        node_starts=jnp.zeros((nc + 2,), jnp.int32).at[nc + 1].set(E),
        node_group_counts=zeros_v, node_tref=zeros_v, node_tbase=zeros_v)
    if views.prefixes:
        zeros_p = jnp.zeros((E + 1,), jnp.float32)
        index = index._replace(pexp=zeros_p, plin=zeros_p,
                               pexp_store=zeros_p, plin_store=zeros_p)
    if views.adjacency:
        index = index._replace(adj_order=iota, adj_dst=store.dst)
    return index

# ---------------------------------------------------------------------------
# Ranged searches (branch-free, fixed trip count)
# ---------------------------------------------------------------------------

_ROW = 128        # elements per row of the blocked search (one vreg row)


def ranged_search(arr: jax.Array, lo: jax.Array, hi: jax.Array,
                  target: jax.Array, *, strict: bool) -> jax.Array:
    """First index k in [lo, hi) with arr[k] > target (strict) or >= target.

    Vectorized over lo/hi/target (same shape); ``arr`` is 1-D and sorted
    within every queried range. Returns hi if no such k (lo if lo >= hi).

    A blocked search. Level j views every 128^j-th element of ``arr`` as
    rows of 128; the top level is one row. Each level counts, in one row,
    the elements of the current bracket that lie below the target, which
    narrows the bracket to the stretch between two consecutive elements
    of the next level down — a stretch that one row of that level holds.
    A query thus reads ceil(log_128 len(arr)) rows, where a binary search
    makes log2(len(arr)) dependent single-element reads — the access a
    TPU serves most slowly. The answer is exactly the binary search's.
    """
    R = _ROW
    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)
    t = target[..., None]

    def below(v):
        return (v <= t) if strict else (v < t)

    levels = []                           # (stride, entries, rows)
    level, stride = arr, 1
    while True:
        n = level.shape[0]
        pad = jnp.zeros((-n % R,), arr.dtype)
        levels.append((stride, n, jnp.concatenate([level, pad]).reshape(
            -1, R)))
        if n <= R:
            break
        level, stride = level[::R], stride * R

    lane = jnp.arange(R, dtype=jnp.int32)
    L, U = lo, hi                         # answer in [L, U]
    for stride, n, rows in reversed(levels):
        # [L, U) lies in one row of this level (all of it at the top)
        r = jnp.clip(L // (stride * R), 0, rows.shape[0] - 1)[..., None]
        entry = r * R + lane
        pos = entry * stride              # wraps only where entry >= n
        inside = (entry < n) & (pos >= L[..., None]) & (pos < U[..., None])
        m = jnp.sum((inside & below(rows[r[..., 0]])).astype(jnp.int32),
                    axis=-1)
        if stride == 1:
            return L + m
        first = (L + stride - 1) // stride * stride
        none = first >= U
        L, U = (jnp.where(none | (m == 0), L, first + (m - 1) * stride),
                jnp.where(none, U, jnp.minimum(first + m * stride, U)))


def node_range(index: TemporalIndex, node: jax.Array):
    """[a, b) edge region of ``node`` in the node-ts view — O(1)."""
    v = jnp.clip(node, 0, index.node_capacity)
    return index.node_starts[v], index.node_starts[v + 1]


def temporal_cutoff(index: TemporalIndex, a: jax.Array, b: jax.Array,
                    t: jax.Array) -> jax.Array:
    """c = first position in [a, b) with ns_ts > t, so Γ_t(v) = [c, b)."""
    return ranged_search(index.ns_ts, a, b, t, strict=True)


def adjacency_contains(index: TemporalIndex, u: jax.Array,
                       w: jax.Array) -> jax.Array:
    """Whether edge (u -> w, any ts) exists in the window — O(log E)."""
    a, b = node_range_adj(index, u)
    k = ranged_search(index.adj_dst, a, b, w, strict=False)
    return (k < b) & (index.adj_dst[jnp.clip(k, 0, index.edge_capacity - 1)] == w)


def node_range_adj(index: TemporalIndex, node: jax.Array):
    # adjacency view shares node regions with the ns view (both sort by src
    # first and the sorts are over the same multiset)
    return node_range(index, node)
