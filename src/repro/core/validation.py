"""Causality validation (paper §3.10).

Checks, for every emitted walk:
* **hop validity** — each hop (u -> v at time t) corresponds to a real edge
  (u, v, t) of the active window, and timestamps are strictly increasing;
* **walk validity** — all hops of the walk are valid.

The paper uses this metric to show static engines produce 0% valid walks
while Tempest produces 100%. A numpy reference implementation is provided
alongside the jnp one so the validator itself is cross-checked in tests.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.temporal_index import (
    TemporalIndex,
    adjacency_view,
    ranged_search,
)
from repro.core.walk_engine import NODE_PAD, WalkResult


class ValidityReport(NamedTuple):
    hop_valid_frac: jax.Array
    walk_valid_frac: jax.Array
    num_hops: jax.Array
    num_walks: jax.Array


def _edge_exists(index: TemporalIndex, u, v, t):
    """Membership probe for the exact triple (u, v, t) via the adjacency view.

    The adjacency view is sorted by (src, dst, ts); within node u's region
    we binary-search for dst >= v, then scan the (v, *) run boundaries by a
    second search on ts.
    """
    E = index.edge_capacity
    a = index.node_starts[jnp.clip(u, 0, index.node_capacity)]
    b = index.node_starts[jnp.clip(u, 0, index.node_capacity) + 1]
    lo = ranged_search(index.adj_dst, a, b, v, strict=False)
    hi = ranged_search(index.adj_dst, a, b, v, strict=True)
    adj_ts = index.store.ts[index.adj_order]
    k = ranged_search(adj_ts, lo, hi, t, strict=False)
    k = jnp.clip(k, 0, E - 1)
    return (k < hi) & (adj_ts[k] == t) \
        & (index.adj_dst[jnp.clip(k, 0, E - 1)] == v)


@jax.jit
def validate_walks(index: TemporalIndex, result: WalkResult) -> ValidityReport:
    """Hop and walk validity of ``result`` against ``index``'s window. An
    index without the adjacency view (built for walks that never read it)
    gets its own here."""
    if index.adj_order is None:
        adj_order, adj_dst = adjacency_view(index)
        index = index._replace(adj_order=adj_order, adj_dst=adj_dst)
    nodes, times, lengths = result.nodes, result.times, result.lengths
    W, Lp1 = nodes.shape
    pos = jnp.arange(Lp1 - 1)
    u = nodes[:, :-1]
    v = nodes[:, 1:]
    t_prev = times[:, :-1]
    t = times[:, 1:]
    is_hop = (pos[None, :] + 1) < lengths[:, None]

    # in walk-row batches of ~2^20 hops: each search reads a 128-wide row
    # per query, so one batch of every hop could need gigabytes
    exists = jax.lax.map(lambda row: _edge_exists(index, *row), (u, v, t),
                         batch_size=max(1, (1 << 20) // max(Lp1 - 1, 1)))
    # strictly increasing except the first hop in edges-start mode, where
    # position 0 records the start edge's own timestamp on both endpoints.
    increasing = (t > t_prev) | (pos[None, :] == 0) & (t == t_prev)
    hop_ok = jnp.where(is_hop, exists & increasing, True)

    n_hops = jnp.sum(is_hop)
    hop_valid = jnp.sum(hop_ok & is_hop)
    has_hops = lengths > 1
    walk_ok = jnp.all(hop_ok, axis=1) & has_hops
    n_walks = jnp.sum(has_hops)
    return ValidityReport(
        hop_valid_frac=hop_valid / jnp.maximum(n_hops, 1),
        walk_valid_frac=jnp.sum(walk_ok) / jnp.maximum(n_walks, 1),
        num_hops=n_hops, num_walks=n_walks,
    )


def validate_walks_np(edges: Tuple[np.ndarray, np.ndarray, np.ndarray],
                      nodes: np.ndarray, times: np.ndarray,
                      lengths: np.ndarray) -> Tuple[float, float]:
    """Reference validator over raw (src, dst, ts) arrays (host)."""
    src, dst, ts = edges
    edge_set = set(zip(src.tolist(), dst.tolist(), ts.tolist()))
    hop_total = hop_ok = 0
    walk_total = walk_ok = 0
    for w in range(nodes.shape[0]):
        L = int(lengths[w])
        if L <= 1:
            continue
        walk_total += 1
        ok = True
        for i in range(L - 1):
            hop_total += 1
            u, v, t = int(nodes[w, i]), int(nodes[w, i + 1]), int(times[w, i + 1])
            t_prev = int(times[w, i])
            valid = (u, v, t) in edge_set and (t > t_prev or (i == 0 and t == t_prev))
            hop_ok += valid
            ok &= valid
        walk_ok += ok
    return (hop_ok / max(hop_total, 1), walk_ok / max(walk_total, 1))
