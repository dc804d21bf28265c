"""Temporal bias sampling (paper §2.5).

Two sampler modes over a neighborhood Γ_t(v) = positions [c, b) of the
node-ts view (or [0, n) of the timestamp view for start-edge selection):

* ``index`` — closed-form constant-time inverse CDFs over the ordinal
  position i ∈ [0, n), exact when timestamp gaps are uniform (paper eqs 1-3):

    uniform      i = ⌊u·n⌋
    linear       weights w_i ∝ (i+1);   CDF(k) = (k+1)(k+2)/2 / (n(n+1)/2)
                 i = ⌊(−1 + sqrt(1 + 4·u·n·(n+1)))/2⌋
    exponential  weights w_i ∝ e^i;     the offset k = n−1−i from the newest
                 candidate is truncated geometric, P(k) ∝ e^{−k}, k ∈ [0, n):
                 k = ⌈−log(1 − u·(1 − e^{−n}))⌉ − 1,   i = n − 1 − k
                 exact at every n; n enters only through e^{−n} and the
                 integer subtraction, so no float ever holds n + log u.

* ``weight`` — exact inverse-transform over cumulative true-timestamp
  weights, served from the prefix arrays built at index time
  (paper Table 4 "weight" stage), O(log n) binary search per hop.

Temporal node2vec (paper §2.5): second-order bias β(u,w) applied by
rejection on the first-order proposal with acceptance β(u,w)/β_max,
β_max = max(1/p, 1, 1/q) — keeping the inner CDF prev-independent so the
second-order picker runs through the same dispatch path.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import SamplerConfig
from repro.core.temporal_index import (
    TemporalIndex,
    adjacency_contains,
    ranged_search,
)


# ---------------------------------------------------------------------------
# Closed-form index samplers (O(1) per hop)
# ---------------------------------------------------------------------------


def index_uniform(u: jax.Array, n: jax.Array) -> jax.Array:
    nf = n.astype(jnp.float32)
    i = jnp.floor(u * nf).astype(jnp.int32)
    return jnp.clip(i, 0, jnp.maximum(n - 1, 0))


def index_linear(u: jax.Array, n: jax.Array) -> jax.Array:
    """Inverse CDF for w_i ∝ (i+1): smallest k with (k+1)(k+2) ≥ u·n(n+1)·…

    Paper eq. (2). Solve the quadratic in float32; a one-step correction
    fixes boundary rounding so the result is an exact inverse CDF.
    """
    nf = n.astype(jnp.float32)
    i = jnp.floor((-1.0 + jnp.sqrt(1.0 + 4.0 * u * nf * (nf + 1.0))) / 2.0)
    i = i.astype(jnp.int32)
    # correction: the exact condition is (i)(i+1)/2 < u·n(n+1)/2 ≤ (i+1)(i+2)/2
    target = u * nf * (nf + 1.0)
    if_ = i.astype(jnp.float32)
    too_high = if_ * (if_ + 1.0) >= target
    i = jnp.where(too_high, i - 1, i)
    if2 = i.astype(jnp.float32)
    too_low = (if2 + 1.0) * (if2 + 2.0) < target
    i = jnp.where(too_low, i + 1, i)
    return jnp.clip(i, 0, jnp.maximum(n - 1, 0))


def index_exponential(u: jax.Array, n: jax.Array) -> jax.Array:
    """Inverse CDF for w_i ∝ e^i (most-recent position gets highest weight).

    Paper eq. (3), drawn as the offset k of the pick from the newest
    candidate: P(offset ≤ k) = (1 − e^{−(k+1)})/(1 − e^{−n}), so the
    smallest k with that ≥ u is k = ⌈−log(1 − u·(1 − e^{−n}))⌉ − 1. Every
    float stays in [0, 1] or is a small offset (u < 1 keeps it below
    −log(2^−24) ≈ 17), so the draw is exact at every n up to 2^31 − 1;
    the pick i = n − 1 − k is taken in int32.

    One ``exp`` and one ``log``, and no ``expm1``, which the Pallas TPU
    lowering lacks: e^{−n} of an integer n ≥ 1 is far from 1. The fused
    kernel evaluates this same expression, which keeps its walks
    byte-identical to the jnp paths.
    """
    nf = n.astype(jnp.float32)
    k = jnp.ceil(-jnp.log(1.0 - u * (1.0 - jnp.exp(-nf)))) - 1.0
    # the float bound keeps the int32 cast defined where u = 1 makes k
    # infinite; the integer bound is exact (and gives 0 for n = 0)
    k = jnp.clip(k, 0.0, 2.0 ** 30).astype(jnp.int32)
    return n - 1 - jnp.minimum(k, n - 1)


_INDEX_SAMPLERS = {
    "uniform": index_uniform,
    "linear": index_linear,
    "exponential": index_exponential,
}


def index_pick(bias: str, u: jax.Array, n: jax.Array) -> jax.Array:
    return _INDEX_SAMPLERS[bias](u, n)


# ---------------------------------------------------------------------------
# Per-lane bias dispatch (serving subsystem, DESIGN.md §11)
#
# The three closed-form inverse CDFs are elementwise in (u, n), so a
# heterogeneous batch dispatches them branchlessly: every lane evaluates
# all three O(1) formulas and a two-level select keeps the one named by its
# int8/int32 bias code. This is the vectorized analog of `lax.switch` —
# identical results, no cross-lane divergence, and each lane's pick is a
# pure function of (bias_code, u, n), which is what makes a coalesced
# mega-batch bit-identical to running each query solo.
# ---------------------------------------------------------------------------

BIAS_UNIFORM = 0
BIAS_LINEAR = 1
BIAS_EXPONENTIAL = 2
BIAS_TABLE = 3        # alias/radix tables (core/alias.py, DESIGN.md §17);
                      # dispatched by walk_engine, not index_pick_lanes

BIAS_CODES = {
    "uniform": BIAS_UNIFORM,
    "linear": BIAS_LINEAR,
    "exponential": BIAS_EXPONENTIAL,
    "table": BIAS_TABLE,
}


def bias_code(bias: str) -> int:
    """Map a bias name to its per-lane dispatch code."""
    try:
        return BIAS_CODES[bias]
    except KeyError:
        raise ValueError(f"unknown bias {bias!r} "
                         f"(expected one of {sorted(BIAS_CODES)})") from None


def index_pick_lanes(code: jax.Array, u: jax.Array, n: jax.Array) -> jax.Array:
    """Per-lane index sampling: ``code[i]`` selects the inverse CDF of lane i."""
    i_uni = index_uniform(u, n)
    i_lin = index_linear(u, n)
    i_exp = index_exponential(u, n)
    return jnp.where(code == BIAS_UNIFORM, i_uni,
                     jnp.where(code == BIAS_LINEAR, i_lin, i_exp))


def pick_in_neighborhood_lanes(index: TemporalIndex, code: jax.Array,
                               c: jax.Array, b: jax.Array,
                               u: jax.Array) -> jax.Array:
    """Per-lane-bias pick of k ∈ [c, b); index-mode closed forms only.

    Valid only when b > c (caller masks empty neighborhoods).
    """
    return c + index_pick_lanes(code, u, b - c)


def pick_start_edges_lanes(index: TemporalIndex, code: jax.Array,
                           u: jax.Array) -> jax.Array:
    """Per-lane-bias start-edge sampling over the timestamp view."""
    n = jnp.broadcast_to(index.num_edges, u.shape).astype(jnp.int32)
    return index_pick_lanes(code, u, n)


# ---------------------------------------------------------------------------
# Weight-based samplers (exact, O(log n) over prefix arrays)
# ---------------------------------------------------------------------------


def weighted_pick_exp(pexp: jax.Array, c: jax.Array, b: jax.Array,
                      u: jax.Array) -> jax.Array:
    """Smallest k in [c, b) with pexp[k+1] − pexp[c] ≥ u·(pexp[b] − pexp[c]).

    Falls back to uniform position when the neighborhood's weight mass
    underflows to zero (all edges far older than the node's newest edge).
    """
    total = pexp[b] - pexp[c]
    r = u * total
    target = pexp[c] + r
    # search over the shifted array pexp[k+1]
    k = _shifted_lower_bound(pexp, c, b, target)
    n = b - c
    fallback = c + index_uniform(u, n)
    k = jnp.where(total > 0, k, fallback)
    return jnp.clip(k, c, jnp.maximum(b - 1, c))


def weighted_pick_linear(plin: jax.Array, ns_ts: jax.Array,
                         node_tbase_at: jax.Array, c: jax.Array,
                         b: jax.Array, u: jax.Array) -> jax.Array:
    """Inverse CDF over w_k = ts_k − ts_c + 1 via the dual-prefix trick.

    S(k) = (plin[k+1] − plin[c]) − (k+1−c)·δ,  δ = ts_c − t_base(v).
    S is strictly increasing (w_k ≥ 1), so binary search applies with each
    probe computed in O(1) from the prefix array.
    """
    E = ns_ts.shape[0]
    ts_c = ns_ts[jnp.clip(c, 0, E - 1)]
    delta = (ts_c - node_tbase_at).astype(jnp.float32)
    total = (plin[b] - plin[c]) - (b - c).astype(jnp.float32) * delta
    r = u * total

    steps = max(1, math.ceil(math.log2(max(E + 1, 2))) + 1)

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) >> 1
        s_mid = (plin[jnp.clip(mid + 1, 0, E)] - plin[c]) \
            - (mid + 1 - c).astype(jnp.float32) * delta
        pred = s_mid >= r
        open_ = lo < hi
        hi2 = jnp.where(pred, mid, hi)
        lo2 = jnp.where(pred, lo, mid + 1)
        return (jnp.where(open_, lo2, lo), jnp.where(open_, hi2, hi))

    k, _ = jax.lax.fori_loop(0, steps, body, (c, b))
    n = b - c
    fallback = c + index_uniform(u, n)
    k = jnp.where(total > 0, k, fallback)
    return jnp.clip(k, c, jnp.maximum(b - 1, c))


def _shifted_lower_bound(prefix: jax.Array, lo: jax.Array, hi: jax.Array,
                         target: jax.Array) -> jax.Array:
    """Smallest k in [lo, hi) with prefix[k+1] >= target."""
    E = prefix.shape[0] - 1
    steps = max(1, math.ceil(math.log2(max(E + 1, 2))) + 1)

    def body(_, state):
        lo_, hi_ = state
        mid = (lo_ + hi_) >> 1
        v = prefix[jnp.clip(mid + 1, 0, E)]
        pred = v >= target
        open_ = lo_ < hi_
        hi2 = jnp.where(pred, mid, hi_)
        lo2 = jnp.where(pred, lo_, mid + 1)
        return (jnp.where(open_, lo2, lo_), jnp.where(open_, hi2, hi_))

    k, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return k


# ---------------------------------------------------------------------------
# Hop-level API
# ---------------------------------------------------------------------------


def pick_in_neighborhood(index: TemporalIndex, cfg: SamplerConfig,
                         c: jax.Array, b: jax.Array, u: jax.Array,
                         node: jax.Array) -> jax.Array:
    """Pick a position k ∈ [c, b) under the configured bias; returns k.

    Valid only when b > c (caller masks empty neighborhoods).
    """
    n = b - c
    if cfg.mode == "index":
        return c + index_pick(cfg.bias, u, n)
    if cfg.mode == "weight":
        if cfg.bias == "uniform":
            return c + index_uniform(u, n)
        if cfg.bias == "exponential":
            return weighted_pick_exp(index.pexp, c, b, u)
        if cfg.bias == "linear":
            nc = index.node_capacity
            tbase = index.node_tbase[jnp.clip(node, 0, nc - 1)]
            return weighted_pick_linear(index.plin, index.ns_ts, tbase, c, b, u)
        raise ValueError(f"unknown bias {cfg.bias!r}")
    raise ValueError(f"unknown sampler mode {cfg.mode!r}")


def pick_start_edges(index: TemporalIndex, cfg: SamplerConfig,
                     u: jax.Array) -> jax.Array:
    """Sample start edges from the timestamp-grouped view (store order)."""
    zero = jnp.zeros_like(u, dtype=jnp.int32)
    b = jnp.broadcast_to(index.num_edges, u.shape).astype(jnp.int32)
    n = b
    if cfg.start_bias == "uniform":
        return index_uniform(u, n)
    if cfg.mode == "index":
        return index_pick(cfg.start_bias, u, n)
    if cfg.start_bias == "exponential":
        return weighted_pick_exp(index.pexp_store, zero, b, u)
    if cfg.start_bias == "linear":
        # store-level linear uses t_base = global min ts => delta = 0
        total = index.plin_store[b]
        r = u * total
        k = _shifted_lower_bound(index.plin_store, zero, b, r)
        return jnp.where(total > 0, k, index_uniform(u, n))
    return index_uniform(u, n)


# ---------------------------------------------------------------------------
# Temporal node2vec (second-order bias via rejection, paper §2.5)
# ---------------------------------------------------------------------------


def node2vec_beta(index: TemporalIndex, prev: jax.Array, cand: jax.Array,
                  p: float, q: float) -> jax.Array:
    """β(u,w): 1/p if w == prev (return), 1 if w adjacent to prev, 1/q else."""
    is_return = cand == prev
    is_common = adjacency_contains(index, prev, cand)
    return jnp.where(is_return, 1.0 / p,
                     jnp.where(is_common, 1.0, 1.0 / q)).astype(jnp.float32)


def node2vec_max_beta(p: float, q: float) -> float:
    return max(1.0 / p, 1.0, 1.0 / q)


def node2vec_beta_lanes(index: TemporalIndex, prev: jax.Array,
                        cand: jax.Array, p: jax.Array,
                        q: jax.Array) -> jax.Array:
    """Per-lane β(u,w): like ``node2vec_beta`` but with array (p, q)."""
    is_return = cand == prev
    is_common = adjacency_contains(index, prev, cand)
    return jnp.where(is_return, 1.0 / p,
                     jnp.where(is_common, 1.0, 1.0 / q)).astype(jnp.float32)


def node2vec_max_beta_lanes(p: jax.Array, q: jax.Array) -> jax.Array:
    return jnp.maximum(jnp.maximum(1.0 / p, 1.0), 1.0 / q).astype(
        jnp.float32)
