"""Plain reference of the stream deployments.

It imports nothing of the program and reads only what the benchmark
generated (the edge stream) and what the program answered
(its window, its counters, its walks). What it holds the program to:

* the sliding window: every edge with ts >= t_now - Δ, ordered by
  timestamp with ties in arrival order, the oldest clipped beyond the
  capacity;
* every hop of a walk: u -> v at time t is an edge (u, v, t) of the window
  the walk ran against, strictly later than the hop before; a walk stops
  only when it has no candidate left or has reached its length;
* the sampler: a walk's draws follow the law its bias states, judged by
  the Kolmogorov–Smirnov distance of their randomised probability
  integral transforms from the uniform law (``ks_z``).

The laws are those of ``index`` mode: over the ordinal positions
i = 0..n-1 of the candidates, oldest first, P(i) ∝ 1 (uniform), i+1
(linear) or e^i (exponential). Start nodes are uniform over the nodes
with an edge in the window; start edges follow the start bias over the
window's positions. Draws among edges that share a node and a timestamp
are judged together: the reference cannot tell them apart.

The window is built by one stable sort of every edge in arrival order and
searched by plain binary searches, in ``jax.numpy`` on the device once
the program's state is freed (a host sort of 10^8 edges takes minutes);
the laws and the statistic are numpy on the host.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BIASES = ("uniform", "linear", "exponential")
_MAX = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("capacity",))
def _window(src, dst, ts, t_now, delta, capacity: int):
    keep = ts >= t_now - delta
    kts, ksrc, kdst = jax.lax.sort((jnp.where(keep, ts, _MAX), src, dst),
                                   num_keys=1, is_stable=True)
    total = jnp.sum(keep.astype(jnp.int32))
    first = jnp.maximum(total - capacity, 0)

    def cut(x):
        x = jnp.concatenate([x, jnp.full((capacity,), _MAX, x.dtype)])
        return jax.lax.dynamic_slice(x, (first,), (capacity,))

    return cut(ksrc), cut(kdst), cut(kts), total - first


class Window:
    """The window over edges given in arrival order (device arrays):
    ``src``, ``dst``, ``ts`` of length ``capacity``, the first ``n`` real,
    in (ts, arrival) order."""

    def __init__(self, src, dst, ts, t_now: int, delta: int, capacity: int):
        # pad to a power of two, or a multiple of 2^24 edges beyond it,
        # so that few shapes compile
        size = src.shape[0]
        extra = -size % min(1 << 24, 1 << max(size - 1, 1).bit_length())
        src, dst = (jnp.concatenate([jnp.asarray(a),
                                     jnp.zeros((extra,), jnp.int32)])
                    for a in (src, dst))
        ts = jnp.concatenate([jnp.asarray(ts), jnp.full(
            (extra,), np.iinfo(np.int32).min, jnp.int32)])
        self.src, self.dst, self.ts, n = _window(
            src, dst, ts, jnp.int32(t_now), jnp.int32(delta), capacity)
        self.n = int(n)
        self.t_now = t_now


@jax.jit
def _mismatch(a, b, n):
    live = jnp.arange(a.shape[0]) < n
    return jnp.sum((live & (a != b)).astype(jnp.int32))


def window_mismatch(store, window: Window) -> int:
    """Positions at which the program's store (src, dst, ts, n) differs
    from the window, plus the difference in length."""
    n_got = int(store[3])
    bad = abs(n_got - window.n)
    n = jnp.int32(min(n_got, window.n))
    for got, want in zip(store[:3], (window.src, window.dst, window.ts)):
        bad += int(_mismatch(got, want, n))
    return bad


# ---------------------------------------------------------------------------
# searches
# ---------------------------------------------------------------------------


def _search(arr, lo, hi, x, right: bool):
    """First k in [lo, hi) with arr[k] > x (right) or >= x (left); arr
    sorted on [lo, hi). A plain binary search."""
    steps = max(1, math.ceil(math.log2(arr.shape[0] + 1)) + 1)

    def body(_, c):
        lo, hi = c
        mid = (lo + hi) // 2
        v = arr[jnp.clip(mid, 0, arr.shape[0] - 1)]
        go = (v <= x) if right else (v < x)
        on = lo < hi
        return (jnp.where(on & go, mid + 1, lo),
                jnp.where(on & ~go, mid, hi))

    return jax.lax.fori_loop(0, steps, body, (lo, hi))[0]


@jax.jit
def _by_node(src, dst, ts, n):
    """The window in (src, ts, arrival) order: a stable sort by src of the
    (ts, arrival)-ordered window. Padding sorts last."""
    live = jnp.arange(src.shape[0]) < n
    s, t, d = jax.lax.sort((jnp.where(live, src, _MAX), ts, dst),
                           num_keys=1, is_stable=True)
    new = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]]) & live
    return s, t, d, jnp.cumsum(new.astype(jnp.int32))


@jax.jit
def _hops(s, t, distinct, u, t_prev, tv):
    """For node u after time t_prev: [first, end) of u's edges, c its first
    candidate, [lo, hi) its edges at time tv, and u's rank among the nodes
    with an edge."""
    L = s.shape[0]
    zero = jnp.zeros_like(u)
    first = _search(s, zero, zero + L, u, False)
    end = _search(s, first, zero + L, u, True)
    c = _search(t, first, end, t_prev, True)
    lo = _search(t, c, end, tv, False)
    hi = _search(t, lo, end, tv, True)
    rank = distinct[jnp.clip(first, 0, L - 1)] - 1
    return first, end, c, lo, hi, rank


@jax.jit
def _starts(ts, n, t):
    zero = jnp.zeros_like(t)
    lo = _search(ts, zero, zero + n, t, False)
    return lo, _search(ts, lo, zero + n, t, True)


@partial(jax.jit, static_argnames=("width",))
def _rows(arrays, lo, width: int):
    """arr[lo + j] for j < width, for each array: [m, width] each."""
    at = jnp.clip(lo[:, None] + jnp.arange(width)[None, :], 0,
                  arrays[0].shape[0] - 1)
    return tuple(a[at] for a in arrays)


def _bucket(m: int, least: int = 1024) -> int:
    return 1 << max(least.bit_length() - 1, (m - 1).bit_length())


def _bucketed(fn, *arrays):
    """Call a jitted search on arrays padded to a power of two (few
    shapes, so few compiles); results cut back and brought to the host."""
    m = arrays[0].size
    size = _bucket(m)
    padded = [jnp.asarray(np.concatenate(
        [np.asarray(a).astype(np.int32), np.zeros(size - m, np.int32)]))
        for a in arrays]
    return [np.asarray(r)[:m] for r in fn(*padded)]


def _matching(arrays, lo, hi, wanted):
    """Within each group [lo, hi), the offsets j whose entries equal the
    wanted values: bool [m, G], G a power of two >= the largest group."""
    m = lo.size
    if m == 0:
        return np.zeros((0, 1), bool)
    width = _bucket(int(np.max(hi - lo, initial=1)), least=1)
    size = _bucket(m)
    lo_d = jnp.asarray(np.concatenate([lo, np.zeros(size - m, np.int64)])
                       .astype(np.int32))
    got = [np.asarray(r)[:m] for r in _rows(tuple(arrays), lo_d, width)]
    inside = np.arange(width)[None, :] < (hi - lo)[:, None]
    for g, w in zip(got, wanted):
        inside &= g == np.asarray(w)[:, None]
    return inside


class WindowIndex:
    """Lookups over one window: the candidates of a node after a time,
    where a chosen edge lies among them, whether an edge exists."""

    def __init__(self, window: Window):
        self.window = window
        self.n = window.n
        self.s, self.t, self.d, self.distinct = _by_node(
            window.src, window.dst, window.ts, jnp.int32(window.n))
        self.num_active = int(self.distinct[max(window.n - 1, 0)]) \
            if window.n else 0

    def nodes(self, u, t_prev, t):
        """numpy (first, end, c, lo, hi, rank) per query."""
        return _bucketed(partial(_hops, self.s, self.t, self.distinct),
                         u, t_prev, t)

    def hop_matches(self, lo, hi, v):
        """Which of u's edges at time t, in arrival order, go to v."""
        return _matching((self.d,), lo, hi, (v,))

    def edge_matches(self, src, dst, t):
        """(lo, matches): window positions from lo of the edges at time t,
        and which of them are (src, dst)."""
        lo, hi = _bucketed(partial(_starts, self.window.ts,
                                   jnp.int32(self.n)), t)
        return lo, _matching((self.window.src, self.window.dst), lo, hi,
                             (src, dst))


# ---------------------------------------------------------------------------
# the laws
# ---------------------------------------------------------------------------


def log_cdf(bias: np.ndarray, m, n):
    """log P(i < m) under each draw's law over n positions (m in [0, n])."""
    m = m.astype(np.float64)
    n = np.maximum(n.astype(np.float64), 1.0)
    out = np.full(m.shape, -np.inf)
    pos = m > 0
    with np.errstate(divide="ignore", invalid="ignore"):
        uni = np.log(m) - np.log(n)
        lin = np.log(m) + np.log(m + 1) - np.log(n) - np.log(n + 1)
        # (e^m - 1)/(e^n - 1) in the log domain
        ex = (m - n) + np.log(-np.expm1(-m)) - np.log(-np.expm1(-n))
    law = np.select([bias == 0, bias == 1], [uni, lin], ex)
    out[pos] = law[pos]
    return np.minimum(out, 0.0)


def pit(bias, base, matches, n, rng) -> np.ndarray:
    """Randomised probability integral transform of draws over n positions
    each: the chosen edge is one of the positions base + j with
    ``matches[:, j]``. One of them is drawn in proportion to its mass under
    the law, then a point uniform within it: uniform on (0, 1) when the law
    holds."""
    m = matches.shape[0]
    row, j = np.nonzero(matches)           # row-major: rows in order
    pos = base[row] + j
    law, size = np.asarray(bias)[row], np.asarray(n)[row]
    f_lo = np.exp(log_cdf(law, pos, size))
    mass = np.exp(log_cdf(law, pos + 1, size)) - f_lo
    # per row: a cumulative mass, and a point drawn under it
    start = np.searchsorted(row, np.arange(m))
    cum = np.cumsum(mass)
    before = np.where(start > 0, cum[np.maximum(start - 1, 0)], 0.0)
    total = np.add.reduceat(mass, start) if row.size else np.zeros(m)
    target = before + rng.random(m) * total
    pick = np.minimum(np.searchsorted(cum, target, side="right"),
                      np.append(start[1:], row.size) - 1)
    # a draw the law gives no mass to in float64 reads as its lower end
    pick = np.where(total > 0, np.maximum(pick, start), start)
    return f_lo[pick] + rng.random(m) * mass[pick]


def ks_z(values) -> float:
    """sqrt(N)·D, D the Kolmogorov–Smirnov distance of ``values`` from the
    uniform law on (0, 1). With the law holding it reads below 1.95 in
    999 runs of 1000, whatever N."""
    v = np.sort(np.asarray(values, np.float64))
    n = v.size
    if n == 0:
        return 0.0
    i = np.arange(1, n + 1)
    d = max(float(np.max(i / n - v)), float(np.max(v - (i - 1) / n)))
    return math.sqrt(n) * d


# ---------------------------------------------------------------------------
# the walks
# ---------------------------------------------------------------------------


class WalkReport:
    """What the reference found in the walks it was shown."""

    def __init__(self):
        self.walks = 0
        self.hops = 0
        self.invalid_hops = 0      # not an edge of the window, or not later
        self.early_stops = 0       # stopped with candidates left
        self.bad_starts = 0        # start not as asked, or not in the window
        self.too_long = 0          # more edges than the walk's length
        self.pits: dict = {}       # draw kind -> PIT values

    def add(self, kind: str, values) -> None:
        self.pits.setdefault(kind, []).append(values)

    def wrong(self) -> int:
        return (self.invalid_hops + self.early_stops + self.bad_starts
                + self.too_long)

    def ks_z(self) -> float:
        every = [v for parts in self.pits.values() for v in parts]
        return ks_z(np.concatenate(every) if every else [])

    def ks_by_kind(self) -> dict:
        return {k: ks_z(np.concatenate(v)) for k, v in self.pits.items()}


def check_walks(idx: WindowIndex, report: WalkReport, nodes, times,
                lengths, *, start_mode: str, bias, max_len, rng,
                start_bias=None) -> None:
    """Judge W walks against the window of ``idx``.

    ``nodes``/``times``: [W, L+1]; ``lengths``: nodes recorded per walk.
    ``bias``/``start_bias``/``max_len``: per walk (bias codes index
    ``BIASES``). Start nodes are uniform over the active nodes."""
    nodes = np.asarray(nodes, np.int64)
    times = np.asarray(times, np.int64)
    lengths = np.asarray(lengths, np.int64)
    bias = np.broadcast_to(np.asarray(bias), lengths.shape)
    max_len = np.broadcast_to(np.asarray(max_len), lengths.shape)
    W, width = nodes.shape
    report.walks += W
    report.too_long += int(np.sum(lengths > max_len + 1))
    lengths = np.minimum(lengths, np.minimum(max_len + 1, width))
    rows = np.arange(W)
    edges_mode = start_mode == "edges"
    first_hop = 1 if edges_mode else 0

    # every hop: column i -> i+1, for i in [first_hop, length-2]
    col = np.arange(width - 1)
    is_hop = (col[None, :] >= first_hop) & (col[None, :] + 1
                                            < lengths[:, None])
    w, i = np.nonzero(is_hop)
    hop_u, hop_v, hop_t = nodes[w, i], nodes[w, i + 1], times[w, i + 1]
    hop_prev = np.where((i == 0) & (not edges_mode), -1, times[w, i])
    # where each walk ended: it must have had no candidate left
    alive = lengths >= (2 if edges_mode else 1)
    short = alive & (lengths < max_len + 1)
    last = lengths[short] - 1
    end_u = nodes[rows[short], last]
    end_prev = np.where((last == 0) & (not edges_mode), -1,
                        times[rows[short], last])
    # start nodes are looked up as nodes, after -1
    starts = nodes[:, 0]
    nh, ne = hop_u.size, end_u.size
    first, end, c, lo, hi, rank = idx.nodes(
        np.concatenate([hop_u, end_u, starts]),
        np.concatenate([hop_prev, end_prev, np.full(W, -1)]),
        np.concatenate([hop_t, np.full(ne + W, -1)]))

    report.hops += nh
    matches = idx.hop_matches(lo[:nh], hi[:nh], hop_v)
    ok = (hop_t > hop_prev) & matches.any(axis=1)
    report.invalid_hops += int(np.sum(~ok))
    hb = bias[w]
    for code, name in enumerate(BIASES):
        sel = ok & (hb == code)
        if np.any(sel):
            report.add(f"hop.{name}", pit(
                hb[sel], (lo - c)[:nh][sel], matches[sel],
                (end - c)[:nh][sel], rng))
    report.early_stops += int(np.sum(end[nh:nh + ne] > c[nh:nh + ne]))

    has_edge = (end > first)[nh + ne:]
    if edges_mode:
        # the start edge: nodes[0] -> nodes[1] at times[1] == times[0]
        sel = np.flatnonzero(alive)
        base, found = idx.edge_matches(nodes[sel, 0], nodes[sel, 1],
                                       times[sel, 1])
        s_ok = found.any(axis=1) & (times[sel, 0] == times[sel, 1])
        # every walk starts on an edge while the window holds one
        report.bad_starts += int(np.sum(~s_ok)) + int(np.sum(
            lengths < 2 if idx.n else lengths == 1))
        sb = np.broadcast_to(np.asarray(start_bias), lengths.shape)[sel]
        for code, name in enumerate(BIASES):
            pick = s_ok & (sb == code)
            if np.any(pick):
                report.add(f"start_edge.{name}", pit(
                    sb[pick], base[pick], found[pick],
                    np.full(int(pick.sum()), idx.n), rng))
    else:
        # start nodes uniform over the nodes with an edge in the window
        ok = (lengths >= 1) & has_edge
        report.bad_starts += int(np.sum(~ok))
        r = rank[nh + ne:][ok]
        report.add("start_node.uniform", pit(
            np.zeros(r.shape, np.int64), r, np.ones((r.size, 1), bool),
            np.full(r.shape, idx.num_active), rng))
