"""Seeded edge streams, generated on the device from a deployment's sizes.

A stream is a sequence of batches. Batch k holds ``edges_per_batch``
edges whose timestamps are uniform over [k·span, (k+1)·span], so
consecutive batches share one tick and ties cross batch boundaries. Both
endpoints follow a Zipf(s) law over ranks 1..N, mapped to node ids by an
affine bijection of [0, N) so that hubs land on arbitrary ids.

The sampler is elementwise (no rejection loop, no table gathers): ranks
below ``_HEAD`` are read off their exact cumulative probabilities by
comparisons, and the tail is the continuous power law on
[_HEAD - 1/2, N + 1/2) rounded to the nearest rank — the midpoint rule,
whose relative error per rank is below s(s+1)/(24·_HEAD²) ≈ 1e-4.

Batch k is a pure function of k and of the seed's relabeling of the
nodes: the bulk-loaded window and the timed stream are one stream, and
the reference regenerates it from the seed. Every seed streams the same
graph under other node ids and walks it with other random draws, so
every seed does the same amount of work.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_HEAD = 32


class StreamSpec(NamedTuple):
    nodes: int              # id space [0, N)
    zipf_s: float           # endpoint popularity exponent
    edges_per_batch: int
    span: int               # timestamp units per batch
    id_mult: int            # ids = (id_mult·(rank-1) + id_add) mod N
    id_add: int


def stream_spec(cfg: dict, edges_per_batch: int) -> StreamSpec:
    """The stream of a deployment file at a given batch size: the span
    follows from the deployment's rate (edges per timestamp unit)."""
    st = cfg["stream"]
    span = int(round(edges_per_batch / st["edges_per_tick"]))
    spec = StreamSpec(nodes=int(st["nodes"]), zipf_s=float(st["zipf_s"]),
                      edges_per_batch=int(edges_per_batch), span=span,
                      id_mult=int(st["id_mult"]), id_add=int(st["id_add"]))
    if math.gcd(spec.id_mult, spec.nodes) != 1:
        raise ValueError("id_mult must be coprime with the node count")
    if spec.id_mult * (spec.nodes - 1) + spec.id_add + spec.nodes >= 2 ** 31:
        raise ValueError("id_mult·(N-1) + id_add + N must fit int32")
    return spec


def walk_seed(seed: int) -> int:
    """The program's walk seed for a run's ``--seed``, any whole number,
    also one wider than 32 bits."""
    if seed < 0:
        raise ValueError("--seed must be a whole number >= 0")
    return seed % (2 ** 31 - 1)


class Source(NamedTuple):
    """What a run's stream is drawn from: the key of its edges, the same
    for every seed, and the seed's rotation of the node ids, a device
    value so that one compiled program serves every seed."""
    key: jax.Array
    shift: jax.Array


def source(seed: int, spec: StreamSpec) -> Source:
    shift = np.random.default_rng([walk_seed(seed), 11]).integers(spec.nodes)
    return Source(jax.random.PRNGKey(0), jnp.asarray(shift, jnp.int32))


def _zipf_consts(n: int, s: float):
    """Host float64 constants: head CDF (ranks 1.._HEAD-1) and the tail's
    power-law bounds A = (_HEAD - 1/2)^(1-s), B = (N + 1/2)^(1-s)."""
    head = np.arange(1, _HEAD, dtype=np.float64) ** -s
    a = (_HEAD - 0.5) ** (1.0 - s)
    b = (n + 0.5) ** (1.0 - s)
    z = head.sum() + (a - b) / (s - 1.0)
    return np.cumsum(head) / z, a, b


def zipf_ranks(bits: jax.Array, fine: jax.Array, n: int, s: float):
    """Ranks in [1, n] from two uint32 words per draw (``fine`` refines the
    far tail, where one 24-bit uniform would skip ranks)."""
    cdf, a, b = _zipf_consts(n, s)
    u = (bits >> 8).astype(jnp.float32) * (2.0 ** -24)
    head = jnp.ones(u.shape, jnp.int32)
    for c in cdf.astype(np.float32):
        head = head + (u >= c).astype(jnp.int32)
    mass = float(cdf[-1])
    # tail: w uniform on (0, 1], small w = far tail, refined below 2^-24
    w = ((1.0 - u) - (fine >> 8).astype(jnp.float32) * (2.0 ** -48)) \
        / (1.0 - mass)
    y = b + jnp.clip(w, 0.0, 1.0) * (a - b)
    x = jnp.exp(jnp.log(y) / (1.0 - s))
    tail = jnp.clip(jnp.floor(x + 0.5), _HEAD, n).astype(jnp.int32)
    return jnp.where(u < mass, head, tail)


def _batch(key: jax.Array, k: jax.Array, spec: StreamSpec, shift):
    B = spec.edges_per_batch
    kb, kt = jax.random.split(key)
    bits = jax.random.bits(kb, (4, B), jnp.uint32)

    def ids(r):
        return (spec.id_mult * (r - 1) + spec.id_add + shift) % spec.nodes

    src = ids(zipf_ranks(bits[0], bits[1], spec.nodes, spec.zipf_s))
    dst = ids(zipf_ranks(bits[2], bits[3], spec.nodes, spec.zipf_s))
    ts = k * spec.span + jax.random.randint(kt, (B,), 0, spec.span + 1)
    return src, dst, ts.astype(jnp.int32)


def _batches_impl(src: Source, first, spec: StreamSpec, count: int):
    ks = first + jnp.arange(count, dtype=jnp.int32)
    keys = jax.vmap(lambda k: jax.random.fold_in(src.key, k))(ks)
    return jax.vmap(partial(_batch, spec=spec, shift=src.shift))(keys, ks)


# [count, B] arrays (src, dst, ts) of batches first .. first+count-1
batches = partial(jax.jit, static_argnames=("spec", "count"))(_batches_impl)


def to_host(made):
    """Batches made by ``batches`` as the program's batch-taking entries
    want them: a list of (src, dst, ts) numpy triples."""
    src, dst, ts = (np.asarray(a) for a in made)
    return [(src[i], dst[i], ts[i]) for i in range(src.shape[0])]


def device_edges(src: Source, first: int, spec: StreamSpec, count: int,
                 per_call: int = 8):
    """Batches first .. first+count-1 end to end on the device, in arrival
    order, made ``per_call`` batches at a time."""
    parts = [batches(src, k, spec, min(per_call, first + count - k))
             for k in range(first, first + count, per_call)]
    return tuple(jnp.concatenate([p[i].reshape(-1) for p in parts])
                 for i in range(3))


@partial(jax.jit, static_argnames=("spec", "count", "edge_capacity",
                                   "node_capacity", "window"))
def bulk_window(src: Source, spec: StreamSpec, count: int, window: int,
                edge_capacity: int, node_capacity: int):
    """The store that streaming batches 0..count-1 would leave, padded as
    the program pads one (src = node_capacity, dst = 0, ts = int32 max).

    Batch k's timestamps lie in [k·span, (k+1)·span], so the stable sorts
    of the batches, laid end to end, are the stable sort of the stream:
    ties at a shared tick keep the earlier batch first, as arrival order
    does. Each batch is made and sorted in turn into a store of the
    window's capacity, so memory stays at the store's size.

    Returns (src, dst, ts, num_edges, t_now, evicted): ``evicted`` counts
    the edges streaming would have evicted (older than t_now - window),
    which a bulk load must not have."""
    B, E = spec.edges_per_batch, edge_capacity
    if count * B > E:
        raise ValueError(f"{count} batches of {B} exceed the capacity {E}")
    pad_ts = jnp.iinfo(jnp.int32).max

    def put(k, store):
        s, d, ts = _batch(jax.random.fold_in(src.key, k), k, spec,
                          src.shift)
        ts, s, d = jax.lax.sort((ts, s, d), num_keys=1, is_stable=True)
        return tuple(jax.lax.dynamic_update_slice(a, x, (k * B,))
                     for a, x in zip(store, (s, d, ts)))

    src, dst, ts = jax.lax.fori_loop(
        0, count, put, (jnp.full((E,), node_capacity, jnp.int32),
                        jnp.zeros((E,), jnp.int32),
                        jnp.full((E,), pad_ts, jnp.int32)))
    n = count * B
    t_now = ts[n - 1]
    live = jnp.arange(E, dtype=jnp.int32) < n
    evicted = jnp.sum((live & (ts < t_now - window)).astype(jnp.int32))
    return src, dst, ts, jnp.asarray(n, jnp.int32), t_now, evicted
