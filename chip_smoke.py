#!/usr/bin/env python3
"""Drive the streaming walk engine once on a TPU and check what comes out.

    python chip_smoke.py [--seed S]           # one chip: phases A, B, C
    python chip_smoke.py --chips 4 [--seed S] # node-partitioned replay only

Phase A, streaming replay (the main path): ``StreamingEngine.replay_device``
at a 2^27-edge window over 2^22 nodes, fed 1.125x its capacity from a
45-minute millisecond stream (Zipf(1.2) endpoints, ~56 edges per ms) in
chunks of 16 batches of 2^20 edges, with 2^17 walks of length 80 per batch.
The final window must equal a plain numpy sliding window over the same
stream, and every hop of the final walks must be causally valid.

Phase B, serving: ``WalkService`` over a 2^26-edge window filled to at
least 80%, answering queries through submit/tick/drain; each answer must be
hop-valid and bit-identical to ``run_query_solo``.

Phase C, compiled kernels: on a 2^20-edge window, walks on the fused
(index and weight modes) and tiled Pallas paths must be byte-identical to
the grouped path, and each lowered program must hold a ``tpu_custom_call``.

``--chips 4`` runs only the sharded replay: ``DistributedStreamingEngine``
over four chips (2^25 edges per shard) against a single-device
``StreamingEngine`` reference at the same global capacity; stats and final
walks must be bit-identical, and every shard must live on its own chip.

All data is generated from ``--seed``. Each phase prints one result line;
the last line of standard output is ``{"ok": true, "device": {...}}``.
Without a TPU the script exits non-zero before doing any work. Compiled
programs are cached in ``$JAX_COMPILATION_CACHE_DIR`` when it is set, and
otherwise in ``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent

NODES = 1 << 22                 # node id space
ZIPF_SKEW = 1.2                 # endpoint popularity exponent
BATCH = 1 << 20                 # edges per replay batch
SPAN_MS = 18_750                # stream time per replay batch (~56 edges/ms)
CHUNK = 16                      # batches per replay_device call
SHARDED_BATCHES = 8             # batches of the --chips 4 replay
WALKS = 1 << 17                 # walks per replay batch
WALK_LEN = 80
SERVE_BATCH = 1 << 22           # edges per serving ingest
KERNEL_EDGES = 1 << 20          # window of the compiled-kernel phase
KERNEL_WALKS = 1 << 14


# ---------------------------------------------------------------------------
# Stream and the plain sliding-window reference (numpy only)
# ---------------------------------------------------------------------------


class Stream:
    """Seeded edge stream: batch k covers timestamps [k·span, (k+1)·span]
    (consecutive batches share one millisecond, so ties cross batches).
    Endpoints are Zipf(ZIPF_SKEW) ranks truncated to NODES, scattered over
    the id space by a fixed permutation so hubs land on arbitrary ids."""

    def __init__(self, seed: int, edges_per_batch: int = 0):
        self.seed = seed
        self.n = edges_per_batch or BATCH
        self.span = SPAN_MS * self.n // BATCH
        self.ids = np.random.default_rng([seed, 0]).permutation(
            NODES).astype(np.int32)

    def _zipf(self, rng, n: int) -> np.ndarray:
        ranks = rng.zipf(ZIPF_SKEW, n)
        bad = ranks > NODES
        while bad.any():
            ranks[bad] = rng.zipf(ZIPF_SKEW, int(bad.sum()))
            bad = ranks > NODES
        return self.ids[ranks - 1]

    def batch(self, k: int):
        rng = np.random.default_rng([self.seed, 1, k])
        src = self._zipf(rng, self.n)
        dst = self._zipf(rng, self.n)
        ts = (k * self.span
              + rng.integers(0, self.span + 1, self.n)).astype(np.int32)
        return src, dst, ts


class WindowReference:
    """Every edge with ts >= t_now - window, ordered by timestamp; ties in
    arrival order (within a batch by position, across batches the earlier
    batch first) — the two-run merge rule of core/window.py."""

    def __init__(self, window: int):
        self.window = window
        self.t_now = 0
        self.parts: deque = deque()

    def add(self, src, dst, ts) -> None:
        order = np.argsort(ts, kind="stable")
        self.parts.append((src[order], dst[order], ts[order]))
        self.t_now = max(self.t_now, int(ts.max()))
        while self.parts and self.parts[0][2][-1] < self.t_now - self.window:
            self.parts.popleft()

    def edges(self):
        src, dst, ts = (np.concatenate(c) for c in zip(*self.parts))
        order = np.argsort(ts, kind="stable")
        keep = ts[order] >= self.t_now - self.window
        return src[order][keep], dst[order][keep], ts[order][keep]


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def emit(phase: str, **fields) -> None:
    print(f"phase {phase}: " + json.dumps(fields), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit")}


def walk_hops(stats, num_walks: int) -> int:
    """Hops of a replay chunk: every start-node walk has length >= 1."""
    return int(round(float(np.sum(stats.mean_len.astype(np.float64) - 1.0))
                     * num_walks))


def validate(index, nodes, times, lengths) -> float:
    import jax.numpy as jnp

    from repro.core.validation import validate_walks
    from repro.core.walk_engine import WalkResult

    rep = validate_walks(index, WalkResult(
        nodes=jnp.asarray(nodes), times=jnp.asarray(times),
        lengths=jnp.asarray(lengths), stats=None))
    return float(rep.hop_valid_frac)


def check_window_equals(state, ref: WindowReference, node_capacity: int):
    from repro.core.edge_store import TS_PAD

    src, dst, ts = ref.edges()
    store = state.index.store
    n = int(store.num_edges)
    check(n == src.size, f"window holds {n} edges, reference {src.size}")
    check(int(state.t_now) == ref.t_now,
          f"t_now {int(state.t_now)} != reference {ref.t_now}")
    for name, got, want, pad in (("src", store.src, src, node_capacity),
                                 ("dst", store.dst, dst, 0),
                                 ("ts", store.ts, ts, TS_PAD)):
        got = np.asarray(got)
        check(np.array_equal(got[:n], want), f"store.{name} != reference")
        check(bool(np.all(got[n:] == pad)), f"store.{name} padding")
    return n


# ---------------------------------------------------------------------------
# Phase A: streaming replay at a window that fills the chip
# ---------------------------------------------------------------------------


def phase_a(dev, seed: int, capacity: int) -> None:
    import jax.numpy as jnp  # noqa: F401  (jax is initialised by main)

    from repro.configs.base import (EngineConfig, SamplerConfig,
                                    SchedulerConfig, WalkConfig,
                                    WindowConfig)
    from repro.core.streaming import StreamingEngine

    # 1.125x capacity: the window fills to ~90% and then evicts for ~30
    # batches. More does not fit the run's 20 minutes: one batch (ingest
    # with its index rebuild, then the walks) takes ~3.8 s on a v5e.
    chunks = 9 * capacity // (8 * BATCH * CHUNK)
    num_batches = chunks * CHUNK
    delta = int(0.9 * capacity / BATCH * SPAN_MS)       # ~90% steady fill
    stream = Stream(seed)
    ref = WindowReference(delta)
    engine = StreamingEngine(EngineConfig(
        window=WindowConfig(edge_capacity=capacity, node_capacity=NODES,
                            duration=delta),
        sampler=SamplerConfig(bias="exponential", mode="index"),
        scheduler=SchedulerConfig(path="grouped")), batch_capacity=BATCH)
    wcfg = WalkConfig(num_walks=WALKS, max_length=WALK_LEN,
                      start_mode="nodes")

    def make_chunk(c: int):
        t0 = time.perf_counter()
        out = []
        for k in range(c * CHUNK, (c + 1) * CHUNK):
            b = stream.batch(k)
            ref.add(*b)
            out.append(b)
        return out, time.perf_counter() - t0

    rows = []
    with ThreadPoolExecutor(1) as pool:           # next chunk is generated
        nxt = pool.submit(make_chunk, 0)           # while the chip works
        for c in range(chunks):
            batches, gen_s = nxt.result()
            if c + 1 < chunks:
                nxt = pool.submit(make_chunk, c + 1)
            last = c == chunks - 1
            out = engine.replay_device(batches, wcfg, return_walks=last)
            stats, secs = (out[0], out[2]) if last else out
            row = dict(chunk=c, seconds=secs,
                       timing="includes compilation" if c == 0 else "warm",
                       host_generation_s=gen_s,
                       edges_active=int(stats.edges_active[-1]),
                       edges_per_s=CHUNK * BATCH / secs,
                       hops_per_s=walk_hops(stats, WALKS) / secs)
            rows.append(row)
            print(f"  A chunk {json.dumps(row)}", flush=True)
    walks = out[1]
    late = int(stats.late_drops[-1])
    overflow = int(stats.overflow_drops[-1])
    ingested = int(stats.ingested[-1])
    active = int(stats.edges_active[-1])
    evicted = ingested - active - late - overflow
    check(late == 0 and overflow == 0,
          f"late_drops={late} overflow_drops={overflow}")
    check(evicted > 0, "no edge was evicted")
    check(active >= 0.85 * capacity,
          f"window only {active / capacity:.3f} full")
    n = check_window_equals(engine.state, ref, NODES)
    hop_valid = validate(engine.state.index, walks.nodes, walks.times,
                         walks.lengths)
    check(hop_valid == 1.0, f"hop_valid_frac={hop_valid}")
    warm = sorted(rows[1:], key=lambda r: r["seconds"])
    median = warm[len(warm) // 2] if warm else {}
    emit("A", path="StreamingEngine.replay_device", edge_capacity=capacity,
         node_capacity=NODES, window_ms=delta, batches=num_batches,
         batch=BATCH, walks=WALKS, walk_length=WALK_LEN,
         edges_ingested=ingested, edges_active=active,
         fill=active / capacity, evicted=evicted, late_drops=late,
         overflow_drops=overflow, window_equals_reference=True,
         reference_edges=n, hop_valid_frac=hop_valid,
         first_chunk_s_includes_compilation=rows[0]["seconds"],
         median_warm_chunk_s=median.get("seconds"),
         warm_edges_per_s=median.get("edges_per_s"),
         warm_hops_per_s=median.get("hops_per_s"), **memory(dev))
    del engine, walks, out, ref
    gc.collect()


# ---------------------------------------------------------------------------
# Phase B: serving over a double-buffered 2^26-edge window
# ---------------------------------------------------------------------------


def phase_b(dev, seed: int, capacity: int) -> None:
    from repro.configs.base import (EngineConfig, SamplerConfig,
                                    SchedulerConfig, ServeConfig,
                                    WindowConfig)
    from repro.serve import WalkQuery, WalkService

    delta = int(0.9 * capacity / BATCH * SPAN_MS)
    svc = WalkService(EngineConfig(
        window=WindowConfig(edge_capacity=capacity, node_capacity=NODES,
                            duration=delta),
        sampler=SamplerConfig(mode="index"),
        scheduler=SchedulerConfig(path="grouped")), ServeConfig(),
        batch_capacity=SERVE_BATCH)
    stream = Stream(seed + 1, SERVE_BATCH)
    t0 = time.perf_counter()
    k = 0
    while int(svc.snapshots.current.index.num_edges) < 0.8 * capacity:
        src, dst, ts = stream.batch(k)
        svc.ingest(src, dst, ts)
        k += 1
    ingest_s = time.perf_counter() - t0
    active = int(svc.snapshots.current.index.num_edges)

    starts = np.unique(src)                         # active in the window
    rng = np.random.default_rng([seed, 2])
    pick = lambda n: tuple(int(v) for v in rng.choice(starts, n,  # noqa
                                                      replace=False))
    queries = [
        WalkQuery(start_nodes=pick(64), bias="exponential", max_length=80,
                  seed=seed + 11),
        WalkQuery(start_nodes=pick(64), bias="linear", max_length=50,
                  seed=seed + 12),
        WalkQuery(start_mode="edges", num_walks=64, bias="uniform",
                  start_bias="exponential", max_length=16, seed=seed + 13),
        WalkQuery(start_mode="edges", num_walks=64, bias="exponential",
                  start_bias="linear", max_length=80, seed=seed + 14),
    ]
    t0 = time.perf_counter()
    tickets = [svc.submit(q, strict=True) for q in queries]
    svc.tick()
    done = {r.ticket: r for r in svc.drain()}
    for t in tickets:
        if t not in done:
            done[t] = svc.poll(t)
    serve_s = time.perf_counter() - t0
    check(all(done.get(t) is not None for t in tickets),
          "a query went unanswered")

    width = max(q.max_length for q in queries) + 1
    nodes, times, lengths = [], [], []
    for q, t in zip(queries, tickets):
        r = done[t]
        sn, st, sl = svc.run_query_solo(q)
        check(np.array_equal(r.nodes, sn) and np.array_equal(r.times, st)
              and np.array_equal(r.lengths, sl),
              f"served answer != solo run for {q.bias}/{q.start_mode}")
        pad = width - r.nodes.shape[1]
        nodes.append(np.pad(r.nodes, ((0, 0), (0, pad)),
                            constant_values=-1))
        times.append(np.pad(r.times, ((0, 0), (0, pad))))
        lengths.append(r.lengths)
    lengths = np.concatenate(lengths)
    hop_valid = validate(svc.snapshots.current.index,
                         np.concatenate(nodes), np.concatenate(times),
                         lengths)
    check(hop_valid == 1.0, f"hop_valid_frac={hop_valid}")
    check(int(np.sum(lengths - 1)) > 0, "no hop was taken")
    emit("B", path="WalkService submit/tick/drain", edge_capacity=capacity,
         edges_active=active, fill=active / capacity, ingest_batches=k,
         ingest_batch=SERVE_BATCH,
         ingest_s_includes_compilation=ingest_s, queries=len(queries),
         serve_s_includes_compilation=serve_s,
         hops=int(np.sum(lengths - 1)), hop_valid_frac=hop_valid,
         identical_to_solo=True, **memory(dev))
    del svc
    gc.collect()


# ---------------------------------------------------------------------------
# Phase C: compiled Pallas kernels against the grouped path
# ---------------------------------------------------------------------------


def phase_c(dev, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import (EngineConfig, SamplerConfig,
                                    SchedulerConfig, WalkConfig,
                                    WindowConfig)
    from repro.core.streaming import StreamingEngine
    from repro.core.walk_engine import generate_walks
    from repro.kernels import ref as kref
    from repro.kernels.weight_prefix import weight_prefix

    capacity = KERNEL_EDGES
    engine = StreamingEngine(EngineConfig(
        window=WindowConfig(edge_capacity=capacity, node_capacity=NODES,
                            duration=1 << 30)), batch_capacity=capacity)
    engine.ingest_batch(*Stream(seed + 2, capacity - capacity // 8).batch(0))
    index = engine.state.index
    wcfg = WalkConfig(num_walks=KERNEL_WALKS, max_length=WALK_LEN,
                      start_mode="nodes")
    key = jax.random.PRNGKey(seed)
    results = {}
    for mode in ("index", "weight"):
        scfg = SamplerConfig(bias="exponential", mode=mode)
        want = generate_walks(index, key, wcfg, scfg,
                              SchedulerConfig(path="grouped"))
        want = [np.asarray(a) for a in (want.nodes, want.times,
                                        want.lengths)]
        for path in ("fused", "tiled"):
            lowered = generate_walks.lower(index, key, wcfg, scfg,
                                           SchedulerConfig(path=path))
            check("tpu_custom_call" in lowered.as_text(),
                  f"no compiled kernel in the {path}/{mode} program")
            got = lowered.compile()(index, key)
            got = [np.asarray(a) for a in (got.nodes, got.times,
                                           got.lengths)]
            check(all(np.array_equal(g, w) for g, w in zip(got, want)),
                  f"{path}/{mode} walks differ from grouped")
            results[f"{path}_{mode}"] = "identical to grouped"
    hops = int(np.sum(want[2] - 1))

    nc = index.node_capacity
    dt = (index.ns_ts - index.node_tref[jnp.clip(index.ns_src, 0, nc - 1)]
          ).astype(jnp.float32)
    valid = index.ns_src < nc
    lowered = weight_prefix.lower(dt, valid, interpret=False)
    check("tpu_custom_call" in lowered.as_text(),
          "no compiled kernel in the weight_prefix program")
    got = np.asarray(lowered.compile()(dt, valid))
    want = np.asarray(kref.weight_prefix_ref(dt, valid))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    results["weight_prefix"] = "matches reference"
    emit("C", edge_capacity=capacity,
         edges_active=int(index.num_edges), walks=wcfg.num_walks,
         walk_length=WALK_LEN, hops=hops, tpu_custom_call=True, **results)
    del engine, index
    gc.collect()


# ---------------------------------------------------------------------------
# --chips 4: node-partitioned replay against the single-device engine
# ---------------------------------------------------------------------------


def phase_sharded(seed: int, per_shard: int) -> None:
    import jax

    from repro.configs.base import (EngineConfig, SamplerConfig,
                                    SchedulerConfig, ShardConfig,
                                    WalkConfig, WindowConfig)
    from repro.core.streaming import StreamingEngine
    from repro.distributed.streaming_shard import DistributedStreamingEngine

    devices = jax.devices()
    D = len(devices)
    check(D == 4, f"--chips 4 needs four devices, JAX sees {D}")
    capacity = D * per_shard
    delta = SHARDED_BATCHES * SPAN_MS * 5 // 8   # 5 of 8 batches: evictions
    stream = Stream(seed)
    batches = [stream.batch(k) for k in range(SHARDED_BATCHES)]
    # the sharded walker starts walk w at node w (owner-computable starts)
    wcfg = WalkConfig(num_walks=WALKS, max_length=WALK_LEN,
                      start_mode="all_nodes")
    cfg = EngineConfig(
        window=WindowConfig(edge_capacity=capacity, node_capacity=NODES,
                            duration=delta),
        sampler=SamplerConfig(bias="exponential", mode="index"),
        scheduler=SchedulerConfig(path="grouped"),
        # provisioned so nothing can drop: a sender's whole batch slice
        # fits one bucket, every walk fits one shard and one bucket
        shard=ShardConfig(num_shards=D, edge_capacity_per_shard=per_shard,
                          exchange_capacity=BATCH // D, walk_slots=WALKS,
                          walk_bucket_capacity=WALKS))

    ref = StreamingEngine(cfg, batch_capacity=BATCH)
    rstats, rwalks, ref_s = ref.replay_device(batches, wcfg,
                                              return_walks=True)
    del ref
    gc.collect()

    dist = DistributedStreamingEngine(cfg, batch_capacity=BATCH,
                                      num_shards=D)
    dstats, dwalks, dist_s = dist.replay_device(batches, wcfg)
    check(int(dstats.exchange_drops.sum()) == 0, "exchange drops")
    check(int(dstats.walk_drops.sum()) == 0, "walk drops")
    for f in rstats._fields:
        check(np.array_equal(getattr(rstats, f), getattr(dstats.replay, f)),
              f"ReplayStats.{f} differs from the single-device engine")
    for f in ("nodes", "times", "lengths"):
        check(np.array_equal(getattr(rwalks, f), getattr(dwalks, f)),
              f"walks.{f} differ from the single-device engine")
    check(int(rstats.edges_active[-1]) < int(rstats.ingested[-1]),
          "no edge was evicted")

    # every leaf of the sharded window: one row per chip, one chip per row
    for leaf in jax.tree_util.tree_leaves(dist.state):
        shards = leaf.addressable_shards
        check(sorted(s.device.id for s in shards)
              == sorted(d.id for d in devices),
              "a window shard is not on its own chip")
        check(all(s.data.shape[0] == 1 for s in shards),
              "the window is replicated, not partitioned")
    loads = [int(v) for v in dist.shard_loads()]
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    emit("sharded", path="DistributedStreamingEngine.replay_device",
         chips=D, edge_capacity_per_shard=per_shard,
         global_edge_capacity=capacity, batches=len(batches), batch=BATCH,
         window_ms=delta, edges_active=int(rstats.edges_active[-1]),
         shard_edges=loads, bytes_in_use_per_chip=in_use,
         identical_to_single_device=True,
         single_device_s_includes_compilation=ref_s,
         sharded_s_includes_compilation=dist_s)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))
    sys.path.insert(0, str(REPO / "src"))

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(args.seed, per_shard=1 << 25)
    else:
        phase_a(dev, args.seed, capacity=1 << 27)
        phase_b(dev, args.seed, capacity=1 << 26)
        phase_c(dev, args.seed)
    print(f"total {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
