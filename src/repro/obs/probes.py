"""Jit-safe on-device probes: fixed-slot stat vectors (DESIGN.md §16).

The registry (obs/registry.py) is host-side; the replay/serve hot paths
run entirely on device under ``lax.scan`` / ``shard_map`` with exactly
one host sync per call. Probes bridge the two without adding transfers:
a fixed-slot ``int32`` stat vector — the same pattern as the
``STAT_*`` dispatch-stats layout in ``core/scheduler.py``, generalized
to streaming counters — is threaded through the scan carry (one vector
per replay) or assembled in the ``shard_map`` body (one vector per
shard), returned alongside the existing outputs, and **flushed to the
registry only at the call's existing host sync point**. Instrumented
runs are bit-identical to uninstrumented ones (the probe arithmetic
never touches the RNG chain or any walk value) and add zero extra
device→host syncs per batch — both properties are pinned by
tests/test_obs_probes.py.

Slot layouts are append-only: exporters and flushers index by the
``RP_*`` / ``SP_*`` constants, never by position literals.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.obs.registry import MetricsRegistry, count_drop

# ---------------------------------------------------------------------------
# Replay probes: one int32[NUM_REPLAY_PROBES] vector per replay (or per
# shard of a sharded replay), accumulated across the scanned batches.
# ---------------------------------------------------------------------------

RP_BATCHES = 0           # batches replayed
RP_EDGES_INGESTED = 1    # edges delivered into the window (post-exchange)
RP_LATE_DROPS = 2        # edges older than the eviction cutoff
RP_OVERFLOW_DROPS = 3    # capacity evictions of in-window edges
RP_EXCHANGE_DROPS = 4    # sharded only: ingest all_to_all bucket overflow
RP_WALK_DROPS = 5        # sharded only: walk slot/bucket overflow
RP_HOPS = 6              # hop cells executed (this shard's, when sharded)
RP_WALKS_EMITTED = 7     # walks with >= 1 hop (single-device driver)
RP_LANE_STEPS = 8        # lanes the hop loop processed (single-device driver)
RP_LOOP_STEPS = 9        # iterations the hop loop ran (single-device driver)
NUM_REPLAY_PROBES = 10

# Serve probes: one int32[NUM_SERVE_PROBES] vector per shard of a
# ``serve_lanes_sharded`` dispatch.
SP_LANES_CLAIMED = 0     # start lanes claimed by this shard
SP_WALK_DROPS = 1        # start-slot + migration overflow on this shard
SP_HOPS = 2              # hop cells executed by this shard
NUM_SERVE_PROBES = 3


def replay_probe_zeros() -> jnp.ndarray:
    return jnp.zeros((NUM_REPLAY_PROBES,), jnp.int32)


def serve_probe_zeros() -> jnp.ndarray:
    return jnp.zeros((NUM_SERVE_PROBES,), jnp.int32)


def replay_probe_update(vec, *, ingested_delta=None, late_delta=None,
                        overflow_delta=None, exchange_drops=None,
                        walk_drops=None, hops=None, lengths=None,
                        lane_steps=None, loop_steps=None):
    """One batch's accumulation into a replay probe vector (device-side).

    All arguments are optional scalars (int32); ``lengths`` is the
    batch's [W] walk-length vector, from which the hop and emitted-walk
    counts derive when the caller doesn't track them separately;
    ``lane_steps`` the lanes the batch's hop loop processed
    (``WalkResult.lane_steps``), which bound its hops from above: like
    ``RP_HOPS``, the slot holds one call's sum in int32; ``loop_steps``
    the iterations it ran (``WalkResult.steps``), summed the same way. Pure
    ``at[].add`` arithmetic — no RNG, no data-dependent control flow —
    so threading it through a scan carry cannot perturb the walk math.
    """
    vec = vec.at[RP_BATCHES].add(1)
    if ingested_delta is not None:
        vec = vec.at[RP_EDGES_INGESTED].add(ingested_delta.astype(jnp.int32))
    if late_delta is not None:
        vec = vec.at[RP_LATE_DROPS].add(late_delta.astype(jnp.int32))
    if overflow_delta is not None:
        vec = vec.at[RP_OVERFLOW_DROPS].add(overflow_delta.astype(jnp.int32))
    if exchange_drops is not None:
        vec = vec.at[RP_EXCHANGE_DROPS].add(exchange_drops.astype(jnp.int32))
    if walk_drops is not None:
        vec = vec.at[RP_WALK_DROPS].add(walk_drops.astype(jnp.int32))
    if hops is not None:
        vec = vec.at[RP_HOPS].add(hops.astype(jnp.int32))
    if lane_steps is not None:
        vec = vec.at[RP_LANE_STEPS].add(lane_steps.astype(jnp.int32))
    if loop_steps is not None:
        vec = vec.at[RP_LOOP_STEPS].add(loop_steps.astype(jnp.int32))
    if lengths is not None:
        if hops is None:
            vec = vec.at[RP_HOPS].add(
                jnp.sum(jnp.maximum(lengths - 1, 0)).astype(jnp.int32))
        vec = vec.at[RP_WALKS_EMITTED].add(
            jnp.sum((lengths >= 2).astype(jnp.int32)))
    return vec


# ---------------------------------------------------------------------------
# Host-side flush (at the caller's existing sync point)
# ---------------------------------------------------------------------------


def _shard_labels(shard: Optional[int], **extra) -> dict:
    labels = dict(extra)
    if shard is not None:
        labels["shard"] = str(shard)
    return labels


def flush_replay_probes(registry: MetricsRegistry, vec, *,
                        driver: str, shard: Optional[int] = None) -> None:
    """Publish one replay probe vector into the registry.

    ``driver`` labels the producing loop ("device" for the single-device
    scan, "sharded" for the node-partitioned one); ``shard`` adds the
    per-shard label for sharded flushes. Drop slots land in the
    consolidated ``drops_total{kind=...}`` taxonomy; the hop loop's
    lane-steps go to ``walk_lane_steps_total`` where the driver counts
    them, and the single-device driver's loop iterations, summed over the
    call's batches, are one observation of ``walk_loop_steps``.
    """
    v = np.asarray(vec, dtype=np.int64)
    if v.shape != (NUM_REPLAY_PROBES,):
        raise ValueError(
            f"replay probe vector must be [{NUM_REPLAY_PROBES}] "
            f"(got shape {v.shape})")
    lab = _shard_labels(shard, driver=driver)
    registry.inc("stream_batches_total", int(v[RP_BATCHES]), labels=lab,
                 help="batches replayed through the streaming drivers")
    registry.inc("stream_edges_ingested_total", int(v[RP_EDGES_INGESTED]),
                 labels=lab, help="edges delivered into the window")
    registry.inc("walk_hops_total", int(v[RP_HOPS]),
                 labels=_shard_labels(shard, source="replay"),
                 help="hop cells executed")
    registry.inc("walks_emitted_total", int(v[RP_WALKS_EMITTED]), labels=lab,
                 help="walks with at least one hop")
    if v[RP_LANE_STEPS]:
        registry.inc("walk_lane_steps_total", int(v[RP_LANE_STEPS]),
                     labels=_shard_labels(shard, source="replay"),
                     help="lanes processed by the hop loop, live or not")
    if shard is None:
        registry.observe("walk_loop_steps", int(v[RP_LOOP_STEPS]),
                         help="hop-loop iterations per walk call")
    count_drop(registry, "ingest_late", int(v[RP_LATE_DROPS]))
    count_drop(registry, "window_overflow", int(v[RP_OVERFLOW_DROPS]))
    count_drop(registry, "exchange_clip", int(v[RP_EXCHANGE_DROPS]))
    count_drop(registry, "walk_slot_overflow", int(v[RP_WALK_DROPS]))


def flush_serve_probes(registry: MetricsRegistry, vecs) -> None:
    """Publish a [D, NUM_SERVE_PROBES] serve probe matrix (one dispatch)."""
    v = np.asarray(vecs, dtype=np.int64)
    if v.ndim != 2 or v.shape[1] != NUM_SERVE_PROBES:
        raise ValueError(
            f"serve probe matrix must be [D, {NUM_SERVE_PROBES}] "
            f"(got shape {v.shape})")
    for d in range(v.shape[0]):
        if v[d, SP_LANES_CLAIMED]:
            registry.inc("serve_lane_claims_total",
                         int(v[d, SP_LANES_CLAIMED]),
                         labels={"shard": str(d)},
                         help="start lanes claimed per owner shard")
        if v[d, SP_HOPS]:
            registry.inc("walk_hops_total", int(v[d, SP_HOPS]),
                         labels={"source": "serve", "shard": str(d)})
    count_drop(registry, "walk_slot_overflow", int(v[:, SP_WALK_DROPS].sum()))
