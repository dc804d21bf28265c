"""A deployment file made into the program's objects, and its window
bulk-loaded from the seed.

The window a cell starts from is the one that streaming the first batches
of its stream would leave (``stream.bulk_window``): made on the device in
one jitted call, indexed once by the program's ``build_index``, and handed
to the program as its ``WindowState``. Streaming it full instead would
cost minutes of set-up in every run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import stream


def window_duration(cfg: dict) -> int:
    """Δ, in timestamp units: the span that holds ``fill`` of the window's
    capacity at the stream's rate."""
    w = cfg["window"]
    return int(w["fill"] * w["edge_capacity"] / cfg["stream"]["edges_per_tick"])


def bulk_batches(spec: stream.StreamSpec, delta: int) -> int:
    """Batches that fill the window's duration without evicting an edge:
    the next batch streamed starts the evictions."""
    return delta // spec.span


def engine_config(cfg: dict, seed: int, **sampler_overrides):
    """The program's engine for a deployment; ``seed`` is the run's, which
    sets the program's walk draws."""
    from repro.configs.base import (EngineConfig, SamplerConfig,
                                    SchedulerConfig, WindowConfig)
    w = cfg["window"]
    sampler = dict(cfg["sampler"], **sampler_overrides)
    return EngineConfig(
        window=WindowConfig(edge_capacity=w["edge_capacity"],
                            node_capacity=w["node_capacity"],
                            duration=window_duration(cfg)),
        sampler=SamplerConfig(**sampler),
        scheduler=SchedulerConfig(**cfg["scheduler"]),
        seed=stream.walk_seed(seed))


def bulk_state(source: stream.Source, spec: stream.StreamSpec, count: int,
               delta: int, edge_capacity: int, node_capacity: int):
    """The program's ``WindowState`` after streaming batches 0..count-1."""
    from repro.core.edge_store import EdgeStore
    from repro.core.temporal_index import build_index
    from repro.core.window import WindowState

    src, dst, ts, n, t_now, evicted = stream.bulk_window(
        source, spec, count, delta, edge_capacity, node_capacity)
    if int(evicted):
        raise ValueError(f"the bulk load would evict {int(evicted)} edges")
    store = EdgeStore(src=src, dst=dst, ts=ts, num_edges=n)

    def scalar(v):
        return jnp.asarray(v, jnp.int32)

    state = WindowState(
        index=build_index(store, node_capacity), t_now=t_now,
        window=scalar(delta), ingested=scalar(count * spec.edges_per_batch),
        late_drops=scalar(0), overflow_drops=scalar(0))
    jax.block_until_ready(state)
    return state
