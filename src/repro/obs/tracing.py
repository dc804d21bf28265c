"""Span-based stage tracing (DESIGN.md §16).

``span(stage)`` is a context manager around one host-observable pipeline
stage — ingest merge, snapshot publish, coalesce, dispatch, result
slicing — that records the stage's wall time into the registry
(``stage_seconds{stage=...}`` histogram + ``stage_calls_total`` counter)
and, when the JAX profiler is active, mirrors the span as a
``jax.profiler.TraceAnnotation`` so host stages line up with XLA device
lanes in the trace viewer::

    with span("ingest_merge", registry=reg):
        state = ingest(state, batch, nc)
        jax.block_until_ready(state.index.ns_order)

Spans nest freely (each records its own wall time; no parent/child
bookkeeping — the profiler timeline shows nesting already). For
device-side (traced, inside-jit) scopes use ``named_scope`` — a
re-export of ``jax.named_scope`` — which names the emitted HLO instead.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Iterator, Optional

from repro.obs.registry import MetricsRegistry, get_registry

from jax import named_scope             # noqa: F401  (re-export)
from jax.profiler import TraceAnnotation

STAGE_METRIC = "stage_seconds"
STAGE_CALLS_METRIC = "stage_calls_total"


class Span:
    """Handle yielded by ``span``; ``elapsed_s`` is set on exit."""

    __slots__ = ("stage", "elapsed_s")

    def __init__(self, stage: str):
        self.stage = stage
        self.elapsed_s: float = 0.0


@contextmanager
def span(stage: str, registry: Optional[MetricsRegistry] = None,
         labels: Optional[dict] = None,
         annotate: bool = True) -> Iterator[Span]:
    """Time one pipeline stage into the registry (and the XLA profile).

    ``labels`` merge into the ``stage_seconds`` series key beside the
    stage name (e.g. ``{"path": "fused"}``); ``annotate=False`` skips the
    profiler pass-through for spans inside profiler-hostile loops.
    The stage time is recorded even when the body raises — a failing
    dispatch still shows up in the stage histogram.
    """
    reg = registry if registry is not None else get_registry()
    handle = Span(stage)
    lab = {"stage": stage}
    if labels:
        lab.update(labels)
    ann = TraceAnnotation(f"obs:{stage}") if annotate else None
    t0 = time.perf_counter()
    try:
        if ann is not None:
            with ann:
                yield handle
        else:
            yield handle
    finally:
        handle.elapsed_s = time.perf_counter() - t0
        reg.observe(STAGE_METRIC, handle.elapsed_s, labels=lab,
                    help="host wall time per pipeline stage")
        reg.inc(STAGE_CALLS_METRIC, 1, labels=lab,
                help="invocations per pipeline stage")
