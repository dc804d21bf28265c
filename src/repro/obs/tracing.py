"""Stage tracing: host spans, device scopes and the compile listener
(DESIGN.md §16).

``span(stage)`` is a context manager around one host-observable pipeline
stage — ingest merge, snapshot publish, coalesce, dispatch, result
slicing — that records the stage's wall time into the registry
(the ``stage_seconds{stage=...}`` histogram, whose count is the calls)
and, when the JAX profiler is active, mirrors the span as a
``jax.profiler.TraceAnnotation`` so host stages line up with XLA device
lanes in the trace viewer::

    with span("ingest_merge", registry=reg):
        state = ingest(state, batch, nc)
        jax.block_until_ready(state.index.ns_order)

Spans nest freely (each records its own wall time; no parent/child
bookkeeping — the profiler timeline shows nesting already). ``args``
(e.g. the call's sequence number) ride the profiler annotation only, so
one call's spans can be grouped in a trace.

Device work is named by ``scope(name)``, a ``jax.named_scope`` restricted
to the fixed set ``SCOPES``: the name lands in the ``op_name`` metadata
of every HLO op traced under it, which the profiler reports as the op's
``tf_op``, so device time can be split by stage without reading source
files. Scopes cost nothing at run time; they change metadata only.

``install_compile_listener`` hooks ``jax.monitoring`` once per process:
every executable JAX builds or loads from the persistent compilation
cache counts into ``jit_compiles_total{source=backend|cache}`` and its
seconds into ``compile_seconds``.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Iterator, Optional

from repro.obs.registry import MetricsRegistry, get_registry

import jax
from jax import monitoring
from jax.profiler import TraceAnnotation

STAGE_METRIC = "stage_seconds"
COMPILES_METRIC = "jit_compiles_total"
COMPILE_SECONDS_METRIC = "compile_seconds"

# Device scopes, outermost first where they nest: ``replay`` holds the
# replay scan; ``advance`` (window advance), ``index`` (index rebuild) and
# ``walks`` (walk generation) the stages of one ingest-and-walk step;
# ``start`` and ``hop`` the walk start and the hop loop's body, which
# splits into ``regroup`` (the per-hop lane regroup or sort) and ``pick``
# (search, draw, gather and write).
SCOPES = ("replay", "advance", "index", "walks", "start", "hop", "regroup",
          "pick")


def scope(name: str):
    """``jax.named_scope(name)`` for one of ``SCOPES``; any other name is
    an error, so the set a trace reader relies on cannot drift."""
    if name not in SCOPES:
        raise ValueError(f"unknown device scope {name!r}; known: {SCOPES}")
    return jax.named_scope(name)


class Span:
    """Handle yielded by ``span``; ``elapsed_s`` is set on exit."""

    __slots__ = ("stage", "elapsed_s")

    def __init__(self, stage: str):
        self.stage = stage
        self.elapsed_s: float = 0.0


@contextmanager
def span(stage: str, registry: Optional[MetricsRegistry] = None,
         labels: Optional[dict] = None,
         annotate: bool = True, args: Optional[dict] = None
         ) -> Iterator[Span]:
    """Time one pipeline stage into the registry (and the XLA profile).

    ``labels`` merge into the ``stage_seconds`` series key beside the
    stage name (e.g. ``{"path": "fused"}``); ``annotate=False`` skips the
    profiler pass-through for spans inside profiler-hostile loops;
    ``args`` become arguments of the profiler annotation (not labels).
    The stage time is recorded even when the body raises — a failing
    dispatch still shows up in the stage histogram.
    """
    reg = registry if registry is not None else get_registry()
    handle = Span(stage)
    lab = {"stage": stage}
    if labels:
        lab.update(labels)
    ann = TraceAnnotation(f"obs:{stage}", **(args or {})) \
        if annotate else None
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


# jax.monitoring events: one per executable built or loaded (the duration
# event), and a persistent-cache hit recorded inside the same build
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_listener = threading.local()
_installed = False


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT_EVENT:
        _listener.cache_hit = True


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    if event != _BACKEND_COMPILE_EVENT:
        return
    source = "cache" if getattr(_listener, "cache_hit", False) else "backend"
    _listener.cache_hit = False
    reg = get_registry()
    lab = {"source": source}
    reg.inc(COMPILES_METRIC, 1, labels=lab,
            help="executables compiled (backend) or loaded from the "
                 "persistent compilation cache (cache)")
    reg.observe(COMPILE_SECONDS_METRIC, seconds, labels=lab,
                help="seconds per executable compiled or loaded")


def install_compile_listener() -> None:
    """Count compiles into the default registry; idempotent."""
    global _installed
    if _installed:
        return
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True
