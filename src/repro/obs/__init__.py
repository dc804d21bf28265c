"""Unified observability layer (DESIGN.md §16).

Four pieces, one substrate:

* ``registry`` — host-side metrics registry: counters / gauges /
  bounded-reservoir histograms with labels, plus the consolidated
  ``drops_total{kind=...}`` taxonomy (``DropCounters``).
* ``probes`` — jit-safe fixed-slot int32 stat vectors threaded through
  scan carries and ``shard_map`` bodies; flushed to the registry only at
  existing host sync points (zero extra device→host transfers).
* ``tracing`` — ``span(stage)`` context managers around host pipeline
  stages, mirrored into XLA profiles via ``TraceAnnotation``; the fixed
  set of device scopes (``SCOPES``, ``scope``) that name the HLO of the
  replay, advance, index and walk stages; and the compile listener
  (``jit_compiles_total``), installed on import.
* ``export`` — Prometheus text exposition, ``tempest-obs/v1`` JSON
  snapshots, ``tempest-health/v1`` streaming-health dumps, and the
  ``tempest-bench/v1`` schema every ``BENCH_*.json`` artifact shares.
"""
from repro.obs.registry import (  # noqa: F401
    DROP_KINDS,
    DROPS_METRIC,
    RESERVOIR_SIZE,
    Counter,
    DropCounters,
    Family,
    Gauge,
    Histogram,
    MetricsRegistry,
    Reservoir,
    count_drop,
    get_registry,
    new_registry,
)
from repro.obs.probes import (  # noqa: F401
    NUM_REPLAY_PROBES,
    NUM_SERVE_PROBES,
    RP_BATCHES,
    RP_EDGES_INGESTED,
    RP_EXCHANGE_DROPS,
    RP_HOPS,
    RP_LANE_STEPS,
    RP_LATE_DROPS,
    RP_LOOP_STEPS,
    RP_OVERFLOW_DROPS,
    RP_WALK_DROPS,
    RP_WALKS_EMITTED,
    SP_HOPS,
    SP_LANES_CLAIMED,
    SP_WALK_DROPS,
    flush_replay_probes,
    flush_serve_probes,
    replay_probe_update,
    replay_probe_zeros,
    serve_probe_zeros,
)
from repro.obs.tracing import (  # noqa: F401
    SCOPES,
    Span,
    install_compile_listener,
    scope,
    span,
)
from repro.obs.export import (  # noqa: F401
    BENCH_SCHEMA,
    HEALTH_SCHEMA,
    OBS_SCHEMA,
    bench_doc,
    dump_health,
    export_json,
    health_snapshot,
    to_prometheus,
    validate_bench,
    validate_health,
    validate_snapshot,
)

install_compile_listener()
