"""Observability layer (repro.obs, DESIGN.md §16): registry semantics,
bounded reservoirs, the consolidated drop taxonomy, stage tracing,
exporter schemas, and the no-extra-syncs contract on the instrumented
replay driver."""
import json
import math

import jax
import numpy as np
import pytest

from repro.configs.base import (
    EngineConfig,
    SamplerConfig,
    SchedulerConfig,
    ServeConfig,
    WalkConfig,
    WindowConfig,
)
from repro.core.streaming import StreamingEngine
from repro.data.synthetic import chronological_batches, powerlaw_temporal_graph
from repro.obs import (
    DROP_KINDS,
    DropCounters,
    Reservoir,
    bench_doc,
    count_drop,
    dump_health,
    export_json,
    health_snapshot,
    new_registry,
    span,
    to_prometheus,
    validate_bench,
    validate_health,
    validate_snapshot,
)
from repro.serve import WalkQuery, WalkService
from repro.serve.service import STATS_WINDOW, ServeStats

NC = 128


def _engine_cfg():
    return EngineConfig(
        window=WindowConfig(duration=4000, edge_capacity=4096,
                            node_capacity=NC),
        sampler=SamplerConfig(mode="index"),
        scheduler=SchedulerConfig(path="grouped"))


def _serve_cfg():
    return ServeConfig(lane_buckets=(8, 16, 64), length_buckets=(4, 8))


# ---------------------------------------------------------------------------
# Reservoir + registry primitives
# ---------------------------------------------------------------------------


def test_reservoir_ring_buffer_bounds():
    r = Reservoir(4)
    for v in (1, 2, 3, 4, 5, 6):
        r.add(v)
    assert len(r) == 4
    assert r.count == 6                      # lifetime, not resident
    assert r.total == 21.0
    assert r.values() == [3.0, 4.0, 5.0, 6.0]   # oldest-first after wrap
    np.testing.assert_array_equal(np.asarray(r), [3.0, 4.0, 5.0, 6.0])


def test_reservoir_percentile_contract():
    r = Reservoir(8)
    assert math.isnan(r.percentile(50))      # empty -> nan
    r.add(7.5)
    assert r.percentile(0) == 7.5            # singleton -> the value
    assert r.percentile(50) == 7.5
    assert r.percentile(100) == 7.5
    with pytest.raises(ValueError):
        r.percentile(-1)
    with pytest.raises(ValueError):
        r.percentile(101)
    r2 = Reservoir(256)
    for v in range(101):
        r2.add(float(v))
    assert r2.percentile(50) == 50.0


def test_registry_counters_gauges_histograms():
    reg = new_registry()
    reg.inc("foo_total", 2, help="foo")
    reg.inc("foo_total", 3, labels={"a": "x"})
    assert reg.value("foo_total") == 2
    assert reg.value("foo_total", labels={"a": "x"}) == 3
    assert reg.sum_values("foo_total") == 5
    reg.set_gauge("depth", 7)
    reg.set_gauge("depth", 3)
    assert reg.value("depth") == 3           # gauges overwrite
    for v in (0.1, 0.2, 0.3):
        reg.observe("lat_seconds", v)
    h = reg.histogram("lat_seconds")
    assert h.count == 3
    assert h.sum == pytest.approx(0.6)
    with pytest.raises(ValueError):
        reg.inc("foo_total", -1)             # counters are monotonic
    with pytest.raises(ValueError):
        reg.counter("Bad-Name")              # name charset is enforced
    with pytest.raises(ValueError):
        reg.gauge("foo_total")               # kind conflicts are errors


def test_drop_taxonomy_and_dropcounters():
    reg = new_registry()
    count_drop(reg, "ingest_late", 3)
    count_drop(reg, "oversize", 1)
    count_drop(reg, "exchange_clip", 0)      # zero increments are skipped
    with pytest.raises(ValueError):
        count_drop(reg, "not_a_kind", 1)
    dc = DropCounters.from_registry(reg)
    assert dc.ingest_late == 3 and dc.oversize == 1
    assert dc.total == 4
    d = dc.as_dict()
    assert d["total"] == 4
    for kind in DROP_KINDS:
        assert kind in d                     # every kind always present
    assert d["exchange_clip"] == 0


def test_span_records_even_on_exception():
    reg = new_registry()
    with span("happy", reg):
        pass
    with pytest.raises(RuntimeError):
        with span("sad", reg, labels={"who": "t"}):
            raise RuntimeError("boom")
    assert reg.histogram("stage_seconds", labels={"stage": "happy"}).count == 1
    assert reg.histogram("stage_seconds",
                         labels={"stage": "sad", "who": "t"}).count == 1
    h = reg.histogram("stage_seconds", labels={"stage": "sad", "who": "t"})
    assert h.count == 1 and h.sum >= 0


# ---------------------------------------------------------------------------
# Exporters + schemas
# ---------------------------------------------------------------------------


def test_prometheus_and_json_export():
    reg = new_registry()
    reg.inc("walks_total", 5, labels={"path": "a b\"c"}, help="walks done")
    reg.set_gauge("occ", 0.5)
    reg.observe("lat_seconds", 0.25)
    text = to_prometheus(reg)
    assert "# HELP walks_total walks done" in text
    assert "# TYPE walks_total counter" in text
    assert 'path="a b\\"c"' in text          # label escaping
    assert "# TYPE lat_seconds summary" in text
    assert 'lat_seconds{quantile="0.5"} 0.25' in text
    assert "lat_seconds_count 1" in text

    doc = export_json(reg)                   # self-validating
    assert doc["schema"] == "tempest-obs/v1"
    assert doc["metrics"]["walks_total"]["series"][0]["value"] == 5
    hist = doc["metrics"]["lat_seconds"]["series"][0]
    assert hist["count"] == 1 and hist["p50"] == 0.25
    json.dumps(doc)                          # round-trippable
    bad = dict(doc, schema="nope/v9")
    with pytest.raises(ValueError):
        validate_snapshot(bad)


def test_bench_schema():
    doc = bench_doc("suite_x", [{"name": "r0", "us_per_call": 1.5,
                                 "derived": "k=v"}],
                    results={"extra": {"n": 1}})
    assert validate_bench(doc) is doc
    with pytest.raises(ValueError):
        validate_bench(dict(doc, rows=[{"name": "r0",
                                        "us_per_call": float("nan")}]))
    with pytest.raises(ValueError):
        validate_bench(dict(doc, rows=[{"us_per_call": 1.0}]))
    with pytest.raises(ValueError):
        validate_bench(dict(doc, suite=""))


def test_serve_stats_latency_contract():
    st = ServeStats()
    assert math.isnan(st.latency_percentile(50))   # empty -> nan
    st.latencies_s.append(0.040)
    assert st.latency_percentile(50) == 0.040      # singleton -> the value
    assert st.p50_ms == pytest.approx(40.0)
    with pytest.raises(ValueError):
        st.latency_percentile(150)
    # bounded: the reservoir never grows past STATS_WINDOW entries
    assert st.latencies_s.capacity == STATS_WINDOW
    for _ in range(STATS_WINDOW + 10):
        st.sample_s.append(0.001)
    assert len(st.sample_s) == STATS_WINDOW


# ---------------------------------------------------------------------------
# Instrumented engines: metrics smoke + the no-extra-syncs contract
# ---------------------------------------------------------------------------


def test_streaming_engine_metrics_smoke():
    reg = new_registry()
    g = powerlaw_temporal_graph(100, 2000, seed=5)
    eng = StreamingEngine(_engine_cfg(), batch_capacity=1024, registry=reg)
    wcfg = WalkConfig(num_walks=128, max_length=8, start_mode="nodes")
    stats, _ = eng.replay_device(chronological_batches(g, 3), wcfg)

    doc = export_json(reg)
    for name in ("stream_batches_total", "stream_edges_ingested_total",
                 "walk_hops_total", "walks_emitted_total", "replay_seconds",
                 "window_edges_active", "window_occupancy", "window_t_now"):
        assert name in doc["metrics"], name
    ingested = reg.value("stream_edges_ingested_total",
                         labels={"driver": "device"})
    assert ingested == int(np.asarray(stats.ingested)[-1])
    assert reg.value("stream_batches_total",
                     labels={"driver": "device"}) == 3
    assert reg.value("window_edges_active") == int(
        np.asarray(stats.edges_active)[-1])
    assert reg.value("walk_hops_total", labels={"source": "replay"}) > 0


def test_replay_device_single_sync_per_batch(monkeypatch):
    """The probe flush rides the replay's one existing host sync: the
    instrumented driver makes exactly as many explicit
    ``block_until_ready`` calls as the uninstrumented one (one per
    ``replay_device``), regardless of ``probes``."""
    g = powerlaw_temporal_graph(100, 2000, seed=5)
    wcfg = WalkConfig(num_walks=128, max_length=8, start_mode="nodes")
    counts = {}
    orig = jax.block_until_ready

    for probes in (False, True):
        eng = StreamingEngine(_engine_cfg(), batch_capacity=1024,
                              registry=new_registry(), probes=probes)
        calls = []
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: calls.append(1) or orig(x))
        try:
            eng.replay_device(chronological_batches(g, 3), wcfg)
        finally:
            monkeypatch.setattr(jax, "block_until_ready", orig)
        counts[probes] = len(calls)

    assert counts[True] == counts[False] == 1, counts


def test_unified_export_after_replay_and_serve(tmp_path):
    """Acceptance check: one registry, one ``export_json`` after a device
    replay AND a serve drain yields ingest/window/dispatch/latency metrics
    in a single schema-validated document, plus a valid health dump."""
    reg = new_registry()
    g = powerlaw_temporal_graph(100, 3000, seed=11)

    eng = StreamingEngine(_engine_cfg(), batch_capacity=1024, registry=reg)
    batches = list(chronological_batches(g, 4))
    eng.replay_device(batches[:3],
                      WalkConfig(num_walks=64, max_length=8,
                                 start_mode="nodes"))
    eng.ingest_batch(*batches[3])            # host-driver ingest path

    svc = WalkService(_engine_cfg(), _serve_cfg(), registry=reg)
    for bs, bd, bt in chronological_batches(g, 3):
        svc.ingest(bs, bd, bt)
    tickets = [svc.submit(WalkQuery(start_nodes=(1, 30, 60), max_length=8,
                                    seed=i), strict=True) for i in range(2)]
    # an oversize query is dropped (not queued) and lands in drops_total
    assert svc.submit(WalkQuery(start_nodes=tuple(range(100)),
                                max_length=8, seed=9)) is None
    while svc.pending_count:
        svc.step()
    assert all(svc.poll(t) is not None for t in tickets)

    doc = export_json(reg)
    for name in ("stream_batches_total", "stream_edges_ingested_total",
                 "window_occupancy", "walks_dispatched_total",
                 "serve_submitted_total", "serve_completed_total",
                 "serve_latency_seconds", "stage_seconds", "drops_total"):
        assert name in doc["metrics"], name
    # both producers landed in the same families, split by label
    drivers = {s["labels"].get("driver")
               for s in doc["metrics"]["stream_batches_total"]["series"]}
    assert {"device", "host"} <= drivers

    health = health_snapshot(reg, service=svc)
    assert validate_health(health) is health
    assert health["serving"]["completed"] == 2
    assert health["ingest"]["batches"] == 4   # 3 replayed + 1 host ingest
    assert health["dispatch"]["walks_by_path"].get("serve", 0) > 0

    path = tmp_path / "health.json"
    dump_health(str(path), reg, service=svc)
    validate_health(json.loads(path.read_text()))
