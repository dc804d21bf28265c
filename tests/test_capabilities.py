"""The capability matrix: every (path, bias, lane-features, sharded,
tables) combination either runs or refuses through the single chokepoint
``walk_engine.check_capabilities`` (DESIGN.md §17).

This used to be four scattered refusal sites (the engine's fused/node2vec
inline checks, the serving constructor, the sharded walker); they now all
delegate here, so this sweep is the one place the support matrix is
pinned. An independent predicate (``_expect_supported``) re-derives what
*should* run; the test asserts behavior matches for the full product
space, and that every refusal carries the uniform message prefix.
"""
import dataclasses
import importlib.util
import itertools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs.base import (
    EngineConfig,
    SamplerConfig,
    SchedulerConfig,
    ServeConfig,
    WindowConfig,
)
from repro.core.streaming import StreamingEngine
from repro.core.temporal_index import IndexViews
from repro.core.walk_engine import (
    LaneFeatures,
    check_capabilities,
    index_views,
)
from repro.obs.registry import MetricsRegistry
from repro.serve import WalkService

PATHS = ("fullwalk", "grouped", "tiled", "fused")
BIASES = ("uniform", "linear", "exponential", "table")
_CAP_PREFIX = "unsupported sampler capability: "


def _expect_supported(scfg, path, lanes, sharded, have_tables):
    """Independent statement of the support matrix."""
    n2v_cfg = scfg.node2vec_p != 1.0 or scfg.node2vec_q != 1.0
    if scfg.bias == "table":
        if scfg.mode != "index" or sharded or not have_tables:
            return False
        if path in ("tiled", "fused"):
            return False
    if n2v_cfg:
        if sharded or lanes is not None or path in ("tiled", "fused"):
            return False
    if lanes is not None:
        if scfg.mode != "index" or path == "tiled":
            return False
        if lanes.table and (sharded or not have_tables or path == "fused"):
            return False
        if lanes.second_order and (sharded or path == "fused"):
            return False
    return True


def _sweep():
    lane_opts = (None, LaneFeatures(), LaneFeatures(table=True),
                 LaneFeatures(second_order=True),
                 LaneFeatures(table=True, second_order=True))
    for mode in ("index", "weight"):
        for bias, path, lanes, sharded, have_tables in itertools.product(
                BIASES, PATHS, lane_opts, (False, True), (False, True)):
            for n2v in (1.0, 2.0):
                yield (SamplerConfig(mode=mode, bias=bias, node2vec_p=n2v),
                       path, lanes, sharded, have_tables)


def test_capability_matrix_exhaustive():
    checked = 0
    for scfg, path, lanes, sharded, have_tables in _sweep():
        expect = _expect_supported(scfg, path, lanes, sharded, have_tables)
        try:
            check_capabilities(scfg, path, lanes, sharded=sharded,
                               have_tables=have_tables)
            ran = True
            msg = None
        except ValueError as e:
            ran = False
            msg = str(e)
        combo = (scfg.mode, scfg.bias, scfg.node2vec_p, path, lanes,
                 sharded, have_tables)
        assert ran == expect, (combo, msg)
        if not ran:
            assert msg.startswith(_CAP_PREFIX), combo
        checked += 1
    # the product space really was swept
    assert checked == 2 * 4 * 4 * 5 * 2 * 2 * 2


def test_unknown_bias_refused():
    with pytest.raises(ValueError, match="unknown bias"):
        check_capabilities(SamplerConfig(mode="index", bias="nope"),
                           "grouped")
    with pytest.raises(ValueError, match="start-edge bias"):
        check_capabilities(
            SamplerConfig(mode="index", start_bias="table"), "grouped")


def test_pinned_messages():
    """Substrings downstream callers and older tests grep for."""
    with pytest.raises(ValueError, match="fused"):
        check_capabilities(SamplerConfig(mode="index", node2vec_p=2.0),
                           "fused")
    with pytest.raises(ValueError, match="node2vec"):
        check_capabilities(SamplerConfig(mode="index", node2vec_p=2.0),
                           "grouped", sharded=True)
    with pytest.raises(ValueError, match="index"):
        check_capabilities(SamplerConfig(mode="weight"), "grouped",
                           LaneFeatures())
    with pytest.raises(ValueError, match="table"):
        check_capabilities(
            SamplerConfig(mode="index", bias="table"), "grouped",
            have_tables=False)


# ---------------------------------------------------------------------------
# Index views (DESIGN.md §3): what each engine builds, derived by
# walk_engine.index_views from the sampler mode, the path and whether a
# second-order draw can reach the index
# ---------------------------------------------------------------------------

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _expect_views(scfg, path, service):
    """Independent statement of the rule: prefixes for weight draws and
    the Pallas kernels, the adjacency view for the node2vec β probe."""
    n2v = scfg.node2vec_p != 1.0 or scfg.node2vec_q != 1.0
    return (scfg.mode == "weight" or path in ("tiled", "fused"),
            n2v or service)


def test_index_views_rule_exhaustive():
    checked = 0
    for mode, bias, path, n2v, service in itertools.product(
            ("index", "weight"), BIASES, PATHS, (1.0, 2.0), (False, True)):
        for key in ("node2vec_p", "node2vec_q"):
            scfg = SamplerConfig(mode=mode, bias=bias, **{key: n2v})
            views = index_views(scfg, path, second_order=service)
            prefixes, adjacency = _expect_views(scfg, path, service)
            assert views == IndexViews(prefixes, adjacency), (scfg, path)
            assert views.edge_columns == 4 + 4 * prefixes + 2 * adjacency
            checked += 1
    assert checked == 2 * 4 * 4 * 2 * 2 * 2


def _tiny_window():
    return WindowConfig(duration=600, edge_capacity=1024, node_capacity=64)


def _one_batch():
    rng = np.random.default_rng(3)
    return (rng.integers(0, 64, 200).astype(np.int32),
            rng.integers(0, 64, 200).astype(np.int32),
            np.sort(rng.integers(0, 500, 200)).astype(np.int32))


@pytest.mark.parametrize("mode,path,n2v", [
    (mode, path, n2v)
    for mode, path, n2v in itertools.product(("index", "weight"), PATHS,
                                             (1.0, 2.0))
    if not (n2v != 1.0 and path in ("tiled", "fused"))])
def test_engine_index_edge_columns_gauge(mode, path, n2v):
    """The engine's ingest builds the derived views, and the gauge says how
    many edge-sized columns its index wrote."""
    scfg = SamplerConfig(mode=mode, node2vec_q=n2v)
    cfg = EngineConfig(window=_tiny_window(), sampler=scfg,
                       scheduler=SchedulerConfig(path=path))
    eng = StreamingEngine(cfg, batch_capacity=256,
                          registry=MetricsRegistry())
    eng.ingest_batch(*_one_batch())
    prefixes, adjacency = _expect_views(scfg, path, False)
    assert eng.state.index.views == IndexViews(prefixes, adjacency)
    assert eng.registry.value("index_edge_columns") == \
        4 + 4 * prefixes + 2 * adjacency


@pytest.mark.parametrize("path,columns", [("grouped", 6), ("tiled", 6),
                                          ("fused", 10)])
def test_service_index_edge_columns_gauge(path, columns):
    """A service may admit second-order lanes with any query, so its
    snapshots keep the adjacency view; it serves 'tiled' on 'grouped'."""
    cfg = EngineConfig(window=_tiny_window(),
                       sampler=SamplerConfig(mode="index"),
                       scheduler=SchedulerConfig(path=path))
    svc = WalkService(cfg, ServeConfig(), batch_capacity=256,
                      registry=MetricsRegistry())
    svc.ingest(*_one_batch())
    assert svc.snapshots.current.index.views.adjacency
    assert svc.registry.value("index_edge_columns") == columns


def _cell_engine_configs():
    """The engine configuration of every benchmark cell, made as the
    benchmark's drivers make it, on a window a test can hold."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    sys.path.insert(0, str(BENCH))
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_deploy_views", BENCH / "deploy.py")
        deploy = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(deploy)
    finally:
        sys.path.remove(str(BENCH))
    configs = {}
    for cell in bench["workloads"]:
        cfg = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                         .read_text())
        traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                             .read_text())
        extra = ({"start_bias": traffic["start_bias"]}
                 if "start_bias" in traffic else {})
        ecfg = deploy.engine_config(cfg, 2 ** 31 + 5,
                                    bias=cfg["sampler"]["bias"], **extra)
        configs[cell["name"]] = dataclasses.replace(ecfg,
                                                    window=_tiny_window())
    return configs


def test_benchmark_cells_build_the_core_alone():
    """Every cell's engine (index mode, grouped path, no second-order
    draws) rebuilds four edge-sized columns, not the full build's ten."""
    configs = _cell_engine_configs()
    assert len(configs) == 4
    for name, cfg in configs.items():
        eng = StreamingEngine(cfg, batch_capacity=256,
                              registry=MetricsRegistry())
        eng.ingest_batch(*_one_batch())
        assert eng.state.index.views == IndexViews(False, False), name
        assert eng.registry.value("index_edge_columns") == 4, name
