"""The benchmark's ``comment25`` deployment (exponential bias) against its
plain reference, on the CPU at a size a test can hold.

``bench/run.py`` drives each cell with the chip look skipped, as the
benchmark's own tests do for ``comment25lin``: as it stands it reads
``correct``, and its control (linear walks where the deployment states
exponential) reads ``ks_z`` over its limit. An engine-level case adds
exponential start edges, which neither cell draws, judged by the same
reference.
"""
import importlib.util
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import SamplerConfig, SchedulerConfig, WalkConfig
from repro.core.edge_store import store_from_arrays
from repro.core.temporal_index import build_index
from repro.core.walk_engine import generate_walks
from repro.data.synthetic import powerlaw_temporal_graph

BENCH = Path(__file__).resolve().parents[1] / "bench"

_WINDOW = {"edge_capacity": 1 << 13, "node_capacity": 1 << 9, "fill": 0.9}
_STREAM = {"nodes": 1 << 9, "zipf_s": 1.2, "edges_per_tick": 2.0,
           "id_mult": 421, "id_add": 5}
# the sizes the benchmark's own tests give comment25lin's cells
SMALL = {
    "comment25.replay": {
        "config": {"window": _WINDOW, "stream": _STREAM},
        "traffic": {"edges_per_batch": 1 << 9, "walks_per_batch": 1 << 12}},
    "comment25.walks": {
        "config": {"window": _WINDOW, "stream": _STREAM},
        "traffic": {"bulk_edges_per_batch": 1 << 9, "walks_per_call": 1024,
                    "checked_walks_per_call": 256}},
}


def _load(name: str):
    spec = importlib.util.spec_from_file_location(f"bench_{name}",
                                                  BENCH / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def bench_run():
    """``bench/run.py``, with the process's compile-cache settings left as
    they were: the run enables the persistent cache where none is set."""
    saved = jax.config.jax_persistent_cache_min_compile_time_secs
    path = list(sys.path)
    yield _load("run")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved)
    sys.path[:] = path


@pytest.fixture
def run_small(bench_run, monkeypatch, tmp_path):
    # a cache directory named in the environment keeps the run from
    # pointing JAX's persistent cache into the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def go(cell, control=False):
        return bench_run.run_cell(cell, 2 ** 31 + 77, 0.5, False, control,
                                  require_chip=False, overrides=SMALL[cell])
    return go


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_cell_as_it_stands_is_correct(cell, run_small):
    line = run_small(cell)
    assert line["correct"], line["checks"]
    assert line["run"]["checked_hops"] > 0
    assert "hop.exponential" in line["run"]["ks_z_by_draw"]
    assert set(line["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_is_not_correct(cell, run_small):
    line = run_small(cell, control=True)
    assert not line["correct"]
    ks = line["checks"]["ks_z"]
    assert ks["value"] > ks["limit"], line["checks"]


def test_exponential_start_edges_follow_the_law():
    """Walks from exponential start edges with exponential hops, on the
    grouped path, judged by the plain reference."""
    reference = _load("reference")
    g = powerlaw_temporal_graph(128, 3000, seed=23)
    store = store_from_arrays(g.src, g.dst, g.ts, edge_capacity=4096,
                              node_capacity=128)
    index = build_index(store, 128)
    wcfg = WalkConfig(num_walks=4096, max_length=12, start_mode="edges")
    scfg = SamplerConfig(bias="exponential", mode="index",
                         start_bias="exponential")
    res = generate_walks(index, jax.random.PRNGKey(5), wcfg, scfg,
                         SchedulerConfig(path="grouped"))

    ts = np.asarray(g.ts)
    window = reference.Window(np.asarray(g.src), np.asarray(g.dst), ts,
                              int(ts.max()), int(ts.max() - ts.min()) + 1,
                              4096)
    report = reference.WalkReport()
    exp = reference.BIASES.index("exponential")
    reference.check_walks(reference.WindowIndex(window), report, res.nodes,
                          res.times, res.lengths, start_mode="edges",
                          bias=exp, start_bias=exp, max_len=wcfg.max_length,
                          rng=np.random.default_rng(9))
    assert report.wrong() == 0
    assert report.walks == wcfg.num_walks and report.hops > 0
    kinds = report.ks_by_kind()
    assert set(kinds) == {"start_edge.exponential", "hop.exponential"}
    # the statistic reads below 1.95 in 999 of 1000 runs where the law holds
    assert report.ks_z() < 1.95, kinds
