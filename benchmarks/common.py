"""Shared benchmark utilities. All timings are host wall-clock on the
backend JAX runs on; CPU timings are relative claims only (DESIGN.md §9).
Device numbers come only from chip runs (``chip_smoke.py``, PERF.md).

Every suite's ``emit()`` rows are also accumulated into a per-suite
record (``begin_suite``/``end_suite``, driven by ``benchmarks.run``);
with ``--emit-json`` each suite writes a schema-validated
``BENCH_<suite>.json`` in the shared ``tempest-bench/v1`` layout
(obs/export.py, DESIGN.md §16) — one schema for every artifact instead
of per-suite ad-hoc payloads.
"""
from __future__ import annotations

import json
import math
import os
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from repro.obs.export import bench_doc

from repro.configs.base import (
    EngineConfig,
    SamplerConfig,
    SchedulerConfig,
    WalkConfig,
    WindowConfig,
)
from repro.core.edge_store import store_from_arrays
from repro.core.temporal_index import build_index
from repro.data.synthetic import powerlaw_temporal_graph


# Toggled by ``benchmarks.run`` flags: --emit-json persists machine-readable
# BENCH_*.json artifacts next to the CSV stream; --small shrinks suite
# configs to nightly-CI scale.
EMIT_JSON = False
SMALL = False

# Active suite record (one per ``begin_suite``/``end_suite`` bracket):
# emit() rows + any write_json() detail payloads land here.
_SUITE: Optional[str] = None
_SUITE_ROWS: List[dict] = []
_SUITE_EXTRAS: Dict[str, dict] = {}


def begin_suite(name: str) -> None:
    """Open a suite record; subsequent ``emit``/``write_json`` calls
    accumulate into it until ``end_suite``."""
    global _SUITE, _SUITE_ROWS, _SUITE_EXTRAS
    _SUITE = name
    _SUITE_ROWS = []
    _SUITE_EXTRAS = {}


def end_suite() -> str | None:
    """Close the active suite; with --emit-json write its accumulated
    rows (+ detail payloads) as a schema-validated ``BENCH_<suite>.json``
    in the shared ``tempest-bench/v1`` layout."""
    global _SUITE, _SUITE_ROWS, _SUITE_EXTRAS
    if _SUITE is None:
        return None
    name, rows, extras = _SUITE, _SUITE_ROWS, _SUITE_EXTRAS
    _SUITE, _SUITE_ROWS, _SUITE_EXTRAS = None, [], {}
    if not EMIT_JSON:
        return None
    doc = bench_doc(name, rows, results=extras or None)
    return _dump_json(name, doc)


def _dump_json(name: str, doc: dict) -> str:
    path = os.path.join(os.getcwd(), f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}", flush=True)
    return path


def write_json(name: str, payload: dict) -> str | None:
    """Persist a suite's detail payload when --emit-json is active.

    The payload is folded into the active suite record (so the suite's
    ``BENCH_<suite>.json`` carries it under ``results``) and, for
    backwards compatibility with existing artifact names, also written
    standalone as ``BENCH_<name>.json`` — wrapped in the same
    ``tempest-bench/v1`` schema with the rows emitted so far.
    """
    if _SUITE is not None:
        _SUITE_EXTRAS[name] = payload
    if not EMIT_JSON:
        return None
    doc = bench_doc(name, list(_SUITE_ROWS), results={name: payload})
    return _dump_json(name, doc)


def timeit(fn: Callable, *args, repeats: int = 5, warmup: int = 1,
           **kwargs) -> tuple:
    for _ in range(warmup):
        out = fn(*args, **kwargs)
        jax.block_until_ready(jax.tree.leaves(out)[0]) if jax.tree.leaves(out) else None
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        leaves = jax.tree.leaves(out)
        if leaves:
            jax.block_until_ready(leaves[0])
        times.append(time.perf_counter() - t0)
    return np.mean(times), np.std(times), out


def emit(name: str, us_per_call: float, derived: str = ""):
    us = float(us_per_call)
    if not math.isfinite(us):
        us = -1.0          # schema wants finite numbers; -1 marks "n/a"
    if _SUITE is not None:
        _SUITE_ROWS.append(
            {"name": name, "us_per_call": us, "derived": derived})
    print(f"{name},{us_per_call:.1f},{derived}")


def make_bench_index(num_nodes=2048, num_edges=60000, skew=1.2, seed=0,
                     edge_capacity=65536, ts_groups=None):
    g = powerlaw_temporal_graph(num_nodes, num_edges, skew=skew, seed=seed,
                                ts_groups=ts_groups)
    store = store_from_arrays(g.src, g.dst, g.ts,
                              edge_capacity=edge_capacity,
                              node_capacity=num_nodes)
    return g, build_index(store, num_nodes)


def steps_per_sec(result, elapsed_s: float) -> float:
    """M-steps/s from walk lengths (paper Table 2 metric)."""
    hops = float(np.sum(np.asarray(result.lengths) - 1).clip(min=0))
    return hops / elapsed_s / 1e6
