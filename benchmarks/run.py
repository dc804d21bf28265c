"""Benchmark runner: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines. Timings are wall-clock on
whatever backend JAX runs on; on the CPU they are relative claims only
(DESIGN.md §9). Device numbers come only from runs on the chip: the
engine's first one is ``chip_smoke.py`` at the repository root, and
PERF.md records what was measured there.

Usage:
    python -m benchmarks.run [--help] [--emit-json] [--small] [filter]

With a ``filter`` argument, only suites whose name contains the substring
run. ``--emit-json`` additionally persists machine-readable artifacts:
every suite's emit() rows are written as a schema-validated
``BENCH_<suite>.json`` in the shared ``tempest-bench/v1`` layout
(repro.obs.export.bench_doc, DESIGN.md §16); suites with extra detail
payloads (fused_walks -> BENCH_fused.json, fig7 -> BENCH_shard.json)
keep those artifact names, wrapped in the same schema. ``--small``
shrinks suite configs to nightly-CI scale. ``--help`` lists every suite
with its paper counterpart (the same set documented in
benchmarks/README.md).
"""
from __future__ import annotations

import sys
import traceback

# (suite name, module name, paper counterpart, one-line description)
SUITES = [
    ("table2_scheduler_ablation", "ablation_scheduler", "Table 2 / Fig. 8",
     "walks/s across scheduler paths incl. per-hop regroup old-vs-new "
     "(lexsort vs bucket) + modeled HBM traffic"),
    ("table3_tier_distribution", "tier_distribution", "Table 3",
     "dispatch-plane tier statistics over the (W, G) grid"),
    ("table4_ingestion_breakdown", "ingestion_breakdown", "Table 4",
     "per-batch ingestion stage breakdown + sort-vs-merge advance"),
    ("table5_tea_baseline", "baseline_tea", "Table 5",
     "Tempest vs TEA-style CPU temporal-walk baseline"),
    ("table6_validity_static", "validity_static", "Table 6",
     "causal validity: temporal engine vs static walker"),
    ("fig6_streaming_replay", "streaming_replay", "Fig. 6",
     "streaming replay latency/headroom; 3 drivers old-vs-new throughput"),
    ("fig7_scaling_edges", "scaling_edges", "Fig. 7",
     "ingest + walk cost vs active edge count; node-partitioned-window "
     "replay throughput vs shard count (DESIGN.md §12)"),
    ("fig8_9_param_sweeps", "param_sweeps", "Figs. 8-9",
     "tile_walks/tile_edges (block-dim analog) + solo_threshold sweeps"),
    ("fig10_window_sensitivity", "window_sensitivity", "Fig. 10",
     "window duration sweep: active edges, drops, per-batch cost"),
    ("fig11_memory_usage", "memory_usage", "Fig. 11",
     "device bytes across a stream (exactly constant) + accounting"),
    ("fused_walk_paths", "fused_walks", "Tables 2-3 (§14)",
     "walks/s across all five walk paths (fullwalk / grouped-lexsort / "
     "grouped-bucket / tiled / fused) + fused per-tier launch counts; "
     "--emit-json writes BENCH_fused.json"),
    ("serving_load", "serving_load", "— (§11, §13, §18)",
     "serving SLO harness: open-loop Poisson load curves (p50/p99 + "
     "goodput under deadlines) blocking vs overlapped async runtime, "
     "closed-loop drain throughput, and the sharded-service sweep vs "
     "shard count (--shards; needs "
     "XLA_FLAGS=--xla_force_host_platform_device_count=8 for multi-shard "
     "rows on CPU); --emit-json writes BENCH_serving.json"),
]


def _print_help() -> None:
    print(__doc__.strip())
    print("\nSuites:")
    width = max(len(n) for n, *_ in SUITES)
    for name, _mod, paper, desc in SUITES:
        print(f"  {name:<{width}}  {paper:<9} {desc}")


def main() -> None:
    if any(a in ("-h", "--help") for a in sys.argv[1:]):
        _print_help()
        return

    import importlib

    from benchmarks import common

    argv = sys.argv[1:]
    if "--emit-json" in argv:
        common.EMIT_JSON = True
        argv = [a for a in argv if a != "--emit-json"]
    if "--small" in argv:
        common.SMALL = True
        argv = [a for a in argv if a != "--small"]

    only = argv[0] if argv else None
    failed = []
    for name, mod_name, _paper, _desc in SUITES:
        if only and only not in name:
            continue
        print(f"# --- {name} ---", flush=True)
        common.begin_suite(name)
        try:
            importlib.import_module(f"benchmarks.{mod_name}").run()
            common.end_suite()
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)
    print("# all benchmark suites completed")


if __name__ == "__main__":
    main()
