"""Tests of the scope reduction and of the readers of the program's own
counts, run by hand on the CPU with the rest of the benchmark's tests:

    JAX_PLATFORMS=cpu python -m pytest bench/
"""
from __future__ import annotations

import importlib.util
import os
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-test-cache-"))

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import obs_read  # noqa: E402
import scopes  # noqa: E402
import trace_reduce as tr  # noqa: E402
from test_bench import SMALL  # noqa: E402

NAMES = ("replay", "advance", "index", "walks", "start", "hop", "regroup",
         "pick")
WALK = "/x/src/repro/core/walk_engine.py:964:25\n"
INDEX = "/x/src/repro/core/temporal_index.py:120:5\n"
ADVANCE = "/x/src/repro/core/window.py:139:32\n"


def _events(ops, modules=((0, 10_000),)):
    """A device line of ops (name, start, dur, tf_op, source_stack) inside
    the given program runs, and a host line."""
    ev = [{"ph": "M", "pid": 3, "name": "process_name",
           "args": {"name": "/device:TPU:0"}},
          {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
           "args": {"name": "XLA Ops"}},
          {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
           "args": {"name": "XLA Modules"}},
          {"ph": "M", "pid": 7, "name": "process_name",
           "args": {"name": "/host:CPU"}}]
    for name, ts, dur, tf_op, stack in ops:
        args = {}
        if tf_op is not None:
            args["tf_op"] = tf_op
        if stack is not None:
            args["source_stack"] = stack
        ev.append({"ph": "X", "pid": 3, "tid": 3, "ts": ts, "dur": dur,
                   "name": name, "args": args})
    for k, (ts, end) in enumerate(modules):
        ev.append({"ph": "X", "pid": 3, "tid": 2, "ts": ts, "dur": end - ts,
                   "name": f"jit_program_{k}", "args": {}})
    return ev


R = "jit(replay_scan_probed)/replay/while/body/"


def _replay_ops():
    return [
        ("while.1", 0, 100, "jit(replay_scan_probed)/replay/while:", None),
        ("sort", 10, 20, R + "jit(ingest_impl)/advance/sort:", ADVANCE),
        ("sort.2", 30, 15, R + "jit(_build_index_impl)/index/sort:", INDEX),
        ("reduce-window", 45, 5, "", None),             # bare: index
        ("while.2", 50, 40, "", WALK),                  # container: walks
        ("fusion.1", 55, 5, R + "walks/while/body/hop/regroup/sort:", WALK),
        ("fusion.2", 60, 20, R + "walks/while/body/hop/pick/gather:", WALK),
        ("fusion.3", 80, 5, R + "walks/while/cond/reduce_or:", WALK),
    ]


def _reduce(ops, lo=0, hi=1000, modules=((0, 10_000),)):
    return scopes.scope_seconds(scopes.parse(_events(ops, modules), NAMES),
                                lo, hi)


def test_scope_path_reads_known_names_only():
    assert scopes.scope_path(R + "walks/while/body/hop/pick/gather:",
                             NAMES) == ("replay", "walks", "hop", "pick")
    assert scopes.scope_path("jit(f)/reduce_max:", NAMES) == ()
    assert scopes.scope_path("", NAMES) is None
    # the op's own name is not a scope, even where it reads like one
    assert scopes.scope_path("jit(f)/index", NAMES) == ()


def test_nesting_innermost_precedence_and_inheritance():
    sc = _reduce(_replay_ops())
    s = sc.seconds
    assert s["replay"] == pytest.approx(100e-6)
    assert s["replay/advance"] == pytest.approx(20e-6)
    # the bare reduce-window follows the index sort: index
    assert s["replay/index"] == pytest.approx(20e-6)
    # the hop loop's while has no tf_op: it takes the path its body shares
    assert s["replay/walks"] == pytest.approx(40e-6)
    assert s["replay/walks/hop"] == pytest.approx(25e-6)
    assert s["replay/walks/hop/regroup"] == pytest.approx(5e-6)
    assert s["replay/walks/hop/pick"] == pytest.approx(20e-6)
    assert sc.busy_s == pytest.approx(100e-6)
    # the bare op (45..50) and the loop's own time (50..55, 85..90) are
    # inherited; the loop's condition (80..85) is as deep and later
    assert sc.by_origin["inherited"] == pytest.approx(15e-6)
    assert sc.by_origin["unscoped"] == 0.0
    assert sc.by_origin["scoped"] == pytest.approx(85e-6)


def test_clipping_to_the_window():
    sc = _reduce(_replay_ops(), lo=20, hi=65)
    assert sc.busy_s == pytest.approx(45e-6)
    assert sc.seconds["replay/advance"] == pytest.approx(10e-6)
    assert sc.seconds["replay/index"] == pytest.approx(20e-6)
    assert sc.seconds["replay/walks/hop/regroup"] == pytest.approx(5e-6)
    assert sc.seconds["replay/walks/hop/pick"] == pytest.approx(5e-6)
    assert sc.seconds["replay/walks"] == pytest.approx(15e-6)
    # the loop inherits from all of its body, not only the part inside
    assert sc.seconds["replay/walks/hop"] == pytest.approx(10e-6)


def test_bare_ops_inherit_within_their_program_only():
    ops = [("sort", 0, 10, "jit(a)/index/sort:", INDEX),
           ("reduce-window", 10, 5, "", None),          # program 0: index
           ("reduce-window.1", 20, 5, "", None),        # program 1: nothing
           ("copy", 25, 5, "jit(b)/copy:", None)]       # named, no scope
    sc = _reduce(ops, modules=((0, 15), (20, 30)))
    assert sc.seconds == {"index": pytest.approx(15e-6)}
    assert sc.by_origin["inherited"] == pytest.approx(5e-6)
    assert sc.by_origin["unscoped"] == pytest.approx(10e-6)
    assert sc.busy_s == pytest.approx(25e-6)


def test_differences_name_the_ops_that_move():
    moved = scopes.differences(
        scopes.parse(_events(_replay_ops()), NAMES), 0, 1000)
    # the replay's while (no layer file) stays unattributed in both; the
    # reduce-window moves from no layer to index
    assert set(moved) == {"unattributed->index"}
    assert moved["unattributed->index"]["ops"] == [
        ("reduce-window", pytest.approx(5e-6))]


def test_top_ops_and_program_spans():
    top = scopes.top_ops(scopes.parse(_events(_replay_ops()), NAMES), 0, 1000)
    assert top["replay/walks/hop/pick"] == [
        ["fusion.2", pytest.approx(20e-6), R + "walks/while/body/hop/pick/"
         "gather:", "scoped"]]
    assert top["replay/walks"][0][0] == "while.2"
    assert top["replay/walks"][0][3] == "inherited"
    gap = tr.Span("", 10, 30)
    spans = [tr.Span("bench:replay_call", 0, 100),
             tr.Span("obs:replay.stage", 5, 25),
             tr.Span("obs:replay.dispatch", 25, 40),
             tr.Span("PjitFunction", 12, 20)]
    assert scopes.program_span(gap, spans) == "obs:replay.stage"
    assert scopes.program_span(gap, spans[:1]) == ""


def test_window_counts_take_only_new_spans():
    before = {"counters": {"walk_hops_total": 10},
              "stages": {"replay.stage": (2, [0.5, 0.25])}}
    after = {"counters": {"walk_hops_total": 25, "jit_compiles_total": 1},
             "stages": {"replay.stage": (4, [0.5, 0.25, 0.1, 0.3]),
                        "replay.fetch": (1, [0.05])}}
    got = scopes.window_counts(before, after)
    assert got["counters"] == {"walk_hops_total": 15,
                               "jit_compiles_total": 1}
    assert got["stages"]["replay.stage"] == {
        "calls": 2, "total_s": pytest.approx(0.4), "max_s": 0.3}
    assert got["stages"]["replay.fetch"]["calls"] == 1


# ---------------------------------------------------------------------------
# readers of the program's own counts
# ---------------------------------------------------------------------------


def _reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class _Reading:
    def __init__(self, counts):
        self.counts = counts


READERS = ("walk_lane_util_pct.replay", "walk_lane_util_pct.walks",
           "host_ms_per_call.replay")


@pytest.fixture
def registry(monkeypatch):
    import repro.obs
    reg = repro.obs.new_registry()
    monkeypatch.setattr(repro.obs, "get_registry", lambda: reg)
    return reg


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_the_program_counts(name, registry):
    reader = _reader(name)
    assert reader.read(_Reading({"calls": 3, "batches": 6})) is None
    # what a program without lane-steps or replay spans leaves behind
    registry.inc("walk_hops_total", 50, labels={"source": "replay"})
    registry.observe("stage_seconds", 0.1, labels={"stage": "ingest_merge"})
    assert reader.read(_Reading({"calls": 3, "batches": 6})) is None


def test_readers_read_the_program_counts(registry):
    registry.inc("walk_hops_total", 30, labels={"source": "replay"})
    registry.inc("walk_lane_steps_total", 120, labels={"source": "replay"})
    for stage, v in (("replay.stage", 0.010), ("replay.fetch", 0.004),
                     ("replay.publish", 0.001), ("replay.sync", 9.0)):
        for _ in range(3):          # the set-up's call, then two timed
            registry.observe("stage_seconds", v, labels={"stage": stage})
    assert _reader("walk_lane_util_pct.walks").read(None) == 25.0
    host = _reader("host_ms_per_call.replay")
    assert host.read(_Reading({"calls": 2})) == pytest.approx(15.0)
    assert host.read(_Reading({"calls": 4})) is None    # too few spans
    assert obs_read.stage_tail("replay.stage", 2) == [0.010, 0.010]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_measure_small_cell(cell):
    """The tool end to end at a small size: the program's stage spans and
    counts over the window. The CPU backend writes no device line, so
    there is no scope split to read here."""
    line = scopes.measure(cell, 2 ** 31 + 91, 1.0, require_chip=False,
                          overrides=SMALL[cell])
    calls = line["counts"]["calls"]
    kind = "replay" if "batches" in line["counts"] else "walks"
    stages = {k for k in line["program"]["stages"]
              if k.startswith(kind + ".")}
    want = {"replay": {"replay.stage", "replay.dispatch", "replay.sync",
                       "replay.fetch", "replay.publish"},
            "walks": {"walks.dispatch", "walks.sync", "walks.fetch",
                      "walks.publish"}}[kind]
    assert stages == want
    assert all(line["program"]["stages"][s]["calls"] == calls
               for s in stages)
    c = line["program"]["counters"]
    assert 0 < c["walk_hops_total"] <= c["walk_lane_steps_total"]
    assert line["compiles"] == 0        # everything compiled in set-up
    assert "scope_s" not in line


def test_cpu_trace_names_the_program_spans(tmp_path):
    """A trace recorded here carries the program's stage spans, each with
    its call's sequence number."""
    import jax
    from repro.configs.base import (EngineConfig, SamplerConfig,
                                    SchedulerConfig, WalkConfig,
                                    WindowConfig)
    from repro.core.streaming import StreamingEngine
    from repro.data.synthetic import (chronological_batches,
                                      powerlaw_temporal_graph)
    from repro.obs import new_registry

    cfg = EngineConfig(window=WindowConfig(duration=4000, edge_capacity=4096,
                                           node_capacity=128),
                       sampler=SamplerConfig(mode="index"),
                       scheduler=SchedulerConfig(path="grouped"))
    eng = StreamingEngine(cfg, batch_capacity=1024, registry=new_registry())
    wcfg = WalkConfig(num_walks=128, max_length=8, start_mode="nodes")
    g = powerlaw_temporal_graph(100, 2000, seed=5)
    batches = list(chronological_batches(g, 4))
    eng.replay_device(batches[:2], wcfg)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        eng.replay_device(batches[2:], wcfg)
    jax.profiler.stop_trace()
    found = list(tmp_path.rglob("*.trace.json.gz"))
    _, spans = tr.load(found[0])
    names = [s.name for s in spans if s.name.startswith("obs:replay.")]
    assert names == ["obs:replay.stage", "obs:replay.dispatch",
                     "obs:replay.sync", "obs:replay.fetch",
                     "obs:replay.publish"]
    import gzip
    import json
    with gzip.open(found[0], "rt") as f:
        events = json.load(f)["traceEvents"]
    seqs = {e["args"]["seq"] for e in events
            if e.get("name", "").startswith("obs:replay.")}
    assert seqs == {"2"}
