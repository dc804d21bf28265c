"""Program-owned instrumentation (DESIGN.md §16): the device scopes that
name the replay, advance, index and walk stages in the compiled HLO, the
hop loop's iteration count (``WalkResult.steps``) and the lanes it
processed (``WalkResult.lane_steps``), the replay probe that sums those
over batches, the host stage spans
of ``replay_device`` and ``sample_walks_donated``, and the compile
listener."""
import importlib.util
import re
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (
    EngineConfig,
    SamplerConfig,
    SchedulerConfig,
    WalkConfig,
    WindowConfig,
)
from repro.core.edge_store import make_batch, stack_batches
from repro.core.streaming import (
    StreamingEngine,
    ingest_and_walk,
    replay_scan_probed,
)
from repro.core.walk_engine import (
    alloc_walk_buffers,
    generate_walks,
    generate_walks_donated,
)
from repro.data.synthetic import chronological_batches, powerlaw_temporal_graph
from repro.obs import (
    RP_LANE_STEPS,
    RP_LOOP_STEPS,
    SCOPES,
    get_registry,
    new_registry,
    scope,
)
from repro.obs import tracing

N = 96
B = 512
REPLAY_STAGES = ("replay.stage", "replay.dispatch", "replay.sync",
                 "replay.fetch", "replay.publish")
WALK_STAGES = ("walks.dispatch", "walks.sync", "walks.fetch",
               "walks.publish")


def _cfg():
    return EngineConfig(
        window=WindowConfig(duration=2500, edge_capacity=2048,
                            node_capacity=N),
        sampler=SamplerConfig(bias="linear", mode="index"),
        scheduler=SchedulerConfig(path="grouped", regroup="bucket"))


def _graph():
    return powerlaw_temporal_graph(N, 2000, seed=17)


def _scope_paths(compiled_text: str) -> set:
    """Scope paths (the known scope names of an op_name, outermost first)
    of every op of a compiled HLO module."""
    paths = set()
    for name in re.findall(r'op_name="([^"]*)"', compiled_text):
        comps = [c for c in name.split("/")[:-1] if c in SCOPES]
        paths.add("/".join(comps))
    return paths


def _expected_loop_steps(lengths, start_mode: str, max_length: int) -> int:
    """Iterations of the hop loop, from the walks alone: it runs while any
    lane advanced on the previous iteration, so one iteration more than
    the longest walk's hops, and at most the hops left after the start."""
    lengths = np.asarray(lengths, np.int64)
    first = 2 if start_mode == "edges" else 1       # nodes the start wrote
    budget = max_length - 1 if start_mode == "edges" else max_length
    if not np.any(lengths >= first):
        return 0
    return int(min(lengths.max() - first + 1, budget))


# ---------------------------------------------------------------------------
# Device scopes
# ---------------------------------------------------------------------------


def test_scope_rejects_unknown_names():
    assert SCOPES == ("replay", "advance", "index", "walks", "start", "hop",
                      "regroup", "pick")
    with pytest.raises(ValueError, match="unknown device scope"):
        scope("walk")


@pytest.mark.parametrize("entry", ("replay_scan_probed",
                                   "generate_walks_donated"))
def test_scope_names_in_compiled_hlo(entry):
    """Every stage's scope reaches the compiled program's op_name metadata
    (what the profiler reports as an op's ``tf_op``), nested as the
    stages nest."""
    cfg = _cfg()
    wcfg = WalkConfig(num_walks=64, max_length=6, start_mode="nodes")
    eng = StreamingEngine(cfg, batch_capacity=B, registry=new_registry())
    batches = list(chronological_batches(_graph(), 4))[:2]
    if entry == "replay_scan_probed":
        lowered = replay_scan_probed.lower(
            eng.state, stack_batches(batches, B), jax.random.PRNGKey(0),
            N, wcfg, cfg.sampler, cfg.scheduler)
        prefix = "replay/"
        want = {"replay", "replay/advance", "replay/index", "replay/walks"}
    else:
        eng.replay_device(batches, wcfg)
        lowered = generate_walks_donated.lower(
            eng.state.index, jax.random.PRNGKey(0), alloc_walk_buffers(wcfg),
            wcfg, cfg.sampler, cfg.scheduler)
        prefix = ""
        want = {"walks"}
    want |= {prefix + p for p in ("walks/start", "walks/hop/regroup",
                                  "walks/hop/pick")}
    paths = _scope_paths(lowered.compile().as_text())
    assert want <= paths, sorted(want - paths)


# ---------------------------------------------------------------------------
# The hop loop's iteration count
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("start_mode", ("nodes", "edges"))
@pytest.mark.parametrize("regroup", ("bucket", "lexsort"))
@pytest.mark.parametrize("path", ("fullwalk", "grouped"))
def test_steps_match_lengths(small_index, path, regroup, start_mode):
    """``steps`` is the loop's iteration count, as the walks' lengths
    imply it; the hops walked never exceed the lanes it processed."""
    wcfg = WalkConfig(num_walks=256, max_length=12, start_mode=start_mode)
    scfg = SamplerConfig(bias="linear", mode="index")
    for seed in range(3):
        res = generate_walks(small_index, jax.random.PRNGKey(seed), wcfg,
                             scfg, SchedulerConfig(path=path,
                                                   regroup=regroup))
        lengths = np.asarray(res.lengths)
        steps = int(res.steps)
        assert steps == _expected_loop_steps(lengths, start_mode,
                                             wcfg.max_length)
        first = 2 if start_mode == "edges" else 1
        hops = int(np.maximum(lengths.astype(np.int64) - first, 0).sum())
        assert 0 < hops <= wcfg.num_walks * steps


def test_replay_loop_steps_probe_sums_batches():
    """The replay probe's lane-step and loop-step slots are the sums of
    every batch's lane-steps and loop iterations (the scan body replayed
    batch by batch with the same key chain); the flushed lane-steps are
    that sum, and the call is one ``walk_loop_steps`` observation of its
    iterations. At 512 walks the grouped-bucket loop narrows, so the
    lane-steps are below W × the loop iterations."""
    cfg = _cfg()
    wcfg = WalkConfig(num_walks=512, max_length=8, start_mode="nodes")
    batches = list(chronological_batches(_graph(), 4))

    reg = new_registry()
    eng = StreamingEngine(cfg, batch_capacity=B, registry=reg)
    _, sub = jax.random.split(eng.key)
    # the entry points donate their state: one copy each
    state, state0 = (jax.tree_util.tree_map(jnp.copy, eng.state)
                     for _ in range(2))
    eng.replay_device(batches, wcfg)

    k = sub
    expected, reported, lane_steps = 0, 0, 0
    for src, dst, ts in batches:
        k, s = jax.random.split(k)
        state, res = ingest_and_walk(state, make_batch(src, dst, ts, B), s,
                                     N, wcfg, cfg.sampler, cfg.scheduler)
        expected += _expected_loop_steps(res.lengths, "nodes",
                                         wcfg.max_length)
        reported += int(res.steps)
        lane_steps += int(res.lane_steps)
    assert reported == expected > 0
    assert 0 < lane_steps < wcfg.num_walks * expected
    np.testing.assert_array_equal(np.asarray(state.index.store.ts),
                                  np.asarray(eng.state.index.store.ts))

    pv = jax.device_get(replay_scan_probed(
        state0, stack_batches(batches, B), sub, N, wcfg, cfg.sampler,
        cfg.scheduler)[3])
    assert int(pv[RP_LANE_STEPS]) == lane_steps
    assert int(pv[RP_LOOP_STEPS]) == reported
    assert reg.histogram("walk_loop_steps").reservoir.values() == [reported]
    assert reg.value("walk_lane_steps_total",
                     labels={"source": "replay"}) == lane_steps
    assert reg.value("walk_hops_total", labels={"source": "replay"}) \
        <= lane_steps


def test_sample_walks_counts_lane_steps():
    """Each call adds its ``lane_steps``: at most W × its loop iterations,
    less where the grouped-bucket loop narrowed (512 walks)."""
    cfg = _cfg()
    wcfg = WalkConfig(num_walks=512, max_length=8, start_mode="edges")
    reg = new_registry()
    eng = StreamingEngine(cfg, batch_capacity=B, registry=reg)
    eng.replay_device(list(chronological_batches(_graph(), 4))[:2], wcfg)
    total = 0
    for _ in range(2):
        res = eng.sample_walks_donated(wcfg)
        steps = int(res.steps)
        total += int(res.lane_steps)
        assert 0 < int(res.lane_steps) <= wcfg.num_walks * steps
        assert steps == _expected_loop_steps(res.lengths, "edges",
                                             wcfg.max_length)
    assert reg.value("walk_lane_steps_total",
                     labels={"source": "donated"}) == total
    assert 0 < reg.value("walk_hops_total",
                         labels={"source": "donated"}) <= total


def test_sample_walks_observes_loop_steps():
    """Each ``sample_walks`` call is one ``walk_loop_steps`` observation:
    its ``WalkResult.steps``."""
    cfg = _cfg()
    wcfg = WalkConfig(num_walks=256, max_length=8, start_mode="edges")
    reg = new_registry()
    eng = StreamingEngine(cfg, batch_capacity=B, registry=reg, probes=False)
    eng.replay_device(list(chronological_batches(_graph(), 4))[:2], wcfg)
    assert reg.get_family("walk_loop_steps") is None
    steps = [int(eng.sample_walks(wcfg).steps) for _ in range(3)]
    assert min(steps) > 0
    assert reg.histogram("walk_loop_steps").reservoir.values() == steps


READERS = Path(__file__).resolve().parents[1] / "bench" / "metrics"


@pytest.mark.parametrize("cell", ("replay", "walks"))
def test_ms_per_step_readers(monkeypatch, cell):
    """The benchmark's ``walk_ms_per_step.*`` readers: the walks layer's
    device time over the newest ``calls`` observations of
    ``walk_loop_steps``; nothing where the program keeps no such
    histogram, or fewer observations than calls."""
    path = READERS / f"walk_ms_per_step.{cell}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{cell}", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    reg = new_registry()
    monkeypatch.setattr("repro.obs.get_registry", lambda: reg)
    reading = SimpleNamespace(trace=SimpleNamespace(layers={"walks": 0.6}),
                              counts={"calls": 2})
    assert reader.read(reading) is None
    reg.inc("walk_hops_total", 5)
    assert reader.read(reading) is None
    for steps in (1000, 10, 20):      # the warm-up call, then the window's
        reg.observe("walk_loop_steps", steps)
    assert reader.read(reading) == pytest.approx(0.6 / 30 * 1e3)
    reading.counts["calls"] = 4
    assert reader.read(reading) is None


# ---------------------------------------------------------------------------
# Host stage spans
# ---------------------------------------------------------------------------


class _Annotations:
    """Stands in for ``TraceAnnotation``: records (name, args) per span."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **kwargs):
        self.seen.append((name, kwargs))
        return _Null()


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_replay_device_records_each_stage_once(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(tracing, "TraceAnnotation", ann)
    reg = new_registry()
    eng = StreamingEngine(_cfg(), batch_capacity=B, registry=reg)
    wcfg = WalkConfig(num_walks=64, max_length=6, start_mode="nodes")
    batches = list(chronological_batches(_graph(), 4))
    eng.replay_device(batches[:2], wcfg)
    eng.replay_device(batches[2:], wcfg, return_walks=True)
    for stage in REPLAY_STAGES:
        hist = reg.histogram("stage_seconds", labels={"stage": stage})
        assert hist.count == 2 and hist.max >= 0
    names = [n for n, _ in ann.seen]
    assert names == ["obs:" + s for s in REPLAY_STAGES] * 2
    seqs = [a["seq"] for _, a in ann.seen]
    assert seqs[:5] == [seqs[0]] * 5 and seqs[5:] == [seqs[5]] * 5
    assert seqs[5] == seqs[0] + 1


def test_sample_walks_records_each_stage_once(monkeypatch):
    reg = new_registry()
    eng = StreamingEngine(_cfg(), batch_capacity=B, registry=reg)
    wcfg = WalkConfig(num_walks=64, max_length=6, start_mode="nodes")
    eng.replay_device(list(chronological_batches(_graph(), 4))[:2], wcfg)
    ann = _Annotations()
    monkeypatch.setattr(tracing, "TraceAnnotation", ann)
    for _ in range(3):
        eng.sample_walks_donated(wcfg)
    for stage in WALK_STAGES:
        assert reg.histogram("stage_seconds",
                             labels={"stage": stage}).count == 3
    assert [n for n, _ in ann.seen] == ["obs:" + s for s in WALK_STAGES] * 3
    seqs = [a["seq"] for _, a in ann.seen]
    assert len(set(seqs)) == 3
    assert all(len(set(seqs[i:i + 4])) == 1 for i in (0, 4, 8))


def test_histogram_keeps_running_max():
    reg = new_registry()
    for v in (0.5, 2.0, 1.0):
        reg.observe("x_seconds", v)
    assert reg.histogram("x_seconds").max == 2.0
    assert np.isnan(reg.histogram("y_seconds").max)


# ---------------------------------------------------------------------------
# Compile listener
# ---------------------------------------------------------------------------


def test_compile_listener_counts_fresh_jits():
    reg = get_registry()
    x = jnp.arange(7, dtype=jnp.int32)
    f = jax.jit(lambda v: v * 3 + 11)
    before = reg.sum_values("jit_compiles_total")
    jax.block_until_ready(f(x))
    assert reg.sum_values("jit_compiles_total") == before + 1
    jax.block_until_ready(f(x))
    assert reg.sum_values("jit_compiles_total") == before + 1
    seconds = sum(s.count for s in
                  reg.get_family("compile_seconds").series.values())
    assert seconds >= 1
