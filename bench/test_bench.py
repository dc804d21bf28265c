"""Tests of the benchmark's yardstick, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/

The trace reduction on hand-made events and on a trace recorded here; the
byte count and the peak table; the reference against plain loops; and
each cell driven end to end at a size a test can hold, with the chip look
skipped: as it stands (correct), as its control (not correct) and with the
timed path broken underneath (not correct).
"""
from __future__ import annotations

import os
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-test-cache-"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import hbm  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

WALK = "/x/src/repro/core/walk_engine.py:964:25\n/x/src/repro/core/streaming.py:1:1\n"
INDEX = ("/x/src/repro/core/temporal_index.py:120:5\n"
         "/x/src/repro/core/window.py:235:10\n")
ADVANCE = "/x/src/repro/core/window.py:139:32\n"


def _events(ops, spans):
    ev = [{"ph": "M", "pid": 3, "name": "process_name",
           "args": {"name": "/device:TPU:0"}},
          {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
           "args": {"name": "XLA Ops"}},
          {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
           "args": {"name": "XLA Modules"}},
          {"ph": "M", "pid": 7, "name": "process_name",
           "args": {"name": "/host:CPU"}}]
    for name, ts, dur, stack in ops:
        args = {"source_stack": stack} if stack is not None else {}
        ev.append({"ph": "X", "pid": 3, "tid": 3, "ts": ts, "dur": dur,
                   "name": name, "args": args})
    # a module event on another line: never an op
    ev.append({"ph": "X", "pid": 3, "tid": 2, "ts": 0, "dur": 1000,
               "name": "jit_everything", "args": {}})
    for name, ts, dur in spans:
        ev.append({"ph": "X", "pid": 7, "tid": 1, "ts": ts, "dur": dur,
                   "name": name})
    return ev


def test_layers_nesting_and_missing_sources():
    ops = [("sort", 10, 40, ADVANCE),            # advance 10..50
           ("fusion", 50, 30, INDEX),            # index 50..80
           ("reduce-window", 80, 10, None),      # unattributed 80..90
           ("while", 100, 50, WALK),             # walks 100..150
           ("body-fusion", 110, 20, ""),         # nested, no source
           ("body-search", 120, 20, INDEX)]      # nested, index frame
    spans = [("bench:window", 0, 200), ("bench:replay_call", 5, 190),
             ("bench:stage_chunk", 150, 45),
             ("TransferToDevice", 160, 20),     # under half of 150..200
             ("PjitFunction", 85, 30)]          # covers half of 90..100
    red = tr.reduce(*tr.parse(_events(ops, spans)))
    s = red.layers
    assert s["advance"] == pytest.approx(40e-6)
    assert s["index"] == pytest.approx(30e-6)
    assert s["unattributed"] == pytest.approx(10e-6)
    assert s["walks"] == pytest.approx(50e-6)       # body counted once
    assert red.busy_s == pytest.approx(130e-6)
    assert red.window_s == pytest.approx(200e-6)
    assert 1 - red.busy_s / red.window_s == pytest.approx(0.35)
    gaps = red.breakdown["idle_gaps"]
    # 0..10, 90..100, 150..200: the longest first, named by the
    # innermost benchmark span that covers it and the runtime's event
    # that covers at least half of it
    assert gaps[0] == ["bench:stage_chunk", pytest.approx(50e-6)]
    assert [g[1] for g in gaps] == pytest.approx([50e-6, 10e-6, 10e-6])
    assert [g[0] for g in gaps[1:]] == [
        "bench:replay_call", "bench:replay_call / PjitFunction"]
    names = [k for k, _ in red.breakdown["device_ops"]]
    assert names[0] == "walks:while"


def test_ops_outside_the_window_are_clipped():
    ops = [("early", 0, 100, ADVANCE), ("late", 150, 100, INDEX)]
    red = tr.reduce(*tr.parse(_events(ops, [("bench:window", 50, 150)])))
    assert red.layers["advance"] == pytest.approx(50e-6)
    assert red.layers["index"] == pytest.approx(50e-6)
    assert red.busy_s == pytest.approx(100e-6)


def test_no_window_or_no_device_reads_nothing():
    assert tr.reduce(*tr.parse(_events([("x", 0, 5, WALK)], []))) is None
    assert tr.reduce(*tr.parse(_events([], [("bench:window", 0, 9)]))) \
        is None


def test_precedence_of_frames():
    assert tr.layer_of(WALK + INDEX) == "walks"
    assert tr.layer_of(INDEX) == "index"
    assert tr.layer_of(ADVANCE) == "advance"
    assert tr.layer_of("/x/src/repro/kernels/fused_step.py:3:1") == "walks"
    assert tr.layer_of("/x/other.py:1:1") == tr.UNATTRIBUTED


def test_cpu_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x) * 2)
    x = jnp.arange(4096.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    found = list(tmp_path.rglob("*.trace.json.gz"))
    assert found
    ops, spans = tr.load(found[0])
    assert any(s.name == "bench:window" for s in spans)
    # the CPU backend has no device line: nothing to read, no zero
    assert not ops
    assert tr.reduce(ops, spans) is None


# ---------------------------------------------------------------------------
# bytes and peaks
# ---------------------------------------------------------------------------


def test_ingest_bytes_count_every_column():
    E, B, N = 1 << 10, 1 << 6, 1 << 5
    words = (3 * E + 3 * B + 1 + 3 * E          # old store, batch, new store
             + 6 * E + 4 * (E + 1)              # ten per-edge columns
             + (N + 2) + 3 * N)                 # four per-node columns
    assert hbm.ingest_hbm_bytes(E, B, N) == 4 * words
    assert len(hbm.EDGE_COLUMNS) == 10 and len(hbm.NODE_COLUMNS) == 4
    big = hbm.ingest_hbm_bytes(1 << 27, 1 << 20, 1 << 22)
    assert 8.6e9 < big < 8.8e9


def test_peaks_know_v5e_and_refuse_unknown():
    row = hbm.peaks("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in row["source"]
    with pytest.raises(KeyError):
        hbm.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------


def test_window_matches_a_plain_loop():
    rng = np.random.default_rng(0)
    n = 500
    src = rng.integers(0, 20, n).astype(np.int32)
    dst = rng.integers(0, 20, n).astype(np.int32)
    ts = np.sort(rng.integers(0, 100, n)).astype(np.int32)
    ts[::7] = ts[::7] // 2                        # out of order, with ties
    want = sorted((t, i) for i, t in enumerate(ts) if t >= 39)
    got = reference.Window(src, dst, ts, 99, 60, 1000)
    assert got.n == len(want)
    assert np.asarray(got.ts)[:got.n].tolist() == [t for t, _ in want]
    assert np.asarray(got.src)[:got.n].tolist() == [src[i] for _, i in want]
    clipped = reference.Window(src, dst, ts, 99, 60, 10)
    assert clipped.n == 10
    assert np.asarray(clipped.ts).tolist() == [t for t, _ in want[-10:]]
    store = (clipped.src, clipped.dst, clipped.ts, 10)
    assert reference.window_mismatch(store, clipped) == 0
    bent = (clipped.src.at[3].add(1), clipped.dst, clipped.ts, 10)
    assert reference.window_mismatch(bent, clipped) == 1
    assert reference.window_mismatch(store[:3] + (9,), clipped) == 1


def test_laws_and_ks():
    rng = np.random.default_rng(1)
    n = np.full(7, 6)
    m = np.arange(7)
    for code in range(3):
        f = np.exp(reference.log_cdf(np.full(7, code), m, n))
        assert f[0] == 0 and f[-1] == pytest.approx(1.0)
        assert np.all(np.diff(f) > 0)
    w = np.exp(np.arange(6.0))
    want = np.concatenate([[0], np.cumsum(w) / w.sum()])
    got = np.exp(reference.log_cdf(np.full(7, 2), m, n))
    assert got == pytest.approx(want)
    assert reference.ks_z(rng.random(20000)) < 1.95
    assert reference.ks_z(rng.random(20000) ** 2) > 10


def _small_window():
    rng = np.random.default_rng(2)
    n = 400
    src = rng.integers(0, 10, n).astype(np.int32)
    dst = rng.integers(0, 10, n).astype(np.int32)
    ts = np.sort(rng.integers(0, 200, n)).astype(np.int32)
    return src, dst, ts


def _walk(src, dst, ts, rng, start, length):
    """A walk drawn by the exponential law, by plain loops."""
    nodes, times = [start], [-1]
    while len(nodes) < length + 1:
        cand = [i for i in range(src.size)
                if src[i] == nodes[-1] and ts[i] > times[-1]]
        if not cand:
            break
        w = np.exp(np.arange(len(cand), dtype=float))
        k = cand[rng.choice(len(cand), p=w / w.sum())]
        nodes.append(dst[k])
        times.append(ts[k])
    return nodes, times


def test_check_walks_accepts_plain_walks_and_finds_faults():
    src, dst, ts = _small_window()
    idx = reference.WindowIndex(reference.Window(src, dst, ts, int(ts.max()),
                                                 10 ** 6, 1024))
    rng = np.random.default_rng(3)
    W, L = 300, 6
    nodes = np.full((W, L + 1), -1)
    times = np.full((W, L + 1), -1)
    lengths = np.zeros(W, int)
    for w in range(W):
        nd, tm = _walk(src, dst, ts, rng, int(rng.choice(np.unique(src))), L)
        nodes[w, :len(nd)], times[w, :len(tm)] = nd, tm
        times[w, 0] = ts[0] - 1
        lengths[w] = len(nd)

    def judge(n, t, ln):
        rep = reference.WalkReport()
        reference.check_walks(idx, rep, n, t, ln, start_mode="nodes",
                              bias=2, max_len=L,
                              rng=np.random.default_rng(4))
        return rep

    good = judge(nodes, times, lengths)
    assert good.wrong() == 0 and good.hops > 300
    bad = nodes.copy()
    bad[:, 1] = np.where(lengths > 1, (bad[:, 1] + 1) % 10, -1)
    assert judge(bad, times, lengths).invalid_hops > 0
    short = np.minimum(lengths, 1)
    assert judge(nodes, times, short).early_stops > 0


# ---------------------------------------------------------------------------
# each cell end to end, small, on the CPU
# ---------------------------------------------------------------------------

_WINDOW = {"edge_capacity": 1 << 13, "node_capacity": 1 << 9, "fill": 0.9}
SMALL = {
    "comment25lin.replay": {
        "config": {"window": _WINDOW,
                   "stream": {"nodes": 1 << 9, "zipf_s": 1.2,
                              "edges_per_tick": 2.0, "id_mult": 421,
                              "id_add": 5}},
        "traffic": {"edges_per_batch": 1 << 9, "walks_per_batch": 1 << 12}},
    "comment25lin.walks": {
        "config": {"window": _WINDOW,
                   "stream": {"nodes": 1 << 9, "zipf_s": 1.2,
                              "edges_per_tick": 2.0, "id_mult": 421,
                              "id_add": 5}},
        "traffic": {"bulk_edges_per_batch": 1 << 9, "walks_per_call": 1024,
                    "checked_walks_per_call": 256}},
}


@pytest.fixture
def run_small():
    def go(cell, control=False, seconds=1.0):
        return run.run_cell(cell, 2 ** 31 + 77, seconds, False, control,
                            require_chip=False, overrides=SMALL[cell])
    return go


def _failed(line):
    return [k for k, c in line["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_cell_as_it_stands_is_correct(cell, run_small):
    line = run_small(cell)
    assert line["correct"], line["checks"]
    assert line["run"]["checked_hops"] > 0
    assert set(line["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_is_not_correct(cell, run_small):
    line = run_small(cell, control=True)
    assert not line["correct"]
    assert "ks_z" in _failed(line)


@pytest.fixture
def fresh_jit():
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_ingest_that_leaves_the_state_unchanged(monkeypatch, fresh_jit,
                                                run_small):
    import repro.core.streaming as streaming
    monkeypatch.setattr(streaming, "ingest_impl",
                        lambda state, batch, *a, **k: state)
    line = run_small("comment25lin.replay")
    assert not line["correct"]
    assert {"window_mismatch", "stats_mismatch"} <= set(_failed(line))


def test_ingest_that_leaves_out_half_of_each_batch(monkeypatch, fresh_jit,
                                                   run_small):
    import repro.core.streaming as streaming
    real = streaming.ingest_impl

    def half(state, batch, *a, **k):
        return real(state, batch._replace(count=batch.count // 2), *a, **k)

    monkeypatch.setattr(streaming, "ingest_impl", half)
    line = run_small("comment25lin.replay")
    assert not line["correct"]
    assert "window_mismatch" in _failed(line)


def _altered(real):
    """A walk generator whose second node is changed where it is made."""
    def walks(*a, **k):
        res = real(*a, **k)
        col = res.nodes[:, 1]
        return res._replace(nodes=res.nodes.at[:, 1].set(
            (col + 1) * (col >= 0) + col * (col < 0)))
    return walks


@pytest.mark.parametrize("cell,module,name", [
    ("comment25lin.replay", "repro.core.streaming", "_generate_walks_impl"),
    ("comment25lin.walks", "repro.core.walk_engine", "_generate_walks_impl"),
])
def test_answer_altered_where_it_is_produced(cell, module, name,
                                             monkeypatch, fresh_jit,
                                             run_small):
    import importlib
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, name, _altered(getattr(mod, name)))
    line = run_small(cell)
    assert not line["correct"]
    assert "invalid_hops" in _failed(line) or "bad_starts" in _failed(line)


def test_walks_that_leave_out_half_of_the_batch(monkeypatch, fresh_jit,
                                                run_small):
    import repro.core.walk_engine as we
    real = we._generate_walks_impl

    def half(*a, **k):
        import jax.numpy as jnp
        res = real(*a, **k)
        W = res.lengths.shape[0]
        keep = jnp.arange(W) < W // 2
        return res._replace(lengths=res.lengths * keep,
                            nodes=jnp.where(keep[:, None], res.nodes, -1))
    monkeypatch.setattr(we, "_generate_walks_impl", half)
    line = run_small("comment25lin.walks")
    assert not line["correct"]
    assert "bad_starts" in _failed(line)


def test_no_program_no_result(tmp_path, capsys):
    """A directory that holds only the benchmark runs nothing."""
    import shutil
    import subprocess
    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    out = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         "comment25lin.replay", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_no_chip_no_result():
    out = __import__("subprocess").run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "comment25lin.replay",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 2
    assert out.stdout.strip() == ""
