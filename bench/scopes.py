#!/usr/bin/env python3
"""Device time by the program's own scopes, and the program's own counts,
over one measured window of a cell.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

Sets the cell up as ``bench/run.py`` does, measures one window under the
profiler and prints one JSON line: device seconds per scope path, the
file-attributed layer seconds of ``trace_reduce`` beside them and the
ops that move between the two, the longest ops of each scope, the idle
gaps of at least a millisecond with what the host did in each (as
``trace_reduce`` names it, and the program span that covers most of it),
and, from
the program's registry over the window, its stage spans (calls, total and
most seconds), the compiles and the hop loop's lane-steps, beside the
cell's end-to-end numbers with the profiler on. It checks nothing
against the reference; ``bench/run.py`` does.

The reduction: every device op carries ``tf_op``, its JAX name stack; the
names in it that are program scopes (``repro.obs.SCOPES``), outermost
first, are its scope path. Time is a union of intervals per device: each
stretch goes to the deepest path among the ops covering it. An op with an
empty ``tf_op`` inherits a path: a container (an op enclosing others,
such as a loop's ``while``) the longest common path of the scoped ops it
encloses, any other the path of the scoped op before it on its device in
the same program. Seconds are reported by path, sub-scopes included, and
split into directly scoped, inherited and unscoped.
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

GAP_S = 1e-3        # idle gaps at least this long are listed


class ScopedOp(NamedTuple):
    device: str
    program: int            # index of the program run the op belongs to
    name: str
    start: float            # microseconds, on the trace's clock
    end: float
    path: Optional[Tuple[str, ...]]    # None: no tf_op to read
    file_layer: str
    where: str              # tf_op, else the source line: for reading


def scope_path(tf_op: str, names: Sequence[str]) -> Optional[Tuple[str, ...]]:
    """The program scopes in a ``tf_op`` name stack, outermost first; the
    last component is the op itself. None for an empty stack."""
    if not tf_op:
        return None
    return tuple(c for c in tf_op.split("/")[:-1] if c in names)


def parse(events: List[dict], names: Sequence[str]) -> List[ScopedOp]:
    """The device ops of a Perfetto trace with their scope paths."""
    import trace_reduce
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e.get("tid"))] = e["args"]["name"]
    modules: Dict[str, List[Tuple[float, float]]] = {}
    raw = []
    for e in events:
        proc = procs.get(e.get("pid"), "")
        if e.get("ph") != "X" or not proc.startswith("/device:"):
            continue
        line = threads.get((e["pid"], e.get("tid")))
        start, end = float(e["ts"]), float(e["ts"]) + float(e["dur"])
        if line == "XLA Modules":
            modules.setdefault(proc, []).append((start, end))
        elif line == "XLA Ops":
            raw.append((proc, e, start, end))
    for runs in modules.values():
        runs.sort()
    ops = []
    for proc, e, start, end in raw:
        args = e.get("args") or {}
        runs = modules.get(proc, [])
        i = bisect.bisect_right(runs, (start, float("inf"))) - 1
        program = i if i >= 0 and runs[i][1] >= start else -1
        tf_op = args.get("tf_op", "")
        ops.append(ScopedOp(proc, program, e["name"], start, end,
                            scope_path(tf_op, names),
                            trace_reduce.layer_of(
                                args.get("source_stack", "")),
                            tf_op or args.get("source", "")))
    return ops


def _common(paths) -> Tuple[str, ...]:
    paths = list(paths)
    out = []
    for parts in zip(*paths):
        if any(p != parts[0] for p in parts):
            break
        out.append(parts[0])
    return tuple(out)


def inherit(ops: List[ScopedOp]) -> List[Tuple[ScopedOp, str]]:
    """Each op with its path filled in where its ``tf_op`` is empty, and
    how it got it: "scoped", "inherited" or "unscoped"."""
    out: List[Tuple[ScopedOp, str]] = []
    by_line: Dict[Tuple[str, int], List[ScopedOp]] = {}
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        by_line.setdefault((o.device, o.program), []).append(o)
    for line in by_line.values():
        starts = [o.start for o in line]
        last = None                      # the last directly scoped op
        for i, o in enumerate(line):
            if o.path is not None:
                if o.path:
                    last = o.path
                out.append((o, "scoped" if o.path else "unscoped"))
                continue
            inner = [p.path for p in
                     line[i + 1:bisect.bisect_left(starts, o.end)]
                     if p.path and p.end <= o.end]
            path = _common(inner) if inner else last
            out.append((o._replace(path=path or ()),
                        "inherited" if path else "unscoped"))
    return out


def _clip(ops: List[Tuple[ScopedOp, str]], lo: float, hi: float):
    """Ops (with how they got their path) cut to [lo, hi); paths are
    inherited before the cut, from the whole trace."""
    return [(o._replace(start=max(o.start, lo), end=min(o.end, hi)), how)
            for o, how in ops if o.end > lo and o.start < hi]


class Scopes(NamedTuple):
    seconds: Dict[str, float]     # by scope path, sub-scopes included
    by_origin: Dict[str, float]   # scoped / inherited / unscoped
    busy_s: float                 # union over every device


def scope_seconds(ops: List[ScopedOp], lo: float, hi: float) -> Scopes:
    """Device seconds within [lo, hi) by scope path: each stretch of time
    goes once to the deepest path among the ops covering it (the later
    starting one where two are as deep)."""
    leaf: Dict[Tuple[Tuple[str, ...], str], float] = {}
    for device in sorted({o.device for o in ops}):
        mine = _clip(inherit([o for o in ops if o.device == device]),
                     lo, hi)
        points = []
        for k, (o, _) in enumerate(mine):
            points.append((o.start, 1, k))
            points.append((o.end, -1, k))
        points.sort()
        active = set()
        prev = None
        for t, step, k in points:
            if active and t > prev:
                o, how = max((mine[j] for j in active),
                             key=lambda m: (len(m[0].path), m[0].start))
                leaf[(o.path, how)] = leaf.get((o.path, how), 0.0) \
                    + (t - prev) * 1e-6
            if step > 0:
                active.add(k)
            else:
                active.discard(k)
            prev = t
    seconds: Dict[str, float] = {}
    by_origin = {"scoped": 0.0, "inherited": 0.0, "unscoped": 0.0}
    for (path, how), s in leaf.items():
        by_origin[how] += s
        for depth in range(1, len(path) + 1):
            key = "/".join(path[:depth])
            seconds[key] = seconds.get(key, 0.0) + s
    return Scopes(seconds=dict(sorted(seconds.items())),
                  by_origin=by_origin, busy_s=sum(by_origin.values()))


def layer_of_path(path: Tuple[str, ...]) -> str:
    """The file-attributed layer (``trace_reduce.LAYERS``) a scope path
    stands for: its innermost advance, index or walks scope."""
    for name in reversed(path):
        if name in ("walks", "index", "advance"):
            return name
    return "unattributed"


def differences(ops: List[ScopedOp], lo: float, hi: float, top: int = 8):
    """Ops whose scope places them in another layer than their source
    files do: seconds (durations, not a union) by (file layer, scope
    layer), with the ops that make each up."""
    out: Dict[str, Dict[str, float]] = {}
    for o, _ in _clip(inherit(ops), lo, hi):
        scoped = layer_of_path(o.path)
        if scoped == o.file_layer:
            continue
        pair = out.setdefault(f"{o.file_layer}->{scoped}", {})
        pair[o.name] = pair.get(o.name, 0.0) + (o.end - o.start) * 1e-6
    return {k: {"seconds": sum(v.values()),
                "ops": sorted(v.items(), key=lambda kv: -kv[1])[:top]}
            for k, v in sorted(out.items())}


def top_ops(ops: List[ScopedOp], lo: float, hi: float, top: int = 5):
    """The longest ops (durations, not a union) of each scope path."""
    out: Dict[str, Dict[str, list]] = {}
    for o, how in _clip(inherit(ops), lo, hi):
        mine = out.setdefault("/".join(o.path) or "(none)", {})
        entry = mine.setdefault(o.name, [0.0, o.where[-80:], how])
        entry[0] += (o.end - o.start) * 1e-6
    return {k: [[name] + v for name, v in
                sorted(d.items(), key=lambda kv: -kv[1][0])[:top]]
            for k, d in sorted(out.items())}


def program_span(gap, spans) -> str:
    """The program's own (``obs:``) span that covers most of an idle gap,
    the shorter where two cover as much; "" where none covers it."""
    best, best_cover, best_len = "", 0.0, float("inf")
    for s in spans:
        if not s.name.startswith("obs:"):
            continue
        cover = min(s.end, gap.end) - max(s.start, gap.start)
        if cover > best_cover or (cover == best_cover and cover > 0
                                  and s.end - s.start < best_len):
            best, best_cover, best_len = s.name, cover, s.end - s.start
    return best


# ---------------------------------------------------------------------------
# The program's registry over the window
# ---------------------------------------------------------------------------


def registry_snapshot() -> dict:
    from repro.obs import get_registry
    reg = get_registry()
    snap = {"counters": {}, "stages": {}}
    for fam in reg.families():
        if fam.kind == "counter":
            snap["counters"][fam.name] = sum(
                s.value for s in fam.series.values())
        elif fam.name == "stage_seconds":
            for key, hist in fam.series.items():
                snap["stages"][dict(key)["stage"]] = (
                    hist.count, hist.reservoir.values())
    return snap


def window_counts(before: dict, after: dict) -> dict:
    """Counter increments and stage spans between two snapshots."""
    counters = {k: v - before["counters"].get(k, 0)
                for k, v in after["counters"].items()}
    stages = {}
    for stage, (count, values) in after["stages"].items():
        n = count - before["stages"].get(stage, (0, []))[0]
        if n > 0:
            new = values[-n:]
            stages[stage] = {"calls": n, "total_s": sum(new),
                             "max_s": max(new)}
    return {"counters": counters, "stages": stages}


def measure(name: str, seed: int, seconds: float, *,
            require_chip: bool = True, overrides=None) -> dict:
    """One traced window of a cell; returns the result line as a dict."""
    import run as harness
    import trace_reduce

    cell, config, traffic, _, _ = harness.load_cell(name)
    for part, value in (overrides or {}).items():
        {"config": config, "traffic": traffic}[part].update(value)
    import jax
    devices = harness.check_chips(cell) if require_chip else jax.devices()
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(harness.CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    sys.path.insert(0, str(harness.CHECKOUT / "src"))
    from repro.obs import SCOPES

    ctx = harness.Context(name, config, traffic, seed, False)
    driver = harness.load_module(BENCH / "drivers" /
                                 f"{traffic['driver']}.py")
    run = driver.Run(ctx)
    run.setup()
    before = registry_snapshot()
    tdir = tempfile.mkdtemp(prefix="bench-scopes-")
    jax.profiler.start_trace(tdir)
    try:
        with ctx.span("window"):
            run.measure(seconds)
    finally:
        jax.profiler.stop_trace()
    program = window_counts(before, registry_snapshot())
    found = list(Path(tdir).rglob("*.trace.json.gz"))
    with gzip.open(found[0], "rt") as f:
        events = json.load(f)["traceEvents"]
    shutil.rmtree(tdir, ignore_errors=True)
    counts = run.counts()
    line = {"workload": name, "seed": seed,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind},
            "end_to_end": run.end_to_end(), "counts": counts,
            "program": program,
            "compiles": program["counters"].get("jit_compiles_total", 0)}

    ops, spans = trace_reduce.parse(events)
    win = [s for s in spans if s.name == "bench:window"][0]
    red = trace_reduce.reduce(ops, spans)
    if red is None:                     # no device line: nothing to split
        return line
    scoped = parse(events, SCOPES)
    sc = scope_seconds(scoped, win.start, win.end)
    in_window = [o for o in ops if o.end > win.start and o.start < win.end]
    gaps = [g for g in trace_reduce.busy_gaps(in_window, win.start, win.end)
            if (g.end - g.start) * 1e-6 >= GAP_S]
    line.update(
        window_s=red.window_s, busy_s=red.busy_s,
        scope_s=sc.seconds, origin_s=sc.by_origin,
        layers_s={k: red.layers[k] for k in trace_reduce.ORDER},
        moved=differences(scoped, win.start, win.end),
        top_ops=top_ops(scoped, win.start, win.end),
        idle_gaps=[[trace_reduce.name_gap(g, spans), program_span(g, spans),
                    (g.end - g.start) * 1e-6] for g in gaps])

    c = program["counters"]
    hops = c.get("walk_hops_total", 0)
    lanes = c.get("walk_lane_steps_total", 0)
    walks_s = sc.seconds.get("walks", 0.0) + sc.seconds.get(
        "replay/walks", 0.0)
    derived = {}
    if "batches" in counts:
        n = counts["batches"]
        for layer in ("advance", "index", "walks"):
            derived[f"{layer}_scoped_ms_per_batch"] = \
                sc.seconds.get(f"replay/{layer}", 0.0) / n * 1e3
    if hops and lanes:
        derived.update(walk_lane_util_pct=hops / lanes * 100.0,
                       walk_scoped_ns_per_hop=walks_s / hops * 1e9,
                       walk_ns_per_lane_step=walks_s / lanes * 1e9)
    line["derived"] = derived
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import run as harness
    t0 = time.perf_counter()
    try:
        line = measure(args.workload, args.seed, args.seconds)
    except harness.NoChip as e:
        print(f"scopes: {e}; nothing was run", file=sys.stderr)
        return 2
    line["total_s"] = time.perf_counter() - t0
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
