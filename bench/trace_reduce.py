"""From a profiler trace to device time per layer, busy time and idle gaps.

The trace is the Perfetto JSON that ``jax.profiler`` writes beside its
xplane file. Device operations are the complete events on the "XLA Ops"
line of each ``/device:TPU:n`` process; each carries ``source_stack``,
the file:line frames of the JAX code that emitted it. An operation
belongs to the first layer of ``LAYERS`` that one of its frames names;
one with no such frame is unattributed.

Nested operations (a ``while`` and the operations of its body) cover the
same time, so time is counted as a union of intervals: each stretch of
time in which some operation runs goes to the first layer, in the order
of ``LAYERS`` and then "unattributed", among the operations covering it.
"""
from __future__ import annotations

import gzip
import json
import re
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional

# (layer, frames that place an operation in it), in order of precedence
LAYERS = (
    ("walks", re.compile(r"repro/(core/(walk_engine|scheduler|samplers)"
                         r"\.py|kernels/)")),
    ("index", re.compile(r"repro/core/temporal_index\.py")),
    ("advance", re.compile(r"repro/core/window\.py")),
)
UNATTRIBUTED = "unattributed"
ORDER = tuple(name for name, _ in LAYERS) + (UNATTRIBUTED,)


class Op(NamedTuple):
    device: str
    name: str
    start: float            # microseconds, on the trace's clock
    end: float
    layer: str


class Span(NamedTuple):
    name: str
    start: float
    end: float


def layer_of(source_stack: str) -> str:
    for name, pattern in LAYERS:
        if pattern.search(source_stack or ""):
            return name
    return UNATTRIBUTED


def load(path: Path):
    """(device ops, host spans) of a Perfetto JSON trace (.json or .gz)."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    return parse(events)


def parse(events: List[dict]):
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e.get("tid"))] = e["args"]["name"]
    ops, spans = [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e["pid"], "")
        start, end = float(e["ts"]), float(e["ts"]) + float(e["dur"])
        if proc.startswith("/device:"):
            if threads.get((e["pid"], e.get("tid"))) == "XLA Ops":
                args = e.get("args") or {}
                ops.append(Op(proc, e["name"], start, end,
                              layer_of(args.get("source_stack", ""))))
        elif proc.startswith("/host:"):
            # an annotation "a:b" is written as name "b", long_name "a:b"
            name = (e.get("args") or {}).get("long_name", e["name"])
            spans.append(Span(name, start, end))
    return ops, spans


def _clip(ops: List[Op], lo: float, hi: float) -> List[Op]:
    return [o._replace(start=max(o.start, lo), end=min(o.end, hi))
            for o in ops if o.end > lo and o.start < hi]


def layer_seconds(ops: List[Op]) -> Dict[str, float]:
    """Seconds of each layer (and ``busy``), summed over devices, each
    stretch of time counted once under its first covering layer."""
    out = {name: 0.0 for name in ORDER}
    for device in sorted({o.device for o in ops}):
        points = []
        for o in ops:
            if o.device == device and o.end > o.start:
                rank = ORDER.index(o.layer)
                points.append((o.start, 1, rank))
                points.append((o.end, -1, rank))
        points.sort()
        cover = [0] * len(ORDER)
        prev = None
        for t, step, rank in points:
            if prev is not None and t > prev:
                for r, c in enumerate(cover):
                    if c:
                        out[ORDER[r]] += (t - prev) * 1e-6
                        break
            cover[rank] += step
            prev = t
    out["busy"] = sum(out[name] for name in ORDER)
    return out


def busy_gaps(ops: List[Op], lo: float, hi: float) -> List[Span]:
    """Stretches of [lo, hi) in which no operation ran on any device."""
    ivs = sorted((o.start, o.end) for o in ops)
    gaps, at = [], lo
    for s, e in ivs:
        if s > at:
            gaps.append(Span("", at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        gaps.append(Span("", at, hi))
    return [g for g in gaps if g.end > g.start]


def _cover(s: Span, gap: Span) -> float:
    return min(s.end, gap.end) - max(s.start, gap.start)


def name_gap(gap: Span, spans: List[Span], prefixes=("bench:", "obs:")):
    """What the host was doing while the device waited: the innermost
    benchmark or program span that covers most of a gap, and after " / "
    the innermost other host event (the runtime's own, such as a dispatch
    or a transfer) that covers at least half of it, where there is one."""
    best, best_cover, best_len = "host (no span)", 0.0, float("inf")
    inner, inner_len = None, float("inf")
    for s in spans:
        if s.name == "bench:window":
            continue
        cover = _cover(s, gap)
        if not s.name.startswith(prefixes):
            if 2 * cover >= gap.end - gap.start > 0 \
                    and s.end - s.start < inner_len:
                inner, inner_len = s.name, s.end - s.start
            continue
        if cover > best_cover or (cover == best_cover and cover > 0
                                  and s.end - s.start < best_len):
            best, best_cover, best_len = s.name, cover, s.end - s.start
    return best if inner is None else f"{best} / {inner[:80]}"


class Reduction(NamedTuple):
    window_s: float
    busy_s: float           # averaged over devices
    layers: Dict[str, float]
    breakdown: dict


def reduce(ops: List[Op], spans: List[Span],
           window_span: str = "bench:window") -> Optional[Reduction]:
    """Everything a per-layer reader needs, within the benchmark's window
    span; None when the trace holds no such span or no device op."""
    win = [s for s in spans if s.name == window_span]
    if not win:
        return None
    lo, hi = win[0].start, win[0].end
    ops = _clip(ops, lo, hi)
    devices = sorted({o.device for o in ops})
    if not devices:
        return None
    layers = layer_seconds(ops)
    per_op: Dict[str, float] = {}
    for o in ops:
        key = f"{o.layer}:{o.name}"
        per_op[key] = per_op.get(key, 0.0) + (o.end - o.start) * 1e-6
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(busy_gaps(ops, lo, hi), key=lambda g: g.start - g.end)[:10]
    breakdown = {
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[name_gap(g, spans), (g.end - g.start) * 1e-6]
                      for g in gaps],
    }
    return Reduction(window_s=(hi - lo) * 1e-6,
                     busy_s=layers["busy"] / len(devices), layers=layers,
                     breakdown=breakdown)
