"""What the program counts about itself, for the per-layer readers.

The program keeps its counters and stage spans in the process's registry
(``repro.obs``); the benchmark runs it in the same process, so a reader
can read them after the window. Each function returns None where the
program does not count the quantity asked for (a program that predates
it), so that a reader then reports nothing instead of raising.
"""
from __future__ import annotations

from typing import List, Optional


def _family(name: str):
    try:
        from repro.obs import get_registry
    except ImportError:
        return None
    fam = get_registry().get_family(name)
    return fam if fam is not None and fam.written else None


def counter_total(name: str) -> Optional[float]:
    """A counter summed over its label series, over the whole run."""
    fam = _family(name)
    if fam is None or fam.kind != "counter":
        return None
    return sum(s.value for s in fam.series.values())


def stage_tail(stage: str, n: int) -> Optional[List[float]]:
    """The seconds of the newest ``n`` spans of a program stage, oldest
    first; None when the program recorded fewer."""
    fam = _family("stage_seconds")
    if fam is None or n <= 0:
        return None
    for key, hist in fam.series.items():
        if dict(key) == {"stage": stage}:
            values = hist.reservoir.values()
            return values[-n:] if len(values) >= n else None
    return None


def lane_util_pct() -> Optional[float]:
    """Hops walked per lane the hop loop processed, in percent, over every
    walk call of the run (the set-up's warm-up call included): both
    counts come from the program, over the same calls."""
    hops = counter_total("walk_hops_total")
    lanes = counter_total("walk_lane_steps_total")
    if not hops or not lanes:
        return None
    return hops / lanes * 100.0
