"""Device time of the walks per iteration of the hop loop: the walks
layer's time in the window over the iterations the loop ran in the
window's replay calls, the newest observations of the program's
``walk_loop_steps`` histogram (one per call, summed over its batches).
None where the program keeps no such histogram."""


def _loop_steps(n):
    """The sum of the newest ``n`` observations of ``walk_loop_steps``."""
    try:
        from repro.obs import get_registry
    except ImportError:
        return None
    fam = get_registry().get_family("walk_loop_steps")
    if fam is None or fam.kind != "histogram" or not fam.written or n <= 0:
        return None
    values = [v for h in fam.series.values() for v in h.reservoir.values()]
    return sum(values[-n:]) if len(values) >= n else None


def read(r):
    steps = _loop_steps(r.counts.get("calls", 0))
    s = r.trace.layers["walks"]
    return s / steps * 1e3 if steps and s > 0 else None
