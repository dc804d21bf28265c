"""Device time of the walks (core/walk_engine.py and below) per batch."""


def read(r):
    n = r.counts.get("batches", 0)
    s = r.trace.layers["walks"]
    return s / n * 1e3 if n and s > 0 else None
