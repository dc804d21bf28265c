"""Device time of the index rebuild (core/temporal_index.py) per batch."""


def read(r):
    n = r.counts.get("batches", 0)
    s = r.trace.layers["index"]
    return s / n * 1e3 if n and s > 0 else None
