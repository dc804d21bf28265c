"""Device time of the window advance (core/window.py) per ingested batch."""


def read(r):
    n = r.counts.get("batches", 0)
    s = r.trace.layers["advance"]
    return s / n * 1e3 if n and s > 0 else None
