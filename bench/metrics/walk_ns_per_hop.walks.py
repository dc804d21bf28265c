"""Device time of the walks per hop walked (hops as the program counts
them: the edges of every walk)."""


def read(r):
    hops = r.counts.get("hops", 0)
    s = r.trace.layers["walks"]
    return s / hops * 1e9 if hops and s > 0 else None
