"""Share of the chip's memory bandwidth that the window advance and index
rebuild reach: the least bytes one ingest must move (hbm.ingest_hbm_bytes,
from the cell's shapes) over the peak bandwidth, against their device time
per batch."""
import hbm


def read(r):
    n = r.counts.get("batches", 0)
    s = r.trace.layers["advance"] + r.trace.layers["index"]
    if not n or s <= 0:
        return None
    w = r.config["window"]
    need = hbm.ingest_hbm_bytes(w["edge_capacity"],
                                r.traffic["edges_per_batch"],
                                w["node_capacity"])
    return need / r.peaks["hbm_bytes_per_s"] / (s / n) * 100.0
