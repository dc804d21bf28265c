"""The least device-memory traffic a window advance must cause, and the
chip's peaks."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"

# Columns of the window's dual index that an ingest writes, as the index
# holds them at this commit, with their lengths (E edges, N nodes).
EDGE_COLUMNS = (
    ("ns_order", 0), ("ns_src", 0), ("ns_dst", 0), ("ns_ts", 0),
    ("pexp", 1), ("plin", 1), ("pexp_store", 1), ("plin_store", 1),
    ("adj_order", 0), ("adj_dst", 0),
)
NODE_COLUMNS = (("node_starts", 2), ("node_group_counts", 0),
                ("node_tref", 0), ("node_tbase", 0))
STORE_COLUMNS = ("src", "dst", "ts")


def ingest_hbm_bytes(edge_capacity: int, batch: int, node_capacity: int,
                     word: int = 4) -> int:
    """Bytes one ingest has to move through device memory, whatever
    implements it:

    * read the old store: src, dst, ts, ``edge_capacity`` each;
    * read the batch: src, dst, ts, ``batch`` each, and its count;
    * write the new store: src, dst, ts;
    * write each index column the walks read: ns_order, ns_src, ns_dst,
      ns_ts, adj_order, adj_dst (E each), pexp, plin, pexp_store,
      plin_store (E + 1 each), node_starts (N + 2), node_group_counts,
      node_tref, node_tbase (N each).

    Every word is 4 bytes (int32 or float32). Passes that a sort makes
    over its data are the implementation's, not the work's, and do not
    count."""
    E, B, N = edge_capacity, batch, node_capacity
    store = len(STORE_COLUMNS) * E
    words = (store + len(STORE_COLUMNS) * B + 1 + store
             + sum(E + extra for _, extra in EDGE_COLUMNS)
             + sum(N + extra for _, extra in NODE_COLUMNS))
    return words * word


def peaks(device_kind: str) -> dict:
    """The chip's row of the peak table; an unknown chip is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add its row with a source")
    return table[device_kind]
