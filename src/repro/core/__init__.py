"""Tempest-JAX core: the paper's contribution as composable JAX modules."""
from repro.core.edge_store import (
    EdgeBatch,
    EdgeStore,
    empty_store,
    make_batch,
    stack_batches,
    store_from_arrays,
)
from repro.core.temporal_index import TemporalIndex, build_index
from repro.core.walk_engine import (
    LaneParams,
    WalkBuffers,
    WalkResult,
    alloc_walk_buffers,
    generate_walk_lanes,
    generate_walks,
    generate_walks_donated,
)
from repro.core.window import (
    WindowState,
    ingest,
    ingest_nodonate,
    ingest_sort,
    init_window,
)

__all__ = [
    "EdgeBatch", "EdgeStore", "empty_store", "make_batch", "stack_batches",
    "store_from_arrays", "TemporalIndex", "build_index", "LaneParams",
    "WalkBuffers", "WalkResult",
    "alloc_walk_buffers", "generate_walk_lanes", "generate_walks",
    "generate_walks_donated", "WindowState", "ingest", "ingest_nodonate",
    "ingest_sort", "init_window",
]
