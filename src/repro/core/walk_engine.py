"""Temporal random-walk engine (paper §2.4).

Execution paths (the TPU mapping of the paper's dispatch plane):

* ``fullwalk`` — the paper's §2.4.1 baseline: every walk advances
  independently; per-hop gathers and binary searches are issued per walk in
  whatever order walks happen to sit in memory.

* ``grouped`` — the hierarchical-cooperative-scheduling adaptation (§2.4.3):
  each hop, walks are regrouped by (current node, current time); identical
  (node, time) pairs form *segments* whose temporal cutoff is computed once
  at the segment head and broadcast to members, and whose gathers touch
  contiguous index regions (the TPU analog of coalesced, smem-amortized
  access). Only the random draw and the picked edge differ per walk —
  exactly the paper's observation.

* ``tiled`` — the grouped path with the hop search+sample executed by the
  Pallas kernel (kernels/walk_step.py), which stages each task's edge slice
  in VMEM (the smem-panel analog). Selected via SchedulerConfig.path.

* ``fused`` — the grouped path with the whole hop (prefix-weight lookup,
  branchless per-lane inverse-CDF draw, and the dst/ts gather) executed by
  the fused convergence-tiered kernel (kernels/fused_step.py, DESIGN.md
  §14): small-degree lanes resolve in one staged tile pass, oversize lanes
  sweep the edge window in-kernel — no jnp fallback. Because the bias
  dispatches by int32 code per lane, ``fused`` also serves heterogeneous
  ``LaneParams`` batches (unlike ``tiled``, which compiles one bias).

The per-hop regrouping itself comes in two flavors
(``SchedulerConfig.regroup``, DESIGN.md §10): ``bucket`` (default) is a
stable node sort of the current lane layout
(core/scheduler.py::bucket_regroup) whose permutation is **carried across
hops** in the walk state — lanes stay in grouped order and only the
lane→walk map is tracked, so no scatter-built inverse permutation is paid
per hop. ``lexsort`` keeps
the seed's per-hop ``jnp.lexsort`` + inverse scatter as the
equivalence/benchmark reference. Since the bucket regroup sorts dead lanes
last, the grouped path's hop loop narrows to its live lanes in a static
ladder of quartering widths as its walks end (``_tier_widths``).

All paths and regroup modes produce **identical walks for identical keys**
(tested): random draws are generated in original walk order and indexed
through the lane→walk map, so grouping is purely an execution-layout
decision — the paper makes the same claim for its tiers.

Steady-state callers reuse the output buffers via
``generate_walks_donated`` (walk arrays donated back into the jit,
DESIGN.md §10), and ``repro.distributed.walks.generate_walks_sharded``
shards the walk axis across devices (walks are embarrassingly parallel;
the index is replicated). When the window itself no longer fits one
device, ``repro.distributed.streaming_shard`` shards the window and
migrates walks between owners instead (DESIGN.md §12).

**Per-lane sampler parameters** (``LaneParams`` / ``generate_walk_lanes``,
DESIGN.md §11): the serving coalescer packs many heterogeneous queries
into one fixed-shape batch, so bias, max length, and RNG seed become
per-lane *arrays* instead of compile-time config. Bias dispatches
branchlessly over the three closed-form inverse CDFs
(samplers.index_pick_lanes), per-lane max length masks ``has_next`` once a
lane's own budget is spent, and every lane draws from an RNG stream folded
by (request seed, walk-within-request, step) — independent of batch shape
and of which other lanes are present, which makes a coalesced batch
bit-identical to running each query solo. The same lane batches run over
the node-partitioned window via
``repro.distributed.streaming_shard.serve_lanes_sharded`` (DESIGN.md §13),
with the identical bit-identity guarantee.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SamplerConfig, SchedulerConfig, WalkConfig
from repro.core import scheduler as sched
from repro.core.alias import AliasTables, alias_pick
from repro.core.samplers import (
    BIAS_CODES,
    BIAS_TABLE,
    node2vec_beta,
    node2vec_beta_lanes,
    node2vec_max_beta,
    node2vec_max_beta_lanes,
    pick_in_neighborhood,
    pick_in_neighborhood_lanes,
    pick_start_edges,
    pick_start_edges_lanes,
)
from repro.core.temporal_index import (
    IndexViews,
    TemporalIndex,
    node_range,
    ranged_search,
    temporal_cutoff,
)
from repro.obs.tracing import scope

NODE_PAD = -1          # sentinel in emitted walks beyond walk length
N2V_ROUNDS = 8         # rejection-sampling rounds per hop (vectorized)
# Second-order lanes draw their rejection uniforms from dedicated RNG tags
# N2V_TAG_BASE + step·(2·N2V_ROUNDS) + 2r + j, far above any per-step tag
# (tag s+1 for scan step s) a first-order lane ever uses — so enabling
# second-order lanes leaves every existing draw stream bit-identical.
N2V_TAG_BASE = 1 << 20


# ---------------------------------------------------------------------------
# Capability chokepoint: every bias/path/lane refusal goes through here
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LaneFeatures:
    """Static summary of what a coalesced lane batch needs from the engine.

    ``table``: the batch may carry lanes with bias code "table" (alias
    tables are threaded into the dispatch). ``second_order``: the batch
    carries per-lane node2vec (p, q) arrays with at least one lane ≠ 1.
    Both are compile-time facts (the service derives them from the query
    set), so refusals stay trace-time errors.
    """

    table: bool = False
    second_order: bool = False


_CAP = "unsupported sampler capability: "


def check_capabilities(scfg: SamplerConfig, path: str,
                       lanes: Optional[LaneFeatures] = None, *,
                       sharded: bool = False,
                       have_tables: bool = False) -> None:
    """Validate a (sampler config, path, lane features) combination.

    The single chokepoint behind every refusal the engine, the serving
    layer, and the sharded streaming walker used to issue separately —
    one place to read what runs where, one set of error messages, and
    one matrix for tests to sweep (tests/test_capabilities.py). Raises
    ``ValueError``; returns ``None`` when the combination is supported.
    """
    if scfg.bias not in BIAS_CODES:
        raise ValueError(
            _CAP + f"unknown bias {scfg.bias!r} "
            f"(expected one of {sorted(BIAS_CODES)})")
    if scfg.start_bias == "table" or scfg.start_bias not in BIAS_CODES:
        raise ValueError(
            _CAP + f"start-edge bias {scfg.start_bias!r} is not supported; "
            "start draws use the closed forms 'uniform'|'linear'|"
            "'exponential' (alias tables cover neighborhood regions, not "
            "the timestamp view)")
    use_n2v = scfg.node2vec_p != 1.0 or scfg.node2vec_q != 1.0

    if scfg.bias == "table":
        if scfg.mode != "index":
            raise ValueError(
                _CAP + "bias='table' requires SamplerConfig.mode='index' "
                f"(the alias draw replaces the mode dispatch; got "
                f"mode={scfg.mode!r})")
        if sharded:
            raise ValueError(
                _CAP + "sharded streaming walks do not support bias="
                "'table' (per-shard alias tables cover resident regions "
                "only; a migrating walk's draw would need its owner's "
                "table)")
        if not have_tables:
            raise ValueError(
                _CAP + "bias='table' requires alias tables: build the "
                "window with a TableSpec (init_window(..., table=spec) / "
                "ingest(..., table=spec)) and pass state.tables into the "
                "walk entry point")
        if path in ("tiled", "fused"):
            raise ValueError(
                _CAP + f"path={path!r} does not support bias='table' (the "
                "Pallas kernels dispatch the closed-form inverse CDFs "
                "only); use 'fullwalk'|'grouped'")

    if use_n2v:
        if sharded:
            raise ValueError(
                _CAP + "sharded streaming walks do not support node2vec "
                "second-order bias (the β probe needs the previous node's "
                "adjacency, which lives on a different shard)")
        if lanes is not None:
            raise ValueError(
                _CAP + "per-lane batches do not support config-level "
                "node2vec second-order bias; second-order lanes carry "
                "their own (n2v_p, n2v_q) arrays (set node2vec_p="
                "node2vec_q=1.0)")
        if path == "fused":
            raise ValueError(
                _CAP + "path='fused' does not support node2vec "
                "second-order bias (the rejection loop re-draws outside "
                "the kernel); use 'fullwalk'|'grouped'")
        if path == "tiled":
            raise ValueError(
                _CAP + "path='tiled' does not support node2vec "
                "second-order bias (the walk-step kernel draws first-"
                "order only); use 'fullwalk'|'grouped'")

    if lanes is not None:
        if scfg.mode != "index":
            raise ValueError(
                _CAP + "per-lane batches require SamplerConfig.mode="
                "'index': the per-lane dispatch selects over the closed-"
                f"form inverse CDFs (got mode={scfg.mode!r})")
        if path == "tiled":
            raise ValueError(
                _CAP + "per-lane batches support paths 'fullwalk'|"
                "'grouped'|'fused'; the tiled Pallas kernel compiles a "
                "single bias per dispatch (the fused kernel dispatches "
                "per-lane bias codes)")
        if lanes.table:
            if sharded:
                raise ValueError(
                    _CAP + "sharded lane serving does not support bias "
                    "code 'table' (per-shard alias tables cover resident "
                    "regions only; a migrating lane's draw would need its "
                    "owner's table)")
            if not have_tables:
                raise ValueError(
                    _CAP + "lane bias code 'table' requires alias tables: "
                    "ingest with a TableSpec and pass state.tables into "
                    "generate_walk_lanes")
            if path == "fused":
                raise ValueError(
                    _CAP + "path='fused' does not serve lane bias code "
                    "'table' (the fused kernel dispatches the closed-form "
                    "codes only); use 'fullwalk'|'grouped'")
        if lanes.second_order:
            if sharded:
                raise ValueError(
                    _CAP + "sharded lane serving does not support "
                    "node2vec second-order lanes (the β probe needs the "
                    "previous node's adjacency, which lives on a "
                    "different shard)")
            if path == "fused":
                raise ValueError(
                    _CAP + "path='fused' does not support node2vec "
                    "second-order lanes (the rejection loop re-draws "
                    "outside the kernel); use 'fullwalk'|'grouped'")


def index_views(scfg: SamplerConfig, path: str, *,
                second_order: bool = False) -> IndexViews:
    """The optional index views a walk under (sampler config, path) can
    read, beside the core that every walk reads (temporal_index.py).

    * the weight prefixes: ``mode="weight"`` draws and the Pallas paths
      (``tiled``, ``fused``) stage them;
    * the adjacency view: the node2vec β probe, where a second-order draw
      can reach the index — config-level node2vec, or ``second_order``
      lanes (a ``WalkService`` may admit them with any query).

    The engines build, carry and rebuild only these (DESIGN.md §3); this is
    derived, never a user option.
    """
    return IndexViews(
        prefixes=scfg.mode == "weight" or path in ("tiled", "fused"),
        adjacency=(second_order or scfg.node2vec_p != 1.0
                   or scfg.node2vec_q != 1.0))


class WalkResult(NamedTuple):
    nodes: jax.Array     # int32[W, L+1], NODE_PAD beyond length
    times: jax.Array     # int32[W, L+1]
    lengths: jax.Array   # int32[W] number of nodes recorded (>=1)
    stats: Optional[jax.Array]   # float32[L, sched.NUM_STATS] or None
    # int32 scalars (None where the producer does not report them): the
    # iterations the hop loop ran, and the lanes it processed over them —
    # the sum of each iteration's width, W × steps on a one-width loop
    steps: Optional[jax.Array] = None
    lane_steps: Optional[jax.Array] = None


class WalkBuffers(NamedTuple):
    """Reusable walk output buffers (donated through the jit boundary).

    Holds the two O(W·L) arrays of a WalkResult. The previous round's
    contents are dead on entry: the walk loop writes every cell a walk
    records (the start writes its first columns, each hop its column for
    the lanes the loop still processes), and the result masks every cell
    at a column ≥ the walk's own length to NODE_PAD, stale ones included.
    So the donated storage flows straight into the loop carry and XLA
    updates it in place — steady-state walk generation allocates only the
    [W] lengths vector (DESIGN.md §10).
    """

    nodes: jax.Array     # int32[W, L+1]
    times: jax.Array     # int32[W, L+1]


def alloc_walk_buffers(wcfg: WalkConfig) -> WalkBuffers:
    """Allocate walk buffers for ``generate_walks_donated`` round-trips."""
    W, L = wcfg.num_walks, wcfg.max_length
    return WalkBuffers(
        nodes=jnp.full((W, L + 1), NODE_PAD, jnp.int32),
        times=jnp.full((W, L + 1), NODE_PAD, jnp.int32),
    )


class LaneParams(NamedTuple):
    """Per-lane sampler parameters for a coalesced walk batch (DESIGN.md §11).

    All arrays are [W] in walk order. ``rid``/``wid`` drive the per-lane
    RNG: lane draws come from ``fold_in(fold_in(fold_in(base, rid), wid),
    tag)`` with tag 0 for the start draw and tag s+1 for scan step s — a
    pure function of (request seed, walk-within-request, step). A lane's
    stream therefore does not depend on the batch shape or on which other
    lanes share the batch: the bit-identity guarantee the serving
    coalescer relies on.
    """

    start_node: jax.Array   # int32[W] start node (start_mode="nodes")
    bias: jax.Array         # int32[W] hop-bias code (samplers.BIAS_CODES)
    start_bias: jax.Array   # int32[W] start-edge bias code (start_mode="edges")
    max_len: jax.Array      # int32[W] per-lane hop budget (edges emitted <= max_len)
    rid: jax.Array          # int32[W] request seed folded into the RNG
    wid: jax.Array          # int32[W] walk index within the request
    active: jax.Array       # bool[W] real lane vs bucket padding
    # second-order node2vec lane parameters (DESIGN.md §17): float32[W],
    # 1.0 disables the second-order bias for that lane. None (the default,
    # an empty pytree subtree) on batches packed before this field existed
    # — equivalent to all-ones. Only read when the entry point is called
    # with second_order=True.
    n2v_p: Optional[jax.Array] = None
    n2v_q: Optional[jax.Array] = None


def _lane_keys(key: jax.Array, lanes: LaneParams) -> jax.Array:
    """Per-lane PRNG keys: base key folded by request seed then walk id."""
    ks = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, lanes.rid)
    return jax.vmap(jax.random.fold_in)(ks, lanes.wid)


def _lane_uniform(lane_keys: jax.Array, tag) -> jax.Array:
    """One U[0,1) draw per lane from the step-``tag`` substream."""
    ks = jax.vmap(jax.random.fold_in, in_axes=(0, None))(lane_keys, tag)
    return jax.vmap(lambda k: jax.random.uniform(k, ()))(ks)


class _Carry(NamedTuple):
    # cur_node/cur_time/prev_node/alive are in *lane* order, one entry per
    # lane the loop processes (fewer than W on the narrowed tiers of the
    # grouped-bucket loop); ``lane`` maps lane -> original walk id
    # (identity for fullwalk/lexsort, the carried bucket-regroup
    # permutation otherwise). nodes/times/lengths stay in walk order, W
    # rows, throughout.
    cur_node: jax.Array
    cur_time: jax.Array
    prev_node: jax.Array
    alive: jax.Array
    lane: jax.Array
    nodes: jax.Array
    times: jax.Array
    lengths: jax.Array


# ---------------------------------------------------------------------------
# Walk starts
# ---------------------------------------------------------------------------


def start_walks(index: TemporalIndex, wcfg: WalkConfig, scfg: SamplerConfig,
                key: jax.Array, walk_offset=0,
                buffers: Optional[WalkBuffers] = None,
                lanes: Optional[LaneParams] = None,
                lane_keys: Optional[jax.Array] = None) -> _Carry:
    W = wcfg.num_walks
    L = wcfg.max_length
    if buffers is None:
        nodes = jnp.full((W, L + 1), NODE_PAD, jnp.int32)
        times = jnp.full((W, L + 1), NODE_PAD, jnp.int32)
    else:
        # every cell is overwritten before the result is read (see
        # WalkBuffers), so the stale contents pass through untouched and
        # the donated storage is updated in place
        nodes = buffers.nodes
        times = buffers.times
    lane = jnp.arange(W, dtype=jnp.int32)

    t_floor = jnp.where(index.num_edges > 0, index.store.ts[0] - 1, 0)

    if lanes is not None:
        # Per-lane starts (DESIGN.md §11). Padding lanes (active=False)
        # stay dead: all-PAD rows with length 0.
        nc = index.node_capacity
        if wcfg.start_mode == "nodes":
            # explicit per-lane start nodes; mirrors all_nodes aliveness
            # (a start node with no in-window edges yields an empty walk)
            cur = jnp.clip(lanes.start_node, 0, nc - 1)
            deg = index.node_starts[cur + 1] - index.node_starts[cur]
            alive = (lanes.active & (deg > 0) & (lanes.start_node >= 0)
                     & (lanes.start_node < nc))
            cur_time = jnp.full((W,), 1, jnp.int32) * t_floor
            nodes = nodes.at[:, 0].set(jnp.where(alive, cur, NODE_PAD))
            times = times.at[:, 0].set(jnp.where(alive, cur_time, NODE_PAD))
            return _Carry(cur_node=cur, cur_time=cur_time,
                          prev_node=jnp.full((W,), -1, jnp.int32),
                          alive=alive, lane=lane, nodes=nodes, times=times,
                          lengths=alive.astype(jnp.int32))
        if wcfg.start_mode == "edges":
            # per-lane biased start-edge selection over the timestamp view
            u = _lane_uniform(lane_keys, 0)
            e = pick_start_edges_lanes(index, lanes.start_bias, u)
            e = jnp.clip(e, 0, index.edge_capacity - 1)
            src = index.store.src[e]
            cur = index.store.dst[e]
            cur_time = index.store.ts[e]
            alive = lanes.active & (index.num_edges > 0)
            nodes = nodes.at[:, 0].set(jnp.where(alive, src, NODE_PAD))
            times = times.at[:, 0].set(jnp.where(alive, cur_time, NODE_PAD))
            nodes = nodes.at[:, 1].set(jnp.where(alive, cur, NODE_PAD))
            times = times.at[:, 1].set(jnp.where(alive, cur_time, NODE_PAD))
            return _Carry(cur_node=cur, cur_time=cur_time, prev_node=src,
                          alive=alive, lane=lane, nodes=nodes, times=times,
                          lengths=jnp.where(alive, 2, 0).astype(jnp.int32))
        raise ValueError(
            f"lane batches support start_mode 'nodes'|'edges', "
            f"got {wcfg.start_mode!r}")

    if wcfg.start_mode == "all_nodes":
        # paper §3.3: k walks from every active source node; walk_offset
        # shifts the assignment for sharded generation (walk w on shard s
        # starts where global walk s·Wd + w would)
        nc = index.node_capacity
        cur = ((walk_offset + jnp.arange(W, dtype=jnp.int32)) % nc).astype(
            jnp.int32)
        deg = index.node_starts[cur + 1] - index.node_starts[cur]
        alive = deg > 0
        cur_time = jnp.full((W,), 1, jnp.int32) * t_floor
    elif wcfg.start_mode == "nodes":
        # uniform over active nodes via cumulative-count inversion
        nc = index.node_capacity
        deg = index.node_starts[1:nc + 1] - index.node_starts[:nc]
        active = (deg > 0).astype(jnp.int32)
        cum = jnp.cumsum(active)
        num_active = cum[-1]
        u = jax.random.uniform(key, (W,))
        j = jnp.floor(u * num_active.astype(jnp.float32)).astype(jnp.int32)
        j = jnp.clip(j, 0, jnp.maximum(num_active - 1, 0))
        cur = ranged_search(cum, jnp.zeros_like(j), jnp.full_like(j, nc), j,
                            strict=True)
        alive = jnp.broadcast_to(num_active > 0, (W,))
        cur_time = jnp.full((W,), 1, jnp.int32) * t_floor
    elif wcfg.start_mode == "edges":
        # start-edge selection over the timestamp-grouped view (paper §2.3)
        u = jax.random.uniform(key, (W,))
        e = pick_start_edges(index, scfg, u)
        e = jnp.clip(e, 0, index.edge_capacity - 1)
        src = index.store.src[e]
        cur = index.store.dst[e]
        cur_time = index.store.ts[e]
        alive = jnp.broadcast_to(index.num_edges > 0, (W,))
        nodes = nodes.at[:, 0].set(jnp.where(alive, src, NODE_PAD))
        times = times.at[:, 0].set(jnp.where(alive, cur_time, NODE_PAD))
        nodes = nodes.at[:, 1].set(jnp.where(alive, cur, NODE_PAD))
        times = times.at[:, 1].set(jnp.where(alive, cur_time, NODE_PAD))
        return _Carry(cur_node=cur, cur_time=cur_time, prev_node=src,
                      alive=alive, lane=lane, nodes=nodes, times=times,
                      lengths=jnp.where(alive, 2, 0).astype(jnp.int32))
    else:
        raise ValueError(f"unknown start_mode {wcfg.start_mode!r}")

    nodes = nodes.at[:, 0].set(jnp.where(alive, cur, NODE_PAD))
    times = times.at[:, 0].set(jnp.where(alive, cur_time, NODE_PAD))
    return _Carry(cur_node=cur, cur_time=cur_time,
                  prev_node=jnp.full((W,), -1, jnp.int32),
                  alive=alive, lane=lane, nodes=nodes, times=times,
                  lengths=alive.astype(jnp.int32))


# ---------------------------------------------------------------------------
# One hop, full-walk layout
# ---------------------------------------------------------------------------


def _pick_config(index, scfg, tables, a, c, b, u, node):
    """First-order pick under the *config* bias (non-lane paths)."""
    if scfg.bias == "table":
        return alias_pick(tables, a, c, b, u, radix=scfg.table_radix,
                          degree_cap=scfg.table_degree_cap)
    return pick_in_neighborhood(index, scfg, c, b, u, node)


def _pick_lane_codes(index, scfg, tables, code, a, c, b, u):
    """First-order pick under per-lane bias codes.

    The closed forms dispatch branchlessly as before; when alias tables
    are threaded in, lanes coded BIAS_TABLE overlay the alias draw —
    still elementwise in (code, u, region), preserving the coalesced↔solo
    bit-identity guarantee.
    """
    k = pick_in_neighborhood_lanes(index, code, c, b, u)
    if tables is not None:
        k_tab = alias_pick(tables, a, c, b, u, radix=scfg.table_radix,
                           degree_cap=scfg.table_degree_cap)
        k = jnp.where(code == BIAS_TABLE, k_tab, k)
    return k


def _lane_second_order(index, scfg, tables, lane_bias, a, c, b, prev,
                       k_plain, n2v):
    """Per-lane node2vec rejection over the first-order proposal stream.

    ``n2v = (p, q, us2)`` with us2[N2V_ROUNDS, 2, W] from the dedicated
    N2V_TAG_BASE substreams, all in the caller's lane layout. Lanes with
    p == q == 1 keep ``k_plain`` (the ordinary first-order draw), so a
    mixed batch is bit-identical to running each lane solo either way.
    """
    p, q, us2 = n2v
    beta_max = node2vec_max_beta_lanes(p, q)

    def round_(carry_, uv):
        k_acc, accepted = carry_
        u_r, v_r = uv[0], uv[1]
        k_r = _pick_lane_codes(index, scfg, tables, lane_bias, a, c, b, u_r)
        cand = index.ns_dst[jnp.clip(k_r, 0, index.edge_capacity - 1)]
        beta = node2vec_beta_lanes(index, prev, cand, p, q)
        # hops with no previous node accept unconditionally
        ok = (v_r * beta_max <= beta) | (prev < 0)
        take = ok & ~accepted
        return (jnp.where(take, k_r, k_acc), accepted | ok), None

    k0 = _pick_lane_codes(index, scfg, tables, lane_bias, a, c, b,
                          us2[0, 0])
    W = k0.shape[0]
    (k_rej, _), _ = jax.lax.scan(round_, (k0, jnp.zeros((W,), bool)), us2)
    is_n2v = (p != 1.0) | (q != 1.0)
    return jnp.where(is_n2v, k_rej, k_plain)


class _Draws(NamedTuple):
    """One hop's draw inputs for a set of lanes, in that lane order.

    ``u`` is each lane's first-order uniform; ``us`` takes its place for
    the config-level node2vec rounds ([N2V_ROUNDS, 2, lanes]). A lane batch
    (DESIGN.md §11) adds its bias codes, ``limit`` (the lane's budget
    allows this hop's column) and, with second-order lanes, ``n2v = (p, q,
    us2)``. Every entry is a function of the lane's walk id alone (see
    ``draws`` in ``_generate_walks_impl``), which is what makes every
    layout and every loop width emit identical walks for identical keys.
    """

    u: Optional[jax.Array] = None
    us: Optional[jax.Array] = None
    bias: Optional[jax.Array] = None
    limit: Optional[jax.Array] = None
    n2v: Optional[tuple] = None


def _draw_pick(index, scfg, c, b, s_node, s_prev, d: _Draws, tables=None):
    """Sample positions k ∈ [c, b) for lanes at ``s_node`` (previous node
    ``s_prev``) from their draws ``d``, given in the same lane order.
    ``tables`` threads the alias tables for table-coded lanes (or config
    bias='table')."""
    use_n2v = (scfg.node2vec_p != 1.0) or (scfg.node2vec_q != 1.0)
    if tables is not None or d.n2v is not None:
        a, _ = node_range(index, s_node)
    else:
        a = None
    if d.bias is not None:
        k = _pick_lane_codes(index, scfg, tables, d.bias, a, c, b, d.u)
        if d.n2v is not None:
            k = _lane_second_order(index, scfg, tables, d.bias, a, c, b,
                                   s_prev, k, d.n2v)
    elif not use_n2v:
        k = _pick_config(index, scfg, tables, a, c, b, d.u, s_node)
    else:
        # rejection sampling on the first-order proposal (paper §2.5)
        beta_max = node2vec_max_beta(scfg.node2vec_p, scfg.node2vec_q)

        def round_(carry_, uv):
            k_acc, accepted = carry_
            u_r, v_r = uv[0], uv[1]
            k_r = _pick_config(index, scfg, tables, a, c, b, u_r, s_node)
            cand = index.ns_dst[jnp.clip(k_r, 0, index.edge_capacity - 1)]
            beta = node2vec_beta(index, s_prev, cand,
                                 scfg.node2vec_p, scfg.node2vec_q)
            # hops with no previous node accept unconditionally
            ok = (v_r * beta_max <= beta) | (s_prev < 0)
            take = ok & ~accepted
            return (jnp.where(take, k_r, k_acc), accepted | ok), None

        k0 = _pick_config(index, scfg, tables, a, c, b, d.us[0, 0], s_node)
        (k, _), _ = jax.lax.scan(round_, (k0, jnp.zeros(k0.shape, bool)),
                                 d.us)

    return jnp.clip(k, 0, index.edge_capacity - 1)


def _sample_hop(index: TemporalIndex, scfg: SamplerConfig,
                cur_node, cur_time, prev_node, alive, d: _Draws,
                tables=None):
    """Given per-lane (node, time) and the lanes' draws, returns
    (next_node, next_time, has_next).

    Pure sampling logic shared by every jnp path; callers control the
    layout. Every lane computes its own cutoff Γ_t(v) = [c, b) (a
    vectorized search), so *any* lane permutation is correct; lanes of one
    (node, time) segment compute the same value, and grouping them only
    improves gather locality.
    """
    a, b = node_range(index, cur_node)
    c = temporal_cutoff(index, a, b, cur_time)
    has_next = alive & (b - c > 0)
    if d.limit is not None:
        has_next = has_next & d.limit
    k = _draw_pick(index, scfg, c, b, cur_node, prev_node, d, tables)
    return index.ns_dst[k], index.ns_ts[k], has_next


def _hop_fullwalk(index, scfg, sched_cfg, carry: _Carry, step, draws,
                  tables=None) -> _Carry:
    with scope("pick"):
        nn, nt, has_next = _sample_hop(
            index, scfg, carry.cur_node, carry.cur_time, carry.prev_node,
            carry.alive, draws(None), tables)
        return _advance(carry, step, nn, nt, has_next)


# ---------------------------------------------------------------------------
# Grouped layouts: lanes regrouped by (node, time) each hop
# ---------------------------------------------------------------------------


def _lexsort_prologue(index: TemporalIndex, carry: _Carry):
    """Reference regroup: a fresh lexsort by (node, time), dead lanes last.
    Returns the permutation (lane -> walk id) and the permuted state."""
    with scope("regroup"):
        node_key = jnp.where(carry.alive, carry.cur_node,
                             index.node_capacity + 1)
        perm = jnp.lexsort((carry.cur_time, node_key)).astype(jnp.int32)
        return (perm, carry.cur_node[perm], carry.cur_time[perm],
                carry.prev_node[perm], carry.alive[perm])


def _bucket_prologue(index: TemporalIndex, sched_cfg, carry: _Carry):
    """Regroup lanes by current node (DESIGN.md §10) and permute the walk
    state; shared by the grouped, tiled and fused bucket hops. Dead lanes
    sort last, so the live lanes are the prefix of the new order. Returns
    the composed lane→walk map plus the permuted per-lane state."""
    nc = index.node_capacity
    with scope("regroup"):
        node_key = jnp.where(carry.alive, carry.cur_node, nc + 1)
        pp = sched.bucket_regroup(node_key, carry.cur_time, nc,
                                  time_subsort=sched_cfg.regroup_time)
        return (carry.lane[pp], carry.cur_node[pp], carry.cur_time[pp],
                carry.prev_node[pp], carry.alive[pp])


def _hop_grouped(index, scfg, sched_cfg, carry: _Carry, step, draws,
                 tables=None) -> _Carry:
    """Reference regroup: fresh lexsort by (node, time) + inverse scatter."""
    perm, s_node, s_time, s_prev, s_alive = _lexsort_prologue(index, carry)
    with scope("pick"):
        nn_s, nt_s, has_next_s = _sample_hop(
            index, scfg, s_node, s_time, s_prev, s_alive, draws(perm), tables)
        return _advance_unsorted(carry, step, perm, nn_s, nt_s, has_next_s)


def _hop_grouped_bucket(index, scfg, sched_cfg, carry: _Carry, step, draws,
                        tables=None) -> _Carry:
    """Node-sort regroup with carried permutation (DESIGN.md §10).

    Lanes stay in grouped order across hops — the regroup permutes the
    *previous* lane layout (walks keep near-sorted order naturally, since a
    segment's members scatter over one node's neighbor list) and composes
    into ``carry.lane``; no inverse permutation is ever built. It runs at
    the carry's width, which the loop narrows in tiers.
    """
    lane, s_node, s_time, s_prev, s_alive = _bucket_prologue(
        index, sched_cfg, carry)
    with scope("pick"):
        nn, nt, has_next_s = _sample_hop(
            index, scfg, s_node, s_time, s_prev, s_alive, draws(lane), tables)
        return _advance_lanes(carry, lane, step, s_node, s_time, s_prev,
                              nn, nt, has_next_s)


def _hop_tiled(index, scfg, sched_cfg, carry: _Carry, step, draws,
               tables=None) -> _Carry:
    """Lexsort layout with the Pallas kernel executing search+sample."""
    from repro.kernels import ops as kops
    perm, s_node, s_time, _, s_alive = _lexsort_prologue(index, carry)
    with scope("pick"):
        k, n = kops.walk_step(index, s_node, s_time, draws(perm).u, scfg,
                              sched_cfg)
        has_next_s = s_alive & (n > 0)
        k = jnp.clip(k, 0, index.edge_capacity - 1)
        return _advance_unsorted(carry, step, perm, index.ns_dst[k],
                                 index.ns_ts[k], has_next_s)


def _hop_tiled_bucket(index, scfg, sched_cfg, carry: _Carry, step, draws,
                      tables=None) -> _Carry:
    """Bucket-regrouped layout feeding the Pallas kernel (DESIGN.md §10).

    The regroup yields an exact node sort, which is all the tile/task-table
    construction needs.
    """
    from repro.kernels import ops as kops
    lane, s_node, s_time, s_prev, s_alive = _bucket_prologue(
        index, sched_cfg, carry)
    with scope("pick"):
        k, n = kops.walk_step(index, s_node, s_time, draws(lane).u, scfg,
                              sched_cfg)
        has_next_s = s_alive & (n > 0)
        k = jnp.clip(k, 0, index.edge_capacity - 1)
        return _advance_lanes(carry, lane, step, s_node, s_time, s_prev,
                              index.ns_dst[k], index.ns_ts[k], has_next_s)


def _fused_code(scfg: SamplerConfig, d: _Draws) -> jax.Array:
    """Per-lane bias codes for the fused kernel: the lane batch's own, else
    the config bias on every lane."""
    from repro.core.samplers import bias_code
    if d.bias is not None:
        return d.bias
    return jnp.full(d.u.shape, bias_code(scfg.bias), jnp.int32)


def _hop_fused(index, scfg, sched_cfg, carry: _Carry, step, draws,
               tables=None) -> _Carry:
    """Lexsort layout feeding the fused convergence-tiered kernel.

    ``tables`` and second-order draws never reach it — check_capabilities
    refuses table-bias and second-order batches on the fused path.
    """
    from repro.kernels import fused_step as kfused
    perm, s_node, s_time, _, s_alive = _lexsort_prologue(index, carry)
    with scope("pick"):
        d = draws(perm)
        out = kfused.fused_walk_step(index, s_node, s_time,
                                     _fused_code(scfg, d), d.u, scfg.mode,
                                     sched_cfg)
        has_next_s = s_alive & (out.n > 0)
        if d.limit is not None:
            has_next_s = has_next_s & d.limit
        return _advance_unsorted(carry, step, perm, out.dst, out.ts,
                                 has_next_s)


def _hop_fused_bucket(index, scfg, sched_cfg, carry: _Carry, step, draws,
                      tables=None) -> _Carry:
    """Bucket-regrouped layout feeding the fused kernel (DESIGN.md §14).

    The kernel returns the gathered dst/ts directly — the hop issues no
    edge-array gathers at all, unlike ``_hop_tiled_bucket``.
    ``tables`` is always None here (see ``_hop_fused``).
    """
    from repro.kernels import fused_step as kfused
    lane, s_node, s_time, s_prev, s_alive = _bucket_prologue(
        index, sched_cfg, carry)
    with scope("pick"):
        d = draws(lane)
        out = kfused.fused_walk_step(index, s_node, s_time,
                                     _fused_code(scfg, d), d.u, scfg.mode,
                                     sched_cfg)
        has_next_s = s_alive & (out.n > 0)
        if d.limit is not None:
            has_next_s = has_next_s & d.limit
        return _advance_lanes(carry, lane, step, s_node, s_time, s_prev,
                              out.dst, out.ts, has_next_s)


# The hop variant of each path, by regroup: (lexsort, bucket).
_HOPS = {
    "fullwalk": (_hop_fullwalk, _hop_fullwalk),
    "grouped": (_hop_grouped, _hop_grouped_bucket),
    "tiled": (_hop_tiled, _hop_tiled_bucket),
    "fused": (_hop_fused, _hop_fused_bucket),
}


def _advance(carry: _Carry, step, next_node, next_time, has_next) -> _Carry:
    """Advance with lanes in walk order (fullwalk / lexsort paths)."""
    nodes = carry.nodes.at[:, step + 1].set(
        jnp.where(has_next, next_node, NODE_PAD).astype(jnp.int32),
        mode="drop")
    times = carry.times.at[:, step + 1].set(
        jnp.where(has_next, next_time, NODE_PAD).astype(jnp.int32),
        mode="drop")
    return _Carry(
        cur_node=jnp.where(has_next, next_node, carry.cur_node),
        cur_time=jnp.where(has_next, next_time, carry.cur_time),
        prev_node=jnp.where(has_next, carry.cur_node, carry.prev_node),
        alive=has_next,
        lane=carry.lane,
        nodes=nodes, times=times,
        lengths=carry.lengths + has_next.astype(jnp.int32),
    )


def _advance_unsorted(carry: _Carry, step, perm, next_node, next_time,
                      has_next) -> _Carry:
    """Advance lanes laid out by ``perm`` (lane -> walk id) back in walk
    order, through the inverse permutation (lexsort paths)."""
    W = perm.shape[0]
    inv = jnp.zeros((W,), jnp.int32).at[perm].set(
        jnp.arange(W, dtype=jnp.int32))
    return _advance(carry, step, next_node[inv], next_time[inv],
                    has_next[inv])


def _advance_lanes(carry: _Carry, lane, step, s_node, s_time, s_prev,
                   next_node, next_time, has_next) -> _Carry:
    """Advance with lanes in grouped order; walk buffers scatter via lane."""
    nodes = carry.nodes.at[lane, step + 1].set(
        jnp.where(has_next, next_node, NODE_PAD).astype(jnp.int32),
        mode="drop")
    times = carry.times.at[lane, step + 1].set(
        jnp.where(has_next, next_time, NODE_PAD).astype(jnp.int32),
        mode="drop")
    return _Carry(
        cur_node=jnp.where(has_next, next_node, s_node),
        cur_time=jnp.where(has_next, next_time, s_time),
        prev_node=jnp.where(has_next, s_node, s_prev),
        alive=has_next,
        lane=lane,
        nodes=nodes, times=times,
        lengths=carry.lengths.at[lane].add(has_next.astype(jnp.int32),
                                           mode="drop"),
    )


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def _tier_widths(num_walks: int) -> tuple:
    """Loop widths of the grouped-bucket hop loop (DESIGN.md §10): W, W/4,
    W/16 while the next width is a multiple of 128 and at least
    max(W/32, 128), so at most three tiers; one tier, W, where W is not a
    multiple of 512. Each tier is one more copy of the hop body to trace,
    lower and load, so the ladder quarters rather than halves."""
    widths = [num_walks]
    floor = max(num_walks // 32, 128)
    while widths[-1] % 512 == 0 and widths[-1] // 4 >= floor:
        widths.append(widths[-1] // 4)
    return tuple(widths)


def _compact_lanes(carry: _Carry, width: int) -> _Carry:
    """The tier handover: a stable partition of the lanes on ``alive``
    (live lanes first, their grouped order kept), cut to ``width`` lanes.
    The caller narrows only once the live lanes fit, and a dead lane never
    revives, so the lanes cut off would only have written NODE_PAD."""
    n = carry.alive.shape[0]
    _, perm = jax.lax.sort(
        ((~carry.alive).astype(jnp.int32), jnp.arange(n, dtype=jnp.int32)),
        num_keys=1, is_stable=True)
    perm = perm[:width]
    return carry._replace(
        cur_node=carry.cur_node[perm], cur_time=carry.cur_time[perm],
        prev_node=carry.prev_node[perm], alive=carry.alive[perm],
        lane=carry.lane[perm])


def _generate_walks_impl(index: TemporalIndex, key: jax.Array,
                         wcfg: WalkConfig, scfg: SamplerConfig,
                         sched_cfg: SchedulerConfig,
                         collect_stats: bool = False,
                         buffers: Optional[WalkBuffers] = None,
                         walk_offset=0,
                         lanes: Optional[LaneParams] = None,
                         tables: Optional[AliasTables] = None,
                         second_order: bool = False) -> WalkResult:
    """Shared walk-generation body behind every jit entry point.

    ``tables`` threads the window's alias tables (bias='table' configs or
    table-coded lanes, DESIGN.md §17); ``second_order`` (static) compiles
    the per-lane node2vec rejection machinery into the lane dispatch.
    Its device work carries the ``walks`` scope (obs/tracing.py), the
    start ``start`` and each hop ``hop``.
    """
    with scope("walks"):
        path = sched_cfg.path
        if path not in _HOPS:
            raise ValueError(f"unknown scheduler path {path!r}")
        if sched_cfg.regroup not in ("bucket", "lexsort"):
            raise ValueError(f"unknown regroup {sched_cfg.regroup!r}")
        need = index_views(scfg, path, second_order=second_order)
        if not index.views.holds(need):
            raise ValueError(
                f"the walk reads index views {need}, but the index holds "
                f"{index.views}: build it with build_index(..., views=) or "
                "fit it with temporal_index.fit_index")
        if lanes is not None:
            _check_lane_support(wcfg, scfg, sched_cfg, lanes,
                                tables=tables, second_order=second_order)
            # one base key; lane streams are derived by fold_in, no split —
            # the split would make draws depend on batch composition
            lane_keys = _lane_keys(key, lanes)
            start_key = walk_key = key
        else:
            check_capabilities(scfg, path, have_tables=tables is not None)
            lane_keys = None
            start_key, walk_key = jax.random.split(key)
        with scope("start"):
            carry0 = start_walks(index, wcfg, scfg, start_key,
                                 walk_offset=walk_offset, buffers=buffers,
                                 lanes=lanes, lane_keys=lane_keys)
        W, L = wcfg.num_walks, wcfg.max_length
        edges = wcfg.start_mode == "edges"
        # number of remaining hops: start already consumed 1 edge in edges-mode
        hops = L - 1 if edges else L

        bucket = sched_cfg.regroup == "bucket"
        hop_variant = _HOPS[path][bucket]
        pass_tables = tables if scfg.bias == "table" or lanes is not None \
            else None
        use_n2v = (scfg.node2vec_p != 1.0) or (scfg.node2vec_q != 1.0)

        def draws_at(step, write_pos):
            """The hop's draws as a function of the lanes it processes:
            ``draws(order)`` for lanes whose walk ids are ``order`` (None:
            all W, in walk order). A config-level walk draws at full width
            and indexes by walk id; a lane batch draws per lane from
            (request seed, walk id, tag s+1), tag 0 being the start draw,
            and column write_pos+1 is written only while it stays within
            the lane's own max_len."""
            hop_key = jax.random.fold_in(walk_key, step)

            def draws(order):
                def sel(x):
                    return x if order is None else x[..., order]

                if lanes is None:
                    if use_n2v:
                        return _Draws(us=sel(jax.random.uniform(
                            hop_key, (N2V_ROUNDS, 2, W))))
                    return _Draws(u=sel(jax.random.uniform(hop_key, (W,))))
                keys = lane_keys if order is None else lane_keys[order]
                d = _Draws(u=_lane_uniform(keys, step + 1),
                           bias=sel(lanes.bias),
                           limit=(write_pos + 1) <= sel(lanes.max_len))
                if second_order:
                    # second-order rejection uniforms from the dedicated tag
                    # block (see N2V_TAG_BASE): 2 per round per lane
                    base = N2V_TAG_BASE + step * (2 * N2V_ROUNDS)
                    us2 = jnp.stack([
                        jnp.stack([_lane_uniform(keys, base + 2 * r),
                                   _lane_uniform(keys, base + 2 * r + 1)])
                        for r in range(N2V_ROUNDS)])
                    d = d._replace(n2v=(sel(lanes.n2v_p), sel(lanes.n2v_q),
                                        us2))
                return d

            return draws

        def body(state):
            step, carry, stats = state
            with scope("hop"):
                if collect_stats:
                    st = sched.dispatch_stats(index, carry.cur_node,
                                              carry.alive, sched_cfg)
                else:
                    st = jnp.zeros((sched.NUM_STATS,), jnp.float32)
                write_pos = step + (1 if edges else 0)
                carry = hop_variant(index, scfg, sched_cfg, carry, write_pos,
                                    draws_at(step, write_pos), pass_tables)
            return step + 1, carry, stats.at[step].set(st, mode="drop")

        # Hops run while any lane is alive: a dead lane stays dead, so every
        # later hop would only write NODE_PAD (and all-zero dispatch stats) —
        # the length mask below. Temporal walks die fast (each hop moves
        # forward in time), so this skips most of the max_length hops, but
        # a call lasts as long as its longest walk. The grouped-bucket loop
        # therefore narrows as its lanes die (DESIGN.md §10): its regroup
        # leaves the live lanes as the prefix of the lane order, so tier j
        # runs at width w_j (_tier_widths) while more than w_{j+1} lanes
        # live, then hands its lanes over to the next, narrower loop. The
        # loop counts the lanes it processes, Σ widths, as it goes.
        widths = _tier_widths(W) if path == "grouped" and bucket else (W,)
        step = jnp.asarray(0, jnp.int32)
        lane_steps = jnp.asarray(0, jnp.int32)
        carry = carry0
        stats = jnp.zeros((hops, sched.NUM_STATS), jnp.float32)
        for j, width in enumerate(widths):
            if j:
                with scope("hop"), scope("regroup"):
                    carry = _compact_lanes(carry, width)
            rest = widths[j + 1] if j + 1 < len(widths) else 0

            def cond(state, rest=rest):
                step, carry, _ = state
                return (step < hops) & (
                    jnp.sum(carry.alive, dtype=jnp.int32) > rest)

            step_in = step
            step, carry, stats = jax.lax.while_loop(
                cond, body, (step, carry, stats))
            lane_steps = lane_steps + (step - step_in) * width
        # a walk's cells from its own length on are NODE_PAD: lanes cut off
        # by the ladder stop writing their columns, and donated buffers
        # hold the previous round's walks there
        written = (jnp.arange(L + 1, dtype=jnp.int32)[None, :]
                   < carry.lengths[:, None])
        return WalkResult(nodes=jnp.where(written, carry.nodes, NODE_PAD),
                          times=jnp.where(written, carry.times, NODE_PAD),
                          lengths=carry.lengths,
                          stats=stats if collect_stats else None,
                          steps=step, lane_steps=lane_steps)


def _check_lane_support(wcfg: WalkConfig, scfg: SamplerConfig,
                        sched_cfg: SchedulerConfig, lanes: LaneParams,
                        tables: Optional[AliasTables] = None,
                        second_order: bool = False) -> None:
    """Static (trace-time) validation of a per-lane batch (DESIGN.md §11).

    Shape checks live here; everything capability-shaped delegates to
    ``check_capabilities``.
    """
    check_capabilities(
        scfg, sched_cfg.path,
        LaneFeatures(table=tables is not None, second_order=second_order),
        have_tables=tables is not None)
    if lanes.start_node.shape[0] != wcfg.num_walks:
        raise ValueError(
            f"lane arrays have {lanes.start_node.shape[0]} lanes but "
            f"wcfg.num_walks={wcfg.num_walks}")
    if second_order and (lanes.n2v_p is None or lanes.n2v_q is None):
        raise ValueError(
            "second_order=True requires LaneParams.n2v_p/n2v_q arrays "
            "(the coalescer packs them; see serve/coalescer.py)")


# Generate ``wcfg.num_walks`` temporal walks of ≤ ``max_length`` hops.
# ``tables`` (trailing, optional) threads the window's alias tables for
# bias='table' configs.
generate_walks = partial(
    jax.jit,
    static_argnames=("wcfg", "scfg", "sched_cfg", "collect_stats"),
)(_generate_walks_impl)


def _generate_walk_lanes_impl(index: TemporalIndex, key: jax.Array,
                              lanes: LaneParams, wcfg: WalkConfig,
                              scfg: SamplerConfig,
                              sched_cfg: SchedulerConfig,
                              buffers: Optional[WalkBuffers] = None,
                              tables: Optional[AliasTables] = None,
                              second_order: bool = False) -> WalkResult:
    return _generate_walks_impl(index, key, wcfg, scfg, sched_cfg,
                                buffers=buffers, lanes=lanes,
                                tables=tables, second_order=second_order)


# Coalesced heterogeneous batch (DESIGN.md §11): one fixed-shape dispatch
# serving many queries, with bias / max_length / RNG seed per lane (plus
# alias tables and per-lane node2vec (p, q) when the batch needs them,
# DESIGN.md §17). The jit cache keys on (wcfg, scfg, sched_cfg,
# second_order) — the serving coalescer keeps that set small by bucketing
# batch shapes.
generate_walk_lanes = partial(
    jax.jit,
    static_argnames=("wcfg", "scfg", "sched_cfg", "second_order"),
)(_generate_walk_lanes_impl)


def _generate_walks_donated_impl(index: TemporalIndex, key: jax.Array,
                                 buffers: WalkBuffers, wcfg: WalkConfig,
                                 scfg: SamplerConfig,
                                 sched_cfg: SchedulerConfig,
                                 tables: Optional[AliasTables] = None
                                 ) -> WalkResult:
    return _generate_walks_impl(index, key, wcfg, scfg, sched_cfg,
                                collect_stats=False, buffers=buffers,
                                tables=tables)


# Donating entry point for steady-state loops (DESIGN.md §10): pass the
# previous round's WalkResult arrays (or alloc_walk_buffers once) as
# ``buffers`` and XLA reuses their storage for the new result instead of
# allocating ~2·W·(L+1) ints per call. The passed-in buffers are consumed.
generate_walks_donated = partial(
    jax.jit,
    static_argnames=("wcfg", "scfg", "sched_cfg"),
    donate_argnums=(2,),   # buffers only; tables trail after and are read-only
)(_generate_walks_donated_impl)
