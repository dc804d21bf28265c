"""Pallas TPU kernel: fused convergence-tiered walk step (paper §2.4.3-§2.4.4).

One kernel dispatch per hop fuses the three stages the seed-era tiled path
ran as separate ops — the prefix-weight lookup, the inverse-CDF draw, and
the neighbor ``dst``/``ts`` gather — and dispatches all three closed-form
biases **branchlessly by int32 code** (samplers.BIAS_CODES, matching
``LaneParams``), so one compiled kernel serves heterogeneous per-lane
bias batches.

Degree-tiered program lanes (the TPU analogue of the paper's Fig. 5
thread/warp/block terminal kernels, selected by the same convergence and
degree statistics ``core/scheduler.py::dispatch_stats`` reports):

* **tier S (staged)** — lanes whose neighborhood fits the tile's staged
  ``2·tile_edges`` VMEM window (the smem-panel analog, §2.4.3) resolve in
  one pass over the staged rows: dense compare-and-reduce cutoff, per-lane
  branchless pick, one-hot gather. This is the common case the paper's
  shared-memory tiers serve.
* **tier L (swept)** — oversize lanes (region span > 2·tile_edges — the
  paper's G-axis "global" tier) are tiled over the edge window: the grid's
  second axis walks ``tile_edges`` blocks of the node-ts view sequentially
  while per-lane VMEM scratch carries the running cutoff count, the
  one-hot-captured prefix values at the cutoff, and the monotone pick
  count. One sweep suffices because the cutoff finalizes in the block that
  contains it — until then the candidate ``c = a + cnt`` sits at the end
  of the seen range, which self-masks every downstream one-hot (details in
  ``_big_kernel_weight``). The seed path served these lanes through a
  pure-jnp gather fallback (kernels/ops.py); the sweep retires that.

Bit-identity contract: both tiers evaluate exactly the engine's sampler
expressions (samplers.py) over exactly the prefix values the engine reads
— the staged rows are slices of the same global ``pexp``/``plin`` arrays,
so weight-mode counting reproduces the binary search bit-for-bit
(DESIGN.md §14). ``path="fused"`` therefore emits walks byte-identical to
the ``grouped``/``tiled`` paths (tested in tests/test_fused_step.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.configs.base import SchedulerConfig
from repro.core.samplers import (
    BIAS_LINEAR,
    BIAS_UNIFORM,
    index_pick_lanes,
    index_uniform,
)
from repro.core.temporal_index import TemporalIndex, node_range
from repro.kernels.runtime import resolve_interpret


class FusedStepResult(NamedTuple):
    """Per-lane hop outputs plus the actual tier split of this dispatch."""

    k: jax.Array       # int32[W] global pick position (0 where n <= 0)
    n: jax.Array       # int32[W] neighborhood size |Γ_t(v)|
    dst: jax.Array     # int32[W] picked neighbor (0 where n <= 0)
    ts: jax.Array      # int32[W] picked edge timestamp (0 where n <= 0)
    tiers: jax.Array   # int32[3]: (tier-S lanes, tier-L lanes, swept blocks)


def _count_true(mask: jax.Array) -> jax.Array:
    return jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)


def _onehot_i32(values_row: jax.Array, pos: jax.Array,
                k: jax.Array) -> jax.Array:
    """Exact int32 gather-by-one-hot: sum(where(pos == k, values, 0))."""
    sel = jnp.where(pos == k, values_row, 0)
    return jnp.sum(sel, axis=1, keepdims=True)


def _onehot_f32(values_row: jax.Array, pos: jax.Array,
                k: jax.Array) -> jax.Array:
    sel = jnp.where(pos == k, values_row, 0.0)
    return jnp.sum(sel, axis=1, keepdims=True)


def _row(*refs) -> jax.Array:
    """Concatenate staged 1-D edge blocks into one (1, n) lane row."""
    return jnp.concatenate([r[...][None, :] for r in refs], axis=1)


# Layout: per-walk arrays enter the kernels as (W, 1) columns in (TW, 1)
# blocks, so every lane quantity is a (TW, 1) column that broadcasts
# against the (1, n) edge rows without a relayout. Edge blocks stay 1-D
# (tile_edges is a multiple of 1024, the chip's 1-D tiling); a 1-D walk
# block of tile_walks = 256 would not match it.


# ---------------------------------------------------------------------------
# Tier S: one staged pass over the tile's 2·TE VMEM window
# ---------------------------------------------------------------------------


def _finalize(k, n, pos, dst, ts, kmax, k_ref, n_ref, dst_out_ref,
              ts_out_ref):
    k = jnp.clip(k, 0, kmax)
    has = n > 0
    k_ref[...] = jnp.where(has, k, 0)
    n_ref[...] = n
    dst_out_ref[...] = jnp.where(has, _onehot_i32(dst, pos, k), 0)
    ts_out_ref[...] = jnp.where(has, _onehot_i32(ts, pos, k), 0)


def _cutoff(time_ref, lo_ref, hi_ref, ts):
    """Dense compare-and-reduce temporal cutoff (DESIGN.md §2)."""
    lo = lo_ref[...]
    hi = hi_ref[...]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, ts.shape[1]), 1)
    in_region = (pos >= lo) & (pos < hi)
    c = lo + _count_true(in_region & (ts <= time_ref[...]))
    n = hi - c
    return pos, hi, c, n


def _small_kernel_index(
        # scalar prefetch
        base_ref,
        # per-walk tile inputs [TW, 1]
        time_ref, lo_ref, hi_ref, u_ref, code_ref,
        # staged edge-view windows, two consecutive blocks each [TE]
        ts0_ref, ts1_ref, dst0_ref, dst1_ref,
        # outputs [TW, 1]
        k_ref, n_ref, dst_out_ref, ts_out_ref):
    te = ts0_ref.shape[0]
    ts = _row(ts0_ref, ts1_ref)                               # [1, 2TE]
    dst = _row(dst0_ref, dst1_ref)
    pos, _, c, n = _cutoff(time_ref, lo_ref, hi_ref, ts)
    # branchless per-lane closed-form dispatch (paper eqs 1-3, §2.5)
    k = c + index_pick_lanes(code_ref[...], u_ref[...], n)
    _finalize(k, n, pos, dst, ts, 2 * te - 1, k_ref, n_ref, dst_out_ref,
              ts_out_ref)


def _small_kernel_weight(
        base_ref,
        time_ref, lo_ref, hi_ref, u_ref, code_ref, tbase_ref,
        ts0_ref, ts1_ref, dst0_ref, dst1_ref,
        # staged exp and linear prefix rows P(base+j) and P(base+j+1)
        pe0_ref, pe1_ref, pes0_ref, pes1_ref,
        pl0_ref, pl1_ref, pls0_ref, pls1_ref,
        k_ref, n_ref, dst_out_ref, ts_out_ref):
    te = ts0_ref.shape[0]
    ts = _row(ts0_ref, ts1_ref)
    dst = _row(dst0_ref, dst1_ref)
    pe = _row(pe0_ref, pe1_ref)
    pes = _row(pes0_ref, pes1_ref)
    pl_ = _row(pl0_ref, pl1_ref)
    pls = _row(pls0_ref, pls1_ref)

    pos, hi, c, n = _cutoff(time_ref, lo_ref, hi_ref, ts)
    u = u_ref[...]
    fb = c + index_uniform(u, n)          # uniform bias == weight fallback

    # exponential: smallest j in [c, hi) with P(j+1) >= target, by counting
    # over the shifted row. P(hi) must come from the shifted row (ps[hi-1]):
    # reading pe[hi] yields 0 when hi == 2·TE (exact-fit region, §2.4.3).
    pe_c = _onehot_f32(pe, pos, c)
    pe_hi = _onehot_f32(pes, pos, hi - 1)
    total_e = pe_hi - pe_c
    target_e = pe_c + u * total_e
    below_e = (pos >= c) & (pos < hi) & (pes < target_e)
    k_exp = jnp.where(total_e > 0, c + _count_true(below_e), fb)

    # linear: S(j) = (PL(j+1) − PL(c)) − (j+1−c)·δ, δ = ts_c − t_base(v)
    ts_c = _onehot_i32(ts, pos, c)
    delta = (ts_c - tbase_ref[...]).astype(jnp.float32)
    pl_c = _onehot_f32(pl_, pos, c)
    pl_hi = _onehot_f32(pls, pos, hi - 1)
    s = (pls - pl_c) - (pos + 1 - c).astype(jnp.float32) * delta
    total_l = (pl_hi - pl_c) - (hi - c).astype(jnp.float32) * delta
    below_l = (pos >= c) & (pos < hi) & (s < u * total_l)
    k_lin = jnp.where(total_l > 0, c + _count_true(below_l), fb)

    code = code_ref[...]
    k = jnp.where(code == BIAS_UNIFORM, fb,
                  jnp.where(code == BIAS_LINEAR, k_lin, k_exp))
    _finalize(k, n, pos, dst, ts, 2 * te - 1, k_ref, n_ref, dst_out_ref,
              ts_out_ref)


# ---------------------------------------------------------------------------
# Tier L: sweep the edge window, carrying per-lane state in VMEM scratch
# ---------------------------------------------------------------------------
#
# Grid (T, MAXB): for tile t the second axis stages blocks blo[t]..bhi[t]
# of the node-ts view (index map min(blo+j, bhi); steps past the span are
# pl.when-skipped). All positions are *global*. One sweep suffices:
#
#   * cnt accumulates the cutoff count; the candidate c = a + cnt equals
#     the seen-range end until the true cutoff's block is staged, where it
#     finalizes. Every one-hot keyed on c (prefix/ts capture) and every
#     mask (pos >= c) is therefore empty in earlier blocks — the candidate
#     self-masks — and correct from the finalizing block on.
#   * the weight-mode pick count is monotone (prefix rows are
#     nondecreasing), so k = c + count stabilizes in the block containing
#     the pick; the gather one-hot keyed on the current k fires exactly
#     once, in that block (before it, clip(k, c, ·) >= c >= seen end).
#   * P(b) is a per-lane O(1) gather from the global prefix arrays done
#     outside the kernel (pb_e/pb_l inputs) — the same values the engine's
#     binary search reads, preserving bit-identity.


def _big_prologue(blo_ref, bhi_ref, te):
    t_id = pl.program_id(0)
    j = pl.program_id(1)
    blk = jnp.minimum(blo_ref[t_id] + j, bhi_ref[t_id])
    live = (blo_ref[t_id] + j) <= bhi_ref[t_id]
    pos = blk * te + jax.lax.broadcasted_iota(jnp.int32, (1, te), 1)
    return j, live, pos


def _zero_refs(*refs):
    for r in refs:
        r[...] = jnp.zeros_like(r[...])


def _big_kernel_index(
        blo_ref, bhi_ref,
        # per-walk inputs [TW, 1]; a/b are global region bounds (0 for
        # tier-S lanes sharing the tile — their garbage is merged out)
        a_ref, b_ref, time_ref, u_ref, code_ref,
        # one staged edge block [TE]
        ts_ref, dst_ref,
        # outputs [TW, 1]
        k_ref, n_ref, dst_out_ref, ts_out_ref,
        # scratch [TW, 1]
        cnt_ref):
    te = ts_ref.shape[0]
    j, live, pos = _big_prologue(blo_ref, bhi_ref, te)

    @pl.when(j == 0)
    def _init():
        _zero_refs(cnt_ref, k_ref, n_ref, dst_out_ref, ts_out_ref)

    @pl.when(live)
    def _step():
        a = a_ref[...]
        b = b_ref[...]
        ts = _row(ts_ref)
        in_region = (pos >= a) & (pos < b)
        cnt_ref[...] = cnt_ref[...] + _count_true(
            in_region & (ts <= time_ref[...]))
        c = a + cnt_ref[...]
        n = b - c
        k = c + index_pick_lanes(code_ref[...], u_ref[...], n)
        dst_out_ref[...] = dst_out_ref[...] + _onehot_i32(
            _row(dst_ref), pos, k)
        ts_out_ref[...] = ts_out_ref[...] + _onehot_i32(ts, pos, k)
        k_ref[...] = k
        n_ref[...] = n


def _big_kernel_weight(
        blo_ref, bhi_ref,
        a_ref, b_ref, time_ref, u_ref, code_ref, tbase_ref,
        pbe_ref, pbl_ref,                 # P(b): pexp[b], plin[b] per lane
        ts_ref, dst_ref, pe_ref, pes_ref, pl_ref, pls_ref,
        k_ref, n_ref, dst_out_ref, ts_out_ref,
        # scratch [TW, 1]: cutoff count, P(c) captures, ts_c, pick counts
        cnt_ref, pce_ref, pcl_ref, tsc_ref, pke_ref, pkl_ref):
    te = ts_ref.shape[0]
    j, live, pos = _big_prologue(blo_ref, bhi_ref, te)

    @pl.when(j == 0)
    def _init():
        _zero_refs(cnt_ref, pce_ref, pcl_ref, tsc_ref, pke_ref, pkl_ref,
                   k_ref, n_ref, dst_out_ref, ts_out_ref)

    @pl.when(live)
    def _step():
        a = a_ref[...]
        b = b_ref[...]
        u = u_ref[...]
        ts = _row(ts_ref)
        in_region = (pos >= a) & (pos < b)
        cnt_ref[...] = cnt_ref[...] + _count_true(
            in_region & (ts <= time_ref[...]))
        c = a + cnt_ref[...]
        n = b - c

        # capture P(c)/ts_c in the block where c finalizes (self-masking:
        # until then c sits at/past the end of the seen range)
        pce_ref[...] = pce_ref[...] + _onehot_f32(_row(pe_ref), pos, c)
        pcl_ref[...] = pcl_ref[...] + _onehot_f32(_row(pl_ref), pos, c)
        tsc_ref[...] = tsc_ref[...] + _onehot_i32(ts, pos, c)

        pick_region = (pos >= c) & (pos < b)
        # exponential: count P(j+1) < target over [c, b)
        total_e = pbe_ref[...] - pce_ref[...]
        target_e = pce_ref[...] + u * total_e
        pke_ref[...] = pke_ref[...] + _count_true(
            pick_region & (_row(pes_ref) < target_e))
        # linear: count S(j) < u·total over [c, b)
        delta = (tsc_ref[...] - tbase_ref[...]).astype(jnp.float32)
        s = (_row(pls_ref) - pcl_ref[...]) \
            - (pos + 1 - c).astype(jnp.float32) * delta
        total_l = (pbl_ref[...] - pcl_ref[...]) \
            - n.astype(jnp.float32) * delta
        pkl_ref[...] = pkl_ref[...] + _count_true(
            pick_region & (s < u * total_l))

        # per-lane k, matching samplers.py expression order + clip exactly
        fb = c + index_uniform(u, n)
        k_exp = jnp.where(total_e > 0, c + pke_ref[...], fb)
        k_lin = jnp.where(total_l > 0, c + pkl_ref[...], fb)
        code = code_ref[...]
        k = jnp.where(code == BIAS_UNIFORM, fb,
                      jnp.where(code == BIAS_LINEAR, k_lin, k_exp))
        k = jnp.clip(k, c, jnp.maximum(b - 1, c))

        dst_out_ref[...] = dst_out_ref[...] + _onehot_i32(
            _row(dst_ref), pos, k)
        ts_out_ref[...] = ts_out_ref[...] + _onehot_i32(ts, pos, k)
        k_ref[...] = k
        n_ref[...] = n


# ---------------------------------------------------------------------------
# Dispatch wrapper: tier split, both kernels, merge
# ---------------------------------------------------------------------------


def fused_walk_step(index: TemporalIndex, s_node: jax.Array,
                    s_time: jax.Array, code: jax.Array, u: jax.Array,
                    mode: str, cfg: SchedulerConfig,
                    *, interpret: bool | None = None) -> FusedStepResult:
    """Fused hop for walks sorted by node, with per-lane int32 bias codes.

    Splits lanes by the same degree statistic ``dispatch_stats`` reports
    (region span vs the staged 2·tile_edges window, evaluated against the
    tile's actual anchor), runs tier S in one staged pass and tier L as an
    edge-window sweep, and merges by mask. Returns global pick positions,
    neighborhood sizes, and the gathered ``dst``/``ts`` — no jnp fallback.
    """
    interpret = resolve_interpret(interpret)
    if mode not in ("index", "weight"):
        raise ValueError(f"unknown sampler mode {mode!r}")
    W = s_node.shape[0]
    E = index.edge_capacity
    TW, TE = cfg.tile_walks, cfg.tile_edges
    if W % TW or E % TE:
        raise ValueError(f"walks {W} / edges {E} not multiples of tile "
                         f"({TW}, {TE})")
    if E // TE < 2:
        raise ValueError(f"edge capacity {E} must span >= 2 tiles of {TE}")
    T = W // TW
    MAXB = E // TE

    from jax.experimental.pallas import tpu as pltpu

    a, b = node_range(index, s_node)
    # --- tier split: same task table as the seed tiled path --------------
    a_t = a.reshape(T, TW)
    b_t = b.reshape(T, TW)
    base_blocks = jnp.clip(jnp.min(a_t, axis=1) // TE, 0, MAXB - 2)
    base = base_blocks * TE
    lo = (a_t - base[:, None]).reshape(W)
    hi = (b_t - base[:, None]).reshape(W)
    # hi == 2·TE is an exact-fit in-tile region; the clips only bound the
    # garbage of tier-L lanes, whose tier-S output is merged out below
    big = (lo < 0) | (hi > 2 * TE)
    lo_k = jnp.clip(lo, 0, 2 * TE)
    hi_k = jnp.clip(hi, 0, 2 * TE)
    nc = index.node_capacity
    tbase = index.node_tbase[jnp.clip(s_node, 0, nc - 1)]
    base_blocks = base_blocks.astype(jnp.int32)

    def col(x):
        return x.reshape(W, 1)

    walk_spec = pl.BlockSpec((TW, 1), lambda i, base_: (i, 0))
    edge_spec0 = pl.BlockSpec((TE,), lambda i, base_: (base_[i],))
    edge_spec1 = pl.BlockSpec((TE,), lambda i, base_: (base_[i] + 1,))
    out_shape = [jax.ShapeDtypeStruct((W, 1), jnp.int32) for _ in range(4)]

    # --- tier S: one staged pass ----------------------------------------
    if mode == "index":
        kernel_s = _small_kernel_index
        walk_in_s = (s_time, lo_k, hi_k, u, code)
        edge_in_s = (index.ns_ts[:E], index.ns_ts[:E],
                     index.ns_dst[:E], index.ns_dst[:E])
        n_edge_s = 2
    else:
        kernel_s = _small_kernel_weight
        walk_in_s = (s_time, lo_k, hi_k, u, code, tbase)
        edge_in_s = (index.ns_ts[:E], index.ns_ts[:E],
                     index.ns_dst[:E], index.ns_dst[:E],
                     index.pexp[:E], index.pexp[:E],
                     index.pexp[1:E + 1], index.pexp[1:E + 1],
                     index.plin[:E], index.plin[:E],
                     index.plin[1:E + 1], index.plin[1:E + 1])
        n_edge_s = 6
    grid_s = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T,),
        in_specs=[walk_spec] * len(walk_in_s)
        + [edge_spec0, edge_spec1] * n_edge_s,
        out_specs=[walk_spec] * 4,
    )
    k_s, n_s, dst_s, ts_s = (o.reshape(W) for o in pl.pallas_call(
        kernel_s, grid_spec=grid_s, out_shape=out_shape,
        interpret=interpret)(base_blocks, *map(col, walk_in_s), *edge_in_s))

    # --- tier L: edge-window sweep ---------------------------------------
    ab_blk = (a // TE).reshape(T, TW)
    bb_blk = (jnp.maximum(b - 1, a) // TE).reshape(T, TW)
    big_t = big.reshape(T, TW)
    has_big = jnp.any(big_t, axis=1)
    blo = jnp.where(has_big,
                    jnp.min(jnp.where(big_t, ab_blk, MAXB - 1), axis=1), 0)
    bhi = jnp.where(has_big, jnp.max(jnp.where(big_t, bb_blk, 0), axis=1), 0)
    bhi = jnp.maximum(bhi, blo).astype(jnp.int32)
    blo = blo.astype(jnp.int32)
    a_big = jnp.where(big, a, 0)
    b_big = jnp.where(big, b, 0)

    walk_spec_l = pl.BlockSpec((TW, 1), lambda t, j, blo_, bhi_: (t, 0))
    edge_spec_l = pl.BlockSpec(
        (TE,), lambda t, j, blo_, bhi_: (jnp.minimum(blo_[t] + j, bhi_[t]),))
    scratch_i32 = pltpu.VMEM((TW, 1), jnp.int32)
    scratch_f32 = pltpu.VMEM((TW, 1), jnp.float32)
    if mode == "index":
        kernel_l = _big_kernel_index
        walk_in_l = (a_big, b_big, s_time, u, code)
        edge_in_l = (index.ns_ts[:E], index.ns_dst[:E])
        scratch_l = [scratch_i32]
    else:
        kernel_l = _big_kernel_weight
        walk_in_l = (a_big, b_big, s_time, u, code, tbase,
                     index.pexp[b_big], index.plin[b_big])
        edge_in_l = (index.ns_ts[:E], index.ns_dst[:E],
                     index.pexp[:E], index.pexp[1:E + 1],
                     index.plin[:E], index.plin[1:E + 1])
        scratch_l = [scratch_i32, scratch_f32, scratch_f32, scratch_i32,
                     scratch_i32, scratch_i32]
    grid_l = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, MAXB),
        in_specs=[walk_spec_l] * len(walk_in_l)
        + [edge_spec_l] * len(edge_in_l),
        out_specs=[walk_spec_l] * 4,
        scratch_shapes=scratch_l,
    )
    k_l, n_l, dst_l, ts_l = (o.reshape(W) for o in pl.pallas_call(
        kernel_l, grid_spec=grid_l, out_shape=out_shape,
        interpret=interpret)(blo, bhi, *map(col, walk_in_l), *edge_in_l))

    # --- merge ------------------------------------------------------------
    tile_of_walk = jnp.arange(W, dtype=jnp.int32) // TW
    k_sg = jnp.where(n_s > 0, base_blocks[tile_of_walk] * TE + k_s, 0)
    has_l = n_l > 0
    k = jnp.where(big, jnp.where(has_l, k_l, 0), k_sg)
    n = jnp.where(big, n_l, n_s)
    dst = jnp.where(big, jnp.where(has_l, dst_l, 0), dst_s)
    ts = jnp.where(big, jnp.where(has_l, ts_l, 0), ts_s)
    tiers = jnp.stack([
        jnp.sum((~big).astype(jnp.int32)),
        jnp.sum(big.astype(jnp.int32)),
        jnp.sum(jnp.where(has_big, bhi - blo + 1, 0)),
    ])
    return FusedStepResult(k=k, n=n, dst=dst, ts=ts, tiers=tiers)
