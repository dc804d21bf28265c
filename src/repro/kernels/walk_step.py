"""Pallas TPU kernel: cooperative walk step (paper §2.4.3 smem panel).

One *task* = one tile of ``tile_walks`` walk lanes (sorted by current node)
plus a window of ``2 * tile_edges`` consecutive rows of the node-ts view
staged HBM→VMEM once per task via scalar-prefetched, data-dependent
BlockSpec index maps — the TPU analogue of the paper's "preload the node's
adjacency metadata into shared memory once per task".

TPU-native adaptation (recorded in DESIGN.md §2): the paper's per-walk
binary search over smem becomes a **dense compare-and-reduce** over the
staged tile. Each lane's temporal cutoff is

    c = lo + |{ j ∈ [lo, hi) : ts[j] ≤ t }|

computed as a [tile_walks, 2·tile_edges] vectorized compare + row-sum —
pure VPU/MXU work with zero per-lane gathers, which TPUs strongly prefer
over latency-bound pointer chasing. The weight-mode inverse CDF uses the
same counting trick over the staged prefix-sum rows, and the final edge
fetch is a one-hot select over the staged ``dst``/``ts`` rows.

Grid iteration on TPU is sequential per core; tasks are independent, so
the grid parallelizes across cores/megacore without interaction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.samplers import index_exponential, index_linear, index_uniform
from repro.kernels.runtime import resolve_interpret


def _count_true(mask: jax.Array) -> jax.Array:
    return jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)


def _onehot_pick(values_row: jax.Array, pos: jax.Array,
                 k: jax.Array) -> jax.Array:
    """Exact gather-by-one-hot: sum(where(pos == k, values, 0))."""
    sel = jnp.where(pos == k, values_row, jnp.zeros((), values_row.dtype))
    return jnp.sum(sel, axis=1, keepdims=True)


def _row(ref0, ref1) -> jax.Array:
    """Two staged 1-D edge blocks as one (1, 2·TE) lane row."""
    return jnp.concatenate([ref0[...][None, :], ref1[...][None, :]], axis=1)


def _kernel(mode: str, bias: str,
            # scalar prefetch
            base_ref,
            # per-walk tile inputs [TW, 1] (columns: see walk_step_tiled)
            time_ref, lo_ref, hi_ref, u_ref, tbase_ref,
            # staged edge-view windows, two consecutive blocks each [TE]
            ts0_ref, ts1_ref, dst0_ref, dst1_ref,
            px0_ref, px1_ref, ps0_ref, ps1_ref,
            # outputs [TW, 1]
            k_ref, n_ref, dst_out_ref, ts_out_ref):
    te = ts0_ref.shape[0]
    ts = _row(ts0_ref, ts1_ref)                               # [1, 2TE]
    dst = _row(dst0_ref, dst1_ref)

    t = time_ref[...]                                         # [TW, 1]
    lo = lo_ref[...]
    hi = hi_ref[...]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * te), 1)  # [1, 2TE]
    in_region = (pos >= lo) & (pos < hi)

    # temporal cutoff by dense count (ts ascending within [lo, hi))
    c = lo + _count_true(in_region & (ts <= t))
    n = hi - c
    u = u_ref[...]

    if mode == "index":
        if bias == "uniform":
            i = index_uniform(u, n)
        elif bias == "linear":
            i = index_linear(u, n)
        elif bias == "exponential":
            i = index_exponential(u, n)
        else:
            raise ValueError(bias)
        k = c + i
    elif mode == "weight":
        px = _row(px0_ref, px1_ref)                           # P(base+j)
        ps = _row(ps0_ref, ps1_ref)                           # P(base+j+1)
        p_c = _onehot_pick(px, pos, c)
        # P(hi) comes from the shifted row: ps[j] = P(base+j+1), so
        # P(hi) = ps[hi-1]. Reading px[hi] silently yields 0 when hi == 2·TE
        # (a region ending exactly at the staged window's edge — a legal
        # in-tile task), which would zero the neighborhood's weight mass.
        p_hi = _onehot_pick(ps, pos, hi - 1)
        if bias == "exponential":
            total = p_hi - p_c
            target = p_c + u * total
            # smallest j in [c, hi) with P(j+1) >= target, via counting
            below = (pos >= c) & (pos < hi) & (ps < target)
            k = c + _count_true(below)
            # underflowed mass -> uniform fallback (matches samplers.py)
            k = jnp.where(total > 0, k, c + index_uniform(u, n))
        elif bias == "linear":
            # S(j) = (PL(j+1) - PL(c)) - (j+1-c)·δ, δ = ts_c − t_base(v);
            # px/ps here carry the *linear* prefix rows; t_base(v) arrives
            # per walk in tbase_ref (a cheap node-level gather done outside).
            ts_c = _onehot_pick(ts, pos, c)
            delta = (ts_c - tbase_ref[...]).astype(jnp.float32)
            pl_c = p_c
            s = (ps - pl_c) - (pos + 1 - c).astype(jnp.float32) * delta
            total = (p_hi - pl_c) - (hi - c).astype(jnp.float32) * delta
            target = u * total
            below = (pos >= c) & (pos < hi) & (s < target)
            k = c + _count_true(below)
            k = jnp.where(total > 0, k, c + index_uniform(u, n))
        elif bias == "uniform":
            k = c + index_uniform(u, n)
        else:
            raise ValueError(bias)
    else:
        raise ValueError(mode)

    k = jnp.clip(k, 0, 2 * te - 1)
    has = n > 0
    k_ref[...] = jnp.where(has, k, 0)
    n_ref[...] = n
    dst_out_ref[...] = jnp.where(has, _onehot_pick(dst, pos, k), 0)
    ts_out_ref[...] = jnp.where(has, _onehot_pick(ts, pos, k), 0)


@functools.partial(jax.jit, static_argnames=(
    "mode", "bias", "tile_walks", "tile_edges", "interpret"))
def walk_step_tiled(ns_ts, ns_dst, pfx, pfx_shift,
                    base_blocks, time, lo, hi, u, tbase,
                    *, mode: str, bias: str, tile_walks: int,
                    tile_edges: int, interpret: bool | None = None):
    """Run the cooperative walk-step kernel over all tiles.

    Args:
      ns_ts / ns_dst: node-ts view rows, length E (multiple of tile_edges).
      pfx / pfx_shift: P(j) and P(j+1) prefix rows for the active weight
        bias (exp or linear), length E. Ignored for index mode (pass any
        array of the right shape).
      base_blocks: int32[T] block index (units of tile_edges) staged per task.
      time/lo/hi/u/tbase: per-walk arrays, length W = T * tile_walks,
        sorted by node; lo/hi are tile-local row offsets; tbase is the
        per-walk node t_base gather (used by the linear bias only).

    ``interpret=None`` auto-detects (compiled on TPU, interpret elsewhere).

    Returns (k_local, n, dst_pick, ts_pick) — k_local is tile-local.
    """
    interpret = resolve_interpret(interpret)
    W = time.shape[0]
    E = ns_ts.shape[0]
    TW, TE = tile_walks, tile_edges
    assert W % TW == 0 and E % TE == 0, (W, TW, E, TE)
    T = W // TW

    from jax.experimental.pallas import tpu as pltpu

    # per-walk arrays travel as (W, 1) columns in (TW, 1) blocks: a 1-D
    # block of tile_walks = 256 does not match the chip's 1-D tiling (1024)
    walk_spec = pl.BlockSpec((TW, 1), lambda i, base: (i, 0))
    edge_spec0 = pl.BlockSpec((TE,), lambda i, base: (base[i],))
    edge_spec1 = pl.BlockSpec((TE,), lambda i, base: (base[i] + 1,))

    kernel = functools.partial(_kernel, mode, bias)
    out_shape = [jax.ShapeDtypeStruct((W, 1), jnp.int32) for _ in range(4)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T,),
        in_specs=[walk_spec] * 5 + [edge_spec0, edge_spec1] * 4,
        out_specs=[walk_spec] * 4,
    )
    fn = pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                        interpret=interpret)
    cols = (x.reshape(W, 1) for x in (time, lo, hi, u, tbase))
    outs = fn(base_blocks, *cols, ns_ts, ns_ts, ns_dst, ns_dst,
              pfx, pfx, pfx_shift, pfx_shift)
    k, n, dpick, tpick = (o.reshape(W) for o in outs)
    return k, n, dpick, tpick
