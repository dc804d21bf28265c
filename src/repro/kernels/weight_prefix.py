"""Pallas TPU kernel: fused exp-weight + prefix-sum (paper Table 4 "weight").

The cumulative-weight precomputation is one of the paper's four ingestion
stages (up to 26% of per-batch time on Delicious). On TPU we fuse the
elementwise exp with the scan: the grid walks edge blocks **sequentially**
(TPU grids are sequential per core), carrying the running sum in an SMEM
scratch cell — a classic carry-propagating blocked scan with one HBM read
and one HBM write per element.

Block shape: (1, tile) over a (1, E) view — TPU wants ≥2-D refs with the
lane dim last; ``tile`` is a multiple of 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret


def _lane_cumsum(w: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the lane axis of a (1, tile) block.

    Hillis-Steele doubling: log2(tile) rounds of a lane rotate and a masked
    add. Mosaic has no ``cumsum`` lowering; rotates and selects it has.
    """
    from jax.experimental.pallas import tpu as pltpu

    n = w.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, w.ndim - 1)
    shift = 1
    while shift < n:
        w = w + jnp.where(lane >= shift, pltpu.roll(w, shift, w.ndim - 1),
                          0.0)
        shift *= 2
    return w


def _kernel(scale, dt_ref, valid_ref, out_ref, carry_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[0] = 0.0

    w = jnp.where(valid_ref[...] != 0,
                  jnp.exp(scale * dt_ref[...].astype(jnp.float32)), 0.0)
    c = _lane_cumsum(w)
    out_ref[...] = c + carry_ref[0]
    # w >= 0, so the block's last prefix value is its maximum
    carry_ref[0] = carry_ref[0] + jnp.max(c)


@functools.partial(jax.jit,
                   static_argnames=("scale", "tile", "interpret"))
def weight_prefix(dt: jax.Array, valid: jax.Array, *, scale: float = 1.0,
                  tile: int = 1024,
                  interpret: bool | None = None) -> jax.Array:
    """Fused exp+scan. Returns exclusive prefix P of length E+1, P[0]=0.

    ``interpret=None`` auto-detects (compiled on TPU, interpret elsewhere).
    """
    from jax.experimental.pallas import tpu as pltpu

    interpret = resolve_interpret(interpret)

    E = dt.shape[0]
    assert E % tile == 0, (E, tile)
    grid = (E // tile,)
    inc = pl.pallas_call(
        functools.partial(_kernel, scale),
        grid=grid,
        in_specs=[pl.BlockSpec((1, tile), lambda i: (0, i)),
                  pl.BlockSpec((1, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, E), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
    )(dt[None, :], valid.astype(jnp.int32)[None, :])
    return jnp.concatenate([jnp.zeros((1,), jnp.float32), inc[0]])
