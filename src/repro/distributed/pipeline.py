"""Pipeline parallelism over the pod axis (GPipe schedule, shard_map).

Mechanism: stages are laid out along a mesh axis; each scheduling tick,
every stage processes the microbatch it holds and ``ppermute``s its
activation to the next stage. With M microbatches and P stages the loop
runs M + P − 1 ticks; stage s is busy for M of them (the usual GPipe
bubble (P−1)/(M+P−1)).

The multi-pod mesh's ``pod`` axis (size 2) hosts stages; within a pod the
usual data/model sharding applies unchanged — PP composes with the
DP/TP/EP/SP schemes of sharding.py. This module provides the schedule for
an arbitrary per-stage apply function plus a reference implementation
used by the correctness test (pipeline == sequential); wiring a specific
architecture's segments onto stages is a config concern
(stage boundary = segments list split).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_forward(mesh: Mesh, axis: str, stage_fn: Callable,
                  stage_params, x_microbatches):
    """Run ``stage_fn`` as a P-stage pipeline over mesh axis ``axis``.

    Args:
      stage_fn: (params_one_stage, x) -> y, applied by every stage.
      stage_params: pytree with leading stage axis (sharded over ``axis``).
      x_microbatches: [M, mb, ...] microbatched input (replicated).

    Returns [M, mb, ...] pipeline output (replicated).
    """
    num_stages = mesh.shape[axis]
    M = x_microbatches.shape[0]
    ticks = M + num_stages - 1

    def per_stage(params_st, xs):
        stage = jax.lax.axis_index(axis)
        params_local = jax.tree.map(lambda a: a[0], params_st)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)          # activation in flight
        outs = jnp.zeros((M,) + mb_shape, xs.dtype)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any); others use received
            feed = jnp.where(t < M, t, M - 1)
            x_in = jnp.where(stage == 0,
                             xs[feed],
                             buf)
            y = stage_fn(params_local, x_in)
            # active window for this stage at tick t: stage <= t < stage+M
            active = (t >= stage) & (t < stage + M)
            y = jnp.where(active, y, buf)
            # last stage writes its result for microbatch (t - P + 1)
            out_idx = t - (num_stages - 1)
            is_out = (stage == num_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                is_out,
                lambda o: o.at[jnp.maximum(out_idx, 0)].set(y),
                lambda o: o, outs)
            # shift activations forward one stage
            perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast to all
        outs = jax.lax.psum(
            jnp.where(stage == num_stages - 1, outs, 0.0), axis)
        return outs[None]

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params), P())
    fn = shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                   out_specs=P(axis), check_vma=False)
    out = fn(stage_params, x_microbatches)
    # post-psum every stage holds identical outputs; take one replica
    return out[0]


def sequential_reference(stage_fn, stage_params, x_microbatches):
    """Oracle: apply all stages in order, no pipelining."""
    num_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def one(x):
        for s in range(num_stages):
            p = jax.tree.map(lambda a: a[s], stage_params)
            x = stage_fn(p, x)
        return x

    return jax.vmap(one)(x_microbatches)
