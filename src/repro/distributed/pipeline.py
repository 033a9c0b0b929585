"""Pipeline parallelism over a mesh axis (GPipe-style microbatching).

For multi-pod topologies the "pod" axis can carry pipeline STAGES instead of
data parallelism: cross-pod links are an order slower than intra-pod ICI, so
sending one (microbatch, d_model) activation per microbatch beats
all-reducing every gradient.  This module implements the mechanics with
``shard_map`` + ``collective_permute``:

- layer stack is split into S contiguous stages, stage s owned by pipe rank s;
- microbatches stream with the standard GPipe schedule (S + M - 1 ticks);
- each tick every rank runs its stage on its current microbatch then
  ppermutes activations to the next rank.

Bubble fraction = (S-1)/(S+M-1); compose with grad accumulation for the
backward (the driver below is forward-only, used for serving and tested for
exact equivalence with the unpipelined forward).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    mesh: Mesh,
    stage_fn: Callable,      # stage_fn(stage_params, x) -> x
    pipe_axis: str,
    n_microbatches: int,
):
    """Build pipelined_fn(stage_params, x) with params/batch sharded on
    ``pipe_axis``: params (S, ...) one stage per rank; x (M*mb, ...) split
    into M microbatches that flow through the S stages."""
    n_stages = mesh.shape[pipe_axis]

    def body(params_local, x_local):
        # params_local: (1, ...) this rank's stage; x_local: (M*mb_local...)
        # GPipe over M microbatches + (S-1) drain ticks.
        rank = jax.lax.axis_index(pipe_axis)
        stage_params = jax.tree.map(lambda p: p[0], params_local)
        m = n_microbatches
        mb = x_local.shape[0] // m
        micro = x_local.reshape((m, mb) + x_local.shape[1:])

        n_ticks = m + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, out = carry          # buf: (mb, ...) activation held here
            # rank 0 injects microbatch t (if in range) — other ranks use buf
            inject = jnp.where(t < m, jnp.clip(t, 0, m - 1), 0)
            x_in = jnp.where(rank == 0, micro[inject], buf)
            y = stage_fn(stage_params, x_in)
            # the LAST stage's output for microbatch (t - (S-1)) is final
            done_idx = t - (n_stages - 1)
            is_done = (rank == n_stages - 1) & (done_idx >= 0) & (done_idx < m)
            out = jax.lax.cond(
                is_done,
                lambda o: jax.lax.dynamic_update_slice(
                    o, y[None], (jnp.maximum(done_idx, 0),) + (0,) * y.ndim
                ),
                lambda o: o,
                out,
            )
            # rotate activations to the next stage
            buf = jax.lax.ppermute(y, pipe_axis, perm)
            return (buf, out), None

        buf0 = jnp.zeros_like(micro[0])
        out0 = jnp.zeros_like(micro)
        (_, out), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(n_ticks))
        # every rank holds only its writes; the last stage has the real data
        out = jax.lax.psum(out, pipe_axis) / 1.0  # ranks != last wrote zeros
        return out.reshape(x_local.shape)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(pipe_axis), P()),
        out_specs=P(),
        check_vma=False,
    )


def split_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-major split."""
    def rs(p):
        l = p.shape[0]
        return p.reshape((n_stages, l // n_stages) + p.shape[1:])

    return jax.tree.map(rs, stacked_params)
