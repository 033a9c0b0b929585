"""EmbeddingBag Pallas TPU kernel (scalar-prefetch gather + reduce).

The recsys hot path: out[b] = sum_h table[ids[b, h]].  JAX has no native
EmbeddingBag; the jnp reference (``repro.models.recsys.embedding``) does
take + segment_sum which round-trips the (B·H, dim) gathered rows through
HBM.  Here the bag ids are *scalar-prefetched* so the BlockSpec index_map
can steer the table DMA directly: grid (B, H), each step DMAs exactly one
table row HBM->VMEM and accumulates into the bag's output block — the
gathered rows never materialize.

This is the canonical TPU embedding-gather pattern (PrefetchScalarGridSpec);
rows arrive via the same double-buffered pipeline as any other BlockSpec
stream, so consecutive row fetches overlap with the adds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import resolve_interpret


def _bag_kernel(ids_ref, table_row_ref, out_ref, acc_ref, *, n_hot: int, mode: str):
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # f32 accumulation regardless of table dtype (bf16 tables lose ~2^-8
    # per add otherwise; the accumulator lives in VMEM scratch)
    acc_ref[...] += table_row_ref[...].astype(jnp.float32)

    @pl.when(h == n_hot - 1)
    def _finalize():
        acc = acc_ref[...]
        if mode == "mean":
            acc = acc / n_hot
        out_ref[...] = acc.astype(out_ref.dtype)


def embedding_bag(
    table: jax.Array,     # (rows, dim)
    hot_ids: jax.Array,   # (B, H) int32
    *,
    mode: str = "sum",
    interpret: bool | None = None,
) -> jax.Array:
    """Fixed-width multi-hot bag lookup -> (B, dim)."""
    b, n_hot = hot_ids.shape
    rows, dim = table.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_hot),
        in_specs=[
            # one table row per step, steered by the prefetched ids
            pl.BlockSpec((1, dim), lambda bi, hi, ids: (ids[bi, hi], 0)),
        ],
        out_specs=pl.BlockSpec((1, dim), lambda bi, hi, ids: (bi, 0)),
        scratch_shapes=[pltpu.VMEM((1, dim), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_bag_kernel, n_hot=n_hot, mode=mode),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, dim), table.dtype),
        interpret=resolve_interpret(interpret),
    )(hot_ids, table)
