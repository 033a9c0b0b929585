"""jit'd public wrapper for the embedding-bag kernel (``interpret=None``:
the platform decides, see ``kernels/backend.py``)."""

from __future__ import annotations

from functools import partial

import jax

from .kernel import embedding_bag


@partial(jax.jit, static_argnames=("mode", "interpret"))
def embedding_bag_op(table, hot_ids, *, mode: str = "sum", interpret=None):
    return embedding_bag(table, hot_ids, mode=mode, interpret=interpret)
