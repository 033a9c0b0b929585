"""jit'd public wrapper for the flash attention kernel.

``interpret=None`` lets the platform decide (``kernels/backend.py``): the
compiled Mosaic kernel on a TPU, the Pallas interpreter elsewhere.
"""

from __future__ import annotations

from functools import partial

import jax

from .kernel import flash_attention


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_op(
    q, k, v, *, causal=True, block_q=128, block_k=128, interpret=None, kv_lens=None
):
    return flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, kv_lens=kv_lens,
    )
