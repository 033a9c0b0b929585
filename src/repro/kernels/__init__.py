"""Pallas TPU kernels for the perf-critical compute layers.

- flash_attention: CE prefill attention (whole-sequence schedule for short
                   bidirectional pairs, blocked online softmax otherwise; GQA)
- approx_topk:     fused ADACUR approx-score GEMM + masked top-k
- embedding_bag:   scalar-prefetch gather+reduce for recsys tables

Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper),
ref.py (pure-jnp oracle).  Validated in interpret mode on CPU; Mosaic is
the TPU target.
"""
