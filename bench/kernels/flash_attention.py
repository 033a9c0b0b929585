"""``flash_attention``: attention over one bucket of pairs.

One call covers one layer of one CE batch: ``pairs`` sequences of
``seq_len`` tokens, ``heads`` heads, queries and keys of ``head_dim`` and
values of ``v_head_dim`` (``head_dim`` where not given, as in BERT; 128
against 192 in latent attention).  FLOPs are QK^T and PV over the
(query, key) pairs the mask keeps: n = L^2, or L (L + 1) / 2 under a causal
mask, each costing d_qk + d_v MACs a head.  Bytes are Q, K, V read once and
O written once in the activation dtype: L H (2 d_qk + 2 d_v) a pair.
"""


def cost(pairs: int, seq_len: int, heads: int, head_dim: int,
         dtype_bytes: int = 2, v_head_dim=None, causal: bool = False) -> tuple:
    d_v = head_dim if v_head_dim is None else v_head_dim
    n = seq_len * (seq_len + 1) / 2 if causal else seq_len * seq_len
    flops = 2.0 * pairs * heads * n * (head_dim + d_v)
    nbytes = 2.0 * pairs * seq_len * heads * (head_dim + d_v) * dtype_bytes
    return flops, nbytes
