"""``flash_attention``: bidirectional attention over one bucket of pairs.

One call covers one layer of one CE batch: ``pairs`` sequences of
``seq_len`` tokens, ``heads`` heads of ``head_dim``.  FLOPs are QK^T and PV
(2 L^2 hd MACs per head); bytes are Q, K, V read once and O written once in
the activation dtype.
"""


def cost(pairs: int, seq_len: int, heads: int, head_dim: int,
         dtype_bytes: int = 2) -> tuple:
    flops = 4.0 * pairs * heads * seq_len * seq_len * head_dim
    nbytes = 4.0 * pairs * seq_len * heads * head_dim * dtype_bytes
    return flops, nbytes
