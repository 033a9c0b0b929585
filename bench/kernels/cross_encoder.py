"""Model FLOPs of one cross-encoder pair (encoder forward, no backward).

Per token and layer: the Q, K, V and output projections (4 d^2 MACs), the
two MLP matrices (2 d d_ff MACs), and attention over the pair's L keys
(QK^T and PV, 2 L d MACs).  A MAC is 2 FLOPs.  Embedding gathers, norms,
softmax and the score head are left out (under 0.1% at these widths).
"""


def flops_per_token(d_model: int, d_ff: int, n_layers: int, seq_len: int) -> float:
    per_layer = 2 * (4 * d_model * d_model + 2 * d_model * d_ff) + 4 * seq_len * d_model
    return float(per_layer * n_layers)


def flops_per_pair(d_model: int, d_ff: int, n_layers: int, seq_len: int) -> float:
    return flops_per_token(d_model, d_ff, n_layers, seq_len) * seq_len


def flops_per_pair_of(cfg: dict) -> float:
    """The same count from a benchmark configuration file."""
    return flops_per_pair(
        cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"],
        cfg["deployment"]["pair_len"],
    )
