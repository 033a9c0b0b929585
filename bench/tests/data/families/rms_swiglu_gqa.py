"""A second architecture family, for the benchmark's tests only: a
bidirectional pre-norm encoder with RMSNorm, a SwiGLU MLP, grouped-query
attention with per-head RMSNorm on queries and keys, and no biases (the
shape of the program's ``ce-tiny`` preset), scored on its first state.
Its plain reference is ``data/references/rms_swiglu_gqa.py``.

It adds no harness code: the tests reach it by extending the ``families``
and ``references`` packages' search paths, as a configuration of a new
architecture adds only its own files.
"""

import numpy as np

TINY = {
    "hidden_size": 64, "num_hidden_layers": 2, "head_dim": 8,
    "intermediate_size": 128,
    "deployment": {"n_items": 600, "query_len": 13, "item_len": 16,
                   "pair_len": 32, "n_queries": 64},
    "service": {"max_batch": 4, "batch_buckets": [1, 2, 4]},
}


def program_config(cfg: dict):
    from repro.configs.base import LMConfig

    return LMConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qk_norm=True, qkv_bias=False, rope_theta=cfg["rope_theta"],
        rms_eps=cfg["rms_norm_eps"], tie_embeddings=True, causal=False,
        act="swiglu", norm="rmsnorm", mlp_bias=False,
        max_seq_len=cfg["max_position_embeddings"], dtype=cfg["torch_dtype"],
        remat=False,
    )


def init_leaf(path: str, key, shape, dtype, cfg: dict):
    import jax
    import jax.numpy as jnp

    name = path.rsplit("/", 1)[-1]
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    normal = jax.random.normal(key, shape, jnp.float32)
    if name in ("w", "q_norm", "k_norm"):
        x = 1.0 + 0.02 * normal
    elif name == "embed":
        x = normal
    elif name in ("wq", "wk", "wv", "wg", "wu", "score_head"):
        x = normal / np.sqrt(d)
    elif name == "wo":
        x = normal / np.sqrt(cfg["num_attention_heads"] * cfg["head_dim"])
    elif name == "wd":
        x = normal / np.sqrt(f)
    else:
        raise ValueError(f"no initializer for weight {path}")
    return x.astype(dtype)


def scorer_kwargs(cfg: dict) -> dict:
    return dict(pad_id=cfg["pad_token_id"], cls_id=cfg["cls_token_id"],
                sep_id=cfg["sep_token_id"])


def flops_per_pair(cfg: dict) -> float:
    """Per token and layer: Q and O over H heads and K, V over the KV heads
    (d hd (2 H + 2 H_kv) MACs), the three SwiGLU matrices (3 d f) and
    attention over the pair's L keys (2 L H hd); a MAC is 2 FLOPs."""
    d, f, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    h, h_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    seq = cfg["deployment"]["pair_len"]
    per_token = d * hd * (2 * h + 2 * h_kv) + 3 * d * f + 2 * seq * h * hd
    return 2.0 * per_token * cfg["num_hidden_layers"] * seq


def attention(cfg: dict) -> dict:
    return dict(layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
                qk_head_dim=cfg["head_dim"], v_head_dim=cfg["head_dim"],
                seq_len=cfg["deployment"]["pair_len"], causal=False)
