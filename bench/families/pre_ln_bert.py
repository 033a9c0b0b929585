"""The BERT family (``reference: pre_ln_bert``): a bidirectional pre-LN
encoder with LayerNorm, tanh GELU and biases, scored on its [CLS] state
(``references/pre_ln_bert.py`` is its plain reference)."""

import numpy as np

# widths and lengths cut to a size a CPU test can run; the engine,
# service and traffic kinds stay
TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 2,
    "intermediate_size": 128,
    "deployment": {"n_items": 600, "query_len": 13, "item_len": 16,
                   "pair_len": 32, "n_queries": 64},
    "service": {"max_batch": 4, "batch_buckets": [1, 2, 4]},
}


def program_config(cfg: dict):
    """The program's encoder configuration for a benchmark config file."""
    from repro.configs.base import LMConfig

    heads = cfg["num_attention_heads"]
    return LMConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=heads, n_kv_heads=heads,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["hidden_size"] // heads, qkv_bias=True,
        rope_theta=cfg["rope_theta"], tie_embeddings=True, causal=False,
        act="gelu", norm="layernorm", mlp_bias=True,
        max_seq_len=cfg["max_position_embeddings"], dtype=cfg["torch_dtype"],
        remat=False,
    )


def init_leaf(path: str, key, shape, dtype, cfg: dict):
    import jax
    import jax.numpy as jnp

    name = path.rsplit("/", 1)[-1]
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    normal = jax.random.normal(key, shape, jnp.float32)
    if name == "w" and "norm" in path or name == "w" and "/ln" in path:
        x = 1.0 + 0.02 * normal
    elif name in ("b", "bq", "bk", "bv", "bu", "bd"):
        x = 0.02 * normal
    elif name == "embed":
        x = normal
    elif name in ("wq", "wk", "wv", "wu", "score_head"):
        x = normal / np.sqrt(d)
    elif name == "wo":
        x = normal / np.sqrt(d)
    elif name == "wd":
        x = normal / np.sqrt(f)
    else:
        raise ValueError(f"no initializer for weight {path}")
    return x.astype(dtype)


def scorer_kwargs(cfg: dict) -> dict:
    return dict(pad_id=cfg["pad_token_id"], cls_id=cfg["cls_token_id"],
                sep_id=cfg["sep_token_id"])


def flops_per_pair(cfg: dict) -> float:
    from kernels.cross_encoder import flops_per_pair_of

    return flops_per_pair_of(cfg)


def attention(cfg: dict) -> dict:
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return dict(layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
                qk_head_dim=hd, v_head_dim=hd,
                seq_len=cfg["deployment"]["pair_len"], causal=False)
