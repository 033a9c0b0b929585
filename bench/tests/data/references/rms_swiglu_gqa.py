"""Plain float32 forward of the tests' second family (``families/
rms_swiglu_gqa.py``): a bidirectional pre-norm encoder, RMSNorm, Q/K/V/O
without biases, per-head RMSNorm on queries and keys before RoPE, grouped
query attention (each KV head serves H / H_kv query heads), a SwiGLU MLP
(silu(x wg) * (x wu)) wd, a final RMSNorm and a linear score head on the
first state.  Matmuls at HIGHEST precision; ``control=True`` rounds every
matmul operand to float8 e4m3, as the BERT reference's control does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dot(spec, a, b, control):
    if control:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (n, L, H, hd); rotate-half RoPE at positions 0..L-1."""
    hd, seq = x.shape[-1], x.shape[1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def forward(params, tokens, cfg: dict, control: bool = False):
    """(n, L) int32 pair tokens -> (n,) float32 scores."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    eps, theta, hd = cfg["rms_norm_eps"], cfg["rope_theta"], cfg["head_dim"]
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    valid = tokens != cfg["pad_token_id"]
    x = p["embed"][tokens]

    def layer(x, lp):
        a, m = lp["attn"], lp["mlp"]
        h = _rms(x, lp["ln1"]["w"], eps)
        q = _rope(_rms(_dot("nld,dhk->nlhk", h, a["wq"], control), a["q_norm"], eps), theta)
        k = _rope(_rms(_dot("nld,dhk->nlhk", h, a["wk"], control), a["k_norm"], eps), theta)
        v = _dot("nld,dhk->nlhk", h, a["wv"], control)
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        logits = _dot("nqhk,nlhk->nhql", q, k, control) / np.sqrt(hd)
        logits = jnp.where(valid[:, None, None, :], logits, -1e30)
        o = _dot("nhql,nlhk->nqhk", jax.nn.softmax(logits, axis=-1), v, control)
        x = x + _dot("nqhk,hkd->nqd", o, a["wo"], control)
        h = _rms(x, lp["ln2"]["w"], eps)
        u = (jax.nn.silu(_dot("nld,df->nlf", h, m["wg"], control))
             * _dot("nld,df->nlf", h, m["wu"], control))
        return x + _dot("nlf,fd->nld", u, m["wd"], control), None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    first = _rms(x, p["final_norm"]["w"], eps)[:, 0]
    return _dot("nd,do->no", first, p["score_head"], control)[:, 0]


@functools.lru_cache(maxsize=8)
def _jitted(cfg_key, control):
    cfg = dict(cfg_key)
    return jax.jit(lambda params, tokens: forward(params, tokens, cfg, control))


def scores(params, pair_tokens: np.ndarray, cfg: dict, block: int = 32,
           control: bool = False) -> np.ndarray:
    """Scores of (n, L) pair rows, ``block`` rows per call."""
    keys = ("rms_norm_eps", "rope_theta", "head_dim", "num_attention_heads",
            "num_key_value_heads", "pad_token_id")
    fn = _jitted(tuple((k, cfg[k]) for k in keys), control)
    n = pair_tokens.shape[0]
    rows = np.concatenate([pair_tokens, np.repeat(pair_tokens[:1], -n % block, 0)])
    out = [np.asarray(fn(params, jnp.asarray(rows[i:i + block])))
           for i in range(0, rows.shape[0], block)]
    return np.concatenate(out)[:n]


def pair_tokens(q_tokens: np.ndarray, item_tokens: np.ndarray, cfg: dict,
                pair_len: int) -> np.ndarray:
    """[CLS] q [SEP] item [SEP], padded with the pad id to ``pair_len``."""
    n = q_tokens.shape[0]
    fill = lambda tok, w: np.full((n, w), tok, np.int32)
    body = np.concatenate([
        fill(cfg["cls_token_id"], 1), q_tokens, fill(cfg["sep_token_id"], 1),
        item_tokens, fill(cfg["sep_token_id"], 1),
    ], axis=1).astype(np.int32)
    return np.concatenate([body, fill(cfg["pad_token_id"], pair_len - body.shape[1])],
                          axis=1)
