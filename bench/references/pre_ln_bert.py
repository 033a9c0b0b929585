"""Plain float32 forward of the benchmark's BERT-family cross-encoder.

The architecture is the configuration file's: a bidirectional pre-LN
encoder (LayerNorm with bias, tanh GELU MLP with biases, Q/K/V/O with
biases, RoPE on queries and keys), a final LayerNorm, and a linear score
head on the [CLS] state.  Every matmul runs in float32 at HIGHEST
precision, one block of pairs at a time, with the layers in a scan.

``control=True`` computes the same forward with every matmul operand
rounded to float8 e4m3 (per-tensor absmax scale, float32 accumulation):
the precision below the configuration's bfloat16, the step that would
tempt a later change.

Weights are a nested dict: ``embed`` (V, d); ``layers`` stacked over a
leading layer axis with ``attn`` {wq, wk, wv (d, H, hd); wo (H, hd, d);
bq, bk, bv (H, hd)}, ``ln1``/``ln2`` {w, b}, ``mlp`` {wu (d, f), bu (f,),
wd (f, d), bd (d,)}; ``final_norm`` {w, b}; ``score_head`` (d, 1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x):
    """Round ``x`` to float8 e4m3 under a per-tensor absmax scale, back in
    float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _dot(spec, a, b, control):
    if control:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _ln(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["w"] + p["b"]


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
    eps, theta = cfg["layer_norm_eps"], cfg["rope_theta"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    valid = tokens != cfg["pad_token_id"]                       # (n, L)
    x = p["embed"][tokens]

    def layer(x, lp):
        a = lp["attn"]
        h = _ln(x, lp["ln1"], eps)
        q = _rope(_dot("nld,dhk->nlhk", h, a["wq"], control) + a["bq"], theta)
        k = _rope(_dot("nld,dhk->nlhk", h, a["wk"], control) + a["bk"], theta)
        v = _dot("nld,dhk->nlhk", h, a["wv"], control) + a["bv"]
        logits = _dot("nqhk,nlhk->nhql", q, k, control) / np.sqrt(hd)
        logits = jnp.where(valid[:, None, None, :], logits, -1e30)
        o = _dot("nhql,nlhk->nqhk", jax.nn.softmax(logits, axis=-1), v, control)
        x = x + _dot("nqhk,hkd->nqd", o, a["wo"], control)
        m = lp["mlp"]
        h = _ln(x, lp["ln2"], eps)
        u = jax.nn.gelu(_dot("nld,df->nlf", h, m["wu"], control) + m["bu"],
                        approximate=True)
        return x + _dot("nlf,fd->nld", u, m["wd"], control) + m["bd"], None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    cls = _ln(x, p["final_norm"], eps)[:, 0]
    return _dot("nd,do->no", cls, p["score_head"], control)[:, 0]


@functools.lru_cache(maxsize=8)
def _jitted(cfg_key, control):
    cfg = dict(cfg_key)
    return jax.jit(lambda params, tokens: forward(params, tokens, cfg, control))


def scores(params, pair_tokens: np.ndarray, cfg: dict, block: int = 32,
           control: bool = False) -> np.ndarray:
    """Scores of (n, L) pair rows, ``block`` rows per call (the last block
    padded with copies of the first row and cut off)."""
    keys = ("layer_norm_eps", "rope_theta", "hidden_size",
            "num_attention_heads", "pad_token_id")
    fn = _jitted(tuple((k, cfg[k]) for k in keys), control)
    n = pair_tokens.shape[0]
    pad = -n % block
    rows = np.concatenate([pair_tokens, np.repeat(pair_tokens[:1], pad, 0)])
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
