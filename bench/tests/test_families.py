"""The BERT family's hooks give what the harness computed before it
reached the architecture through a family: each expected value below was
recorded from the harness as it was then, with the code it ran."""

import hashlib
from types import SimpleNamespace

import numpy as np
import pytest

import cell
import check
from conftest import tiny

BERT_BASE, MINILM = "zeshel-yugioh.bert-base", "hotpotqa.minilm-l6"


def _cfg(name):
    return cell.load_json("configs", name)


def _lm_config(name, n_layers, d_model, d_ff, head_dim):
    from repro.configs.base import LMConfig

    return LMConfig(
        name=name, n_layers=n_layers, d_model=d_model, n_heads=12, n_kv_heads=12,
        d_ff=d_ff, vocab_size=30522, head_dim=head_dim, qk_norm=False,
        qkv_bias=True, rope_theta=10000.0, rms_eps=1e-06, tie_embeddings=True,
        causal=False, act="gelu", norm="layernorm", mlp_bias=True, moe=None,
        max_seq_len=512, dtype="bfloat16", remat=False, scan_layers=True,
    )


@pytest.mark.parametrize("config, want", [
    (BERT_BASE, (12, 768, 3072, 64)),
    (MINILM, (6, 384, 1536, 32)),
])
def test_program_config_is_the_recorded_one(config, want):
    assert cell.lm_config(_cfg(config)) == _lm_config(config, *want)


def _digest(tree) -> str:
    import jax

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# both configurations cut to the family's TINY have the same shapes and
# weight_seed, so the same weights
TINY_WEIGHTS = "82e6896896fcfe53859ae3c44cb5acd91f85db5e86240da95d8934f195bd19cb"


@pytest.mark.parametrize("config", [BERT_BASE, MINILM])
def test_weights_at_the_tiny_cut_are_the_recorded_ones(config):
    cfg = cell.merge(_cfg(config), tiny("closed", config)["config"])
    params = cell.make_weights(cell.lm_config(cfg), cfg, cell.seed_key(cfg["weight_seed"]))
    assert _digest(params) == TINY_WEIGHTS


@pytest.mark.parametrize("config, flops", [
    # 45.9 and 2.87 GFLOP: the hand counts of test_kernels.test_pair_flops_match_hand_count
    (BERT_BASE, 45902462976.0),
    (MINILM, 2868903936.0),
])
def test_flops_per_pair_are_the_recorded_ones(config, flops):
    assert cell.family(_cfg(config)).flops_per_pair(_cfg(config)) == flops


ENGINE = dict(k_q=150, rounds=5, budget=200, k_anchor=100, k_s=20, k_r=100,
              k_retrieve=50, tile=None, heads=12, pinv_rcond=0.0001)


@pytest.mark.parametrize("config, own", [
    (BERT_BASE, dict(n_items=10031, layers=12, seq_len=256, head_dim=64)),
    (MINILM, dict(n_items=5233329, layers=6, seq_len=128, head_dim=32)),
])
def test_engine_shape_keeps_the_recorded_keys(config, own):
    got = check.engine_shape(_cfg(config), SimpleNamespace(engine_cfg=None))
    old = dict(ENGINE, **own)
    assert {k: got[k] for k in old} == old
    assert set(got) - set(old) == {"v_head_dim", "causal"}
    assert got["v_head_dim"] == got["head_dim"] and got["causal"] is False
