"""Operation and byte counts against hand counts."""

import json

import pytest

from run import BENCH, kernel_cost


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("config, gflop", [
    # BERT-base, 256 tokens: per token and layer 2 (4 d^2 + 2 d f) =
    # 14.16 MFLOP of projections and MLP + 4 L d = 0.79 MFLOP of attention;
    # x 12 layers x 256 tokens = 45.9 GFLOP
    ("zeshel-yugioh.bert-base", 45.9),
    # MiniLM-L6, 128 tokens: (3.54 + 0.20) MFLOP x 6 layers x 128 = 2.87
    ("hotpotqa.minilm-l6", 2.87),
])
def test_pair_flops_match_hand_count(config, gflop):
    got = kernel_cost("cross_encoder").flops_per_pair_of(_cfg(config)) / 1e9
    assert got == pytest.approx(gflop, abs=0.005)


def test_flash_cost_is_attention_share_of_pair():
    cfg = _cfg("zeshel-yugioh.bert-base")
    flops, nbytes = kernel_cost("flash_attention").cost(1, 256, 12, 64)
    assert flops == 4 * 256 * 256 * 768            # QK^T + PV, one layer
    assert nbytes == 4 * 256 * 768 * 2             # Q, K, V in, O out, bf16
    attn = 4 * 256 * 768 * 256                     # attention part of a layer
    assert flops == attn
    assert flops * cfg["num_hidden_layers"] < kernel_cost("cross_encoder").flops_per_pair_of(cfg)


def test_sweep_cost_counts_payload_and_mask():
    flops, nbytes = kernel_cost("approx_topk_sweep").cost(16, 200, 10031, 20, 6144, 4)
    assert flops == 2 * 16 * 200 * 10031
    payload, mask = 200 * 10031 * 4, 16 * 10031
    assert payload + mask < nbytes < payload + mask + 16 * 200 * 4 + 2 * 2 * 16 * 20 * 4 + 1


@pytest.mark.parametrize("pairs, seq_len, heads, head_dim", [
    (1, 256, 12, 64), (14000, 256, 12, 64), (123456, 128, 12, 32), (7, 33, 5, 24),
])
def test_flash_cost_under_defaults_is_the_bidirectional_formula(pairs, seq_len, heads, head_dim):
    """Equal head dims and no mask: 4 pairs H L^2 hd FLOPs and 4 pairs L H hd
    bytes, the counts every cell's flash roofline was read with, to the bit."""
    flops, nbytes = kernel_cost("flash_attention").cost(pairs, seq_len, heads, head_dim)
    assert flops == 4.0 * pairs * heads * seq_len * seq_len * head_dim
    assert nbytes == 4.0 * pairs * seq_len * heads * head_dim * 2
    assert (flops, nbytes) == kernel_cost("flash_attention").cost(
        pairs, seq_len, heads, head_dim, v_head_dim=head_dim, causal=False)


def test_flash_cost_causal_and_latent_hand_count():
    cost = kernel_cost("flash_attention").cost
    # one pair of 4 tokens, one head, q/k 192 and v 128 (latent attention):
    # a causal mask keeps 1 + 2 + 3 + 4 = 10 (query, key) pairs, each 192
    # MACs of QK^T and 128 of PV
    flops, nbytes = cost(1, 4, 1, 192, v_head_dim=128, causal=True)
    assert flops == 2 * 10 * (192 + 128)
    assert nbytes == 4 * (192 + 192 + 128 + 128) * 2      # q, k, v in, o out
    # without the mask all 16 pairs count; bytes do not depend on the mask
    assert cost(1, 4, 1, 192, v_head_dim=128) == (2 * 16 * (192 + 128), nbytes)
    # a layer of Moonlight's shape: 16 heads, 256-token causal pairs
    flops, nbytes = cost(1, 256, 16, 192, v_head_dim=128, causal=True)
    assert flops == 2 * 16 * (256 * 257 // 2) * 320
    assert nbytes == 256 * 16 * 640 * 2
