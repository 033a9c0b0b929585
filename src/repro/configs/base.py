"""Config dataclasses for every architecture family in the framework.

All configs are frozen dataclasses so they can be hashed as jit static
arguments and stored in checkpoint manifests.  Each assigned architecture
gets one module under ``repro.configs`` exporting ``CONFIG``; the registry
(``repro.configs.registry``) maps ``--arch`` ids to them.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Language models (dense + MoE)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts FFN configuration (GShard-style dense dispatch)."""

    n_experts: int
    top_k: int
    d_expert: int                    # per-expert FFN hidden dim
    n_shared_experts: int = 0        # DeepSeek/Moonlight-style shared experts
    first_k_dense: int = 0           # first K layers use a dense FFN instead
    d_ff_dense: int = 0              # hidden dim of those dense layers
    router_jitter: float = 0.0
    aux_loss_coef: float = 0.01
    capacity_factor: float = 1.25    # GShard per-expert capacity (drop beyond)


@dataclass(frozen=True)
class LMConfig:
    """Decoder (or encoder) transformer LM configuration."""

    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None   # defaults to d_model // n_heads
    qk_norm: bool = False            # Qwen3-style per-head RMSNorm on q,k
    qkv_bias: bool = False           # Qwen1.5-style bias on QKV projections
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    tie_embeddings: bool = False
    causal: bool = True              # False => encoder-only (bert4rec-style)
    act: str = "swiglu"              # "swiglu" | "gelu"
    norm: str = "rmsnorm"            # "rmsnorm" | "layernorm" (starcoder2)
    mlp_bias: bool = False           # bias on MLP projections (starcoder2)
    moe: Optional[MoEConfig] = None
    max_seq_len: int = 524288
    dtype: str = "bfloat16"          # activation / param dtype for serving
    remat: bool = True               # activation checkpointing in train_step
    scan_layers: bool = True         # lax.scan over stacked layer params

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def n_params(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS in the roofline)."""
        hd = self.resolved_head_dim
        emb = self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        attn = self.d_model * hd * (self.n_heads + 2 * self.n_kv_heads)  # qkv
        attn += self.n_heads * hd * self.d_model                          # out
        if self.qkv_bias:
            attn += hd * (self.n_heads + 2 * self.n_kv_heads)
        per_layer = attn
        if self.moe is None:
            n_ff = 3 if self.act == "swiglu" else 2
            per_layer += n_ff * self.d_model * self.d_ff
            total_ffn = per_layer * self.n_layers
        else:
            n_ff = 3 if self.act == "swiglu" else 2
            moe_ffn = n_ff * self.d_model * self.moe.d_expert * (
                self.moe.n_experts + self.moe.n_shared_experts
            ) + self.d_model * self.moe.n_experts  # router
            dense_ffn = n_ff * self.d_model * (self.moe.d_ff_dense or self.d_ff)
            n_moe = self.n_layers - self.moe.first_k_dense
            total_ffn = attn * self.n_layers + moe_ffn * n_moe + dense_ffn * self.moe.first_k_dense
        norms = self.n_layers * 2 * self.d_model + self.d_model
        return emb + total_ffn + norms

    def n_active_params(self) -> int:
        """Active (per-token) parameter count — used for MoE MODEL_FLOPS."""
        if self.moe is None:
            return self.n_params()
        hd = self.resolved_head_dim
        emb = self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        attn = self.d_model * hd * (self.n_heads + 2 * self.n_kv_heads)
        attn += self.n_heads * hd * self.d_model
        n_ff = 3 if self.act == "swiglu" else 2
        active_ffn = n_ff * self.d_model * self.moe.d_expert * (
            self.moe.top_k + self.moe.n_shared_experts
        )
        dense_ffn = n_ff * self.d_model * (self.moe.d_ff_dense or self.d_ff)
        n_moe = self.n_layers - self.moe.first_k_dense
        return (
            emb
            + attn * self.n_layers
            + active_ffn * n_moe
            + dense_ffn * self.moe.first_k_dense
        )


# ---------------------------------------------------------------------------
# GNN (NequIP)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class GNNConfig:
    name: str
    n_layers: int
    d_hidden: int                    # multiplicity per irrep channel
    l_max: int                       # max spherical-harmonic degree
    n_rbf: int                       # radial basis functions
    cutoff: float                    # radial cutoff (Angstrom)
    d_feat: int = 0                  # raw input node-feature dim (0 => species embed)
    n_species: int = 64
    equivariance: str = "E(3)-tensor-product"
    dtype: str = "float32"

    @property
    def irrep_dim(self) -> int:
        """Total feature dim per channel over l = 0..l_max: sum(2l+1)."""
        return sum(2 * l + 1 for l in range(self.l_max + 1))


# ---------------------------------------------------------------------------
# RecSys
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RecSysConfig:
    name: str
    kind: str                        # "bst" | "mind" | "bert4rec" | "dlrm"
    embed_dim: int
    n_items: int = 1_000_000         # item vocabulary (retrieval corpus)
    seq_len: int = 20                # user-history length (sequential models)
    n_heads: int = 8
    n_blocks: int = 1
    mlp_dims: Tuple[int, ...] = ()
    # MIND
    n_interests: int = 4
    capsule_iters: int = 3
    # DLRM
    n_dense: int = 0
    n_sparse: int = 0
    bot_mlp: Tuple[int, ...] = ()
    top_mlp: Tuple[int, ...] = ()
    table_sizes: Tuple[int, ...] = ()
    interaction: str = "dot"
    multihot_per_field: int = 1      # lookups per sparse field (embedding-bag size)
    dtype: str = "float32"


# ---------------------------------------------------------------------------
# Shapes: one named shape set per family (see configs/shapes.py)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LMShape:
    name: str
    kind: str          # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


@dataclass(frozen=True)
class GraphShape:
    name: str
    kind: str          # "full" | "minibatch" | "molecule"
    n_nodes: int
    n_edges: int
    d_feat: int = 0
    batch_nodes: int = 0
    fanout: Tuple[int, ...] = ()
    batch_graphs: int = 0


@dataclass(frozen=True)
class RecSysShape:
    name: str
    kind: str          # "train" | "serve" | "retrieval"
    batch: int
    n_candidates: int = 0


# ---------------------------------------------------------------------------
# ADACUR runtime config (the paper's technique)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AdaCURConfig:
    """Inference-time configuration for the multi-round adaptive retriever.

    Mirrors Algorithm 1 in the paper: a CE-call budget ``budget_ce`` split
    between ``k_anchor`` anchor items sampled over ``n_rounds`` rounds and
    ``budget_ce - k_anchor`` items re-ranked with exact CE scores.  With
    ``split_budget=False`` this is ADACUR^No-Split.
    """

    k_anchor: int = 100
    n_rounds: int = 5
    budget_ce: int = 200
    strategy: str = "topk"           # "topk" | "softmax" | "random"
    first_round: str = "random"      # "random" | "retriever"
    split_budget: bool = True
    k_retrieve: int = 100            # top-k to return
    softmax_temp: float = 1.0
    # Beyond-paper (motivated by the paper's own §3.2 oracle study, where an
    # ε-fraction of random anchors fixes TopK's diversity problem): mix
    # round_epsilon·k_s uniform-random anchors into every ADAPTIVE round.
    # 0.0 reproduces the paper's algorithm exactly.
    round_epsilon: float = 0.0
    incremental_pinv: bool = True    # beyond-paper: O(k_q k_i k_s) updates
    distributed_gather: bool = False # one-hot-matmul column gather (pod meshes)
    # --- static-shape round engine (core/engine.py) ------------------------
    # "unrolled": python loop over rounds, one trace per (cfg, shapes) — the
    #   seed behavior, works with any (even non-traceable) score_fn.
    # "fori": shape-invariant round body under lax.fori_loop/while_loop; the
    #   round count becomes a *runtime* operand, so changing n_rounds per
    #   call (adaptive round counts, arXiv 2405.03651) does not retrace.
    loop_mode: str = "unrolled"      # "unrolled" | "fori"
    # Route per-round anchor sampling and the final rerank-candidate
    # selection through the fused Pallas score->top-k kernel so the (B, N)
    # approximate score matrix is never materialized in HBM.
    use_fused_topk: bool = False
    fused_tile: int = 6144           # item-axis tile of the fused kernel
                                     # (the platform picks compiled Pallas on
                                     # TPU, the scan emulation on CPU)
    # Anytime ADACUR (fori mode only): stop early once the round-over-round
    # provisional top-k_retrieve candidate set overlap reaches 1 - tol.
    # 0.0 always runs the full round budget.
    early_exit_tol: float = 0.0
    # How the per-round item-axis work is staged (requires use_fused_topk):
    # "staged": one fused approx_topk_op pass per consumer — anchor sampling,
    #   and (in monitored/early-exit mode) a second pass for the provisional
    #   top-k — each re-streaming the payload from HBM.
    # "persistent": the whole round runs as ONE payload sweep through
    #   kernels/approx_topk/persistent.py — dequant + estimate GEMM + Gumbel
    #   top-k sampling + provisional top-k fused, with the round state
    #   (e_q, running top-k accumulators) VMEM-resident across item tiles.
    #   The monitored early-exit loop is additionally software-pipelined:
    #   round r+1's anchor sample and round r's provisional monitor share
    #   one sweep, halving payload passes per monitored round.  Rankings are
    #   bit-identical to "staged" in every loop mode (asserted by the parity
    #   and property suites).
    round_kernel: str = "staged"     # "staged" | "persistent"
    # Storage/streaming dtype of the R_anc payload the item-axis hot path
    # reads every round.  "int8" stores per-item-tile symmetric codes + fp32
    # scales (~4x fewer bytes; the fused kernel dequantizes tile-by-tile in
    # registers); "int4" packs two codes per byte (0.125x fp32 bytes) and
    # "fp8" stores float8_e4m3 codes (platform-gated); "bfloat16" halves the
    # payload with no extra state.  An index-backed retriever quantizes its
    # AnchorIndex once at from_index; a bare-r_anc retriever converts the
    # operand inside the trace (per call — prefer the index path at scale).
    # Exact CE scores, the pinv state and the final ranking stay fp32
    # throughout.
    payload_dtype: str = "float32"   # "float32"|"bfloat16"|"int8"|"int4"|"fp8"
    payload_tile: int = 512          # item-axis quantization tile (quantized)
    # Regularized pinv: adaptively-selected anchors are correlated, so the
    # anchor column matrix conditions much worse than a random subset
    # (measured ~13500 vs ~210); truncating tiny singular values keeps the
    # global approximation stable (see EXPERIMENTS.md §Repro).
    pinv_rcond: float = 1e-4

    def __post_init__(self):
        if self.k_anchor % self.n_rounds != 0:
            raise ValueError(
                f"k_anchor={self.k_anchor} must divide evenly into n_rounds={self.n_rounds}"
            )
        if self.split_budget and self.budget_ce < self.k_anchor:
            raise ValueError("budget_ce must cover k_anchor when splitting budget")
        if self.loop_mode not in ("unrolled", "fori"):
            raise ValueError(f"unknown loop_mode '{self.loop_mode}'")
        if self.early_exit_tol > 0.0 and self.loop_mode != "fori":
            raise ValueError("early_exit_tol requires loop_mode='fori'")
        if self.payload_dtype not in (
            "float32", "bfloat16", "int8", "int4", "fp8"
        ):
            raise ValueError(
                f"unknown payload_dtype '{self.payload_dtype}' "
                "(float32|bfloat16|int8|int4|fp8)"
            )
        if self.payload_tile <= 0:
            raise ValueError("payload_tile must be positive")
        if self.payload_dtype == "int4" and self.payload_tile % 2:
            raise ValueError("int4 payloads need an even payload_tile "
                             "(two codes pack per byte)")
        if self.round_kernel not in ("staged", "persistent"):
            raise ValueError(f"unknown round_kernel '{self.round_kernel}'")
        if self.round_kernel == "persistent" and not self.use_fused_topk:
            raise ValueError(
                "round_kernel='persistent' fuses the round into the Pallas "
                "sweep; it requires use_fused_topk=True"
            )


def replace(cfg, **kw):
    """dataclasses.replace that works through our frozen configs."""
    return dataclasses.replace(cfg, **kw)
