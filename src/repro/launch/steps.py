"""Step builders: one (jit-able fn, input specs, shardings) per dry-run cell.

Every assigned (architecture x shape) pair maps to exactly one entry here:

  LM      train_4k      -> train_step   (loss+grad+AdamW, FSDP+TP, remat+scan)
          prefill_32k   -> serve_prefill (flash path, returns KV cache)
          decode_32k    -> serve_decode  (sequence-parallel KV, LSE combine)
          long_500k     -> serve_decode  (cache sharded over ALL axes, batch=1)
  GNN     full_*/minibatch/molecule -> train_step (segment_sum MP, edge-sharded)
  RecSys  train_batch   -> train_step   (row-sharded tables)
          serve_p99/bulk-> serve_step   (forward scoring)
          retrieval_cand-> ADACUR retrieval step (the paper's technique at
                           1M-item scale) — MIND uses its native DE retrieval

Params are never materialized for the dry-run: ``abstract_state`` trees come
from jax.eval_shape and carry NamedShardings from the logical-axis rules.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs import registry
from ..configs.base import AdaCURConfig, GNNConfig, LMConfig, RecSysConfig
from ..configs.shapes import GraphShape, LMShape, RecSysShape
from ..core import adacur
from ..distributed import decode_attention, sharding
from ..models import moe as moe_lib, transformer
from ..models.gnn import nequip
from ..models.recsys import bert4rec, bst, dlrm, mind
from ..training import optimizer

F32, I32, BF16 = jnp.float32, jnp.int32, jnp.bfloat16


@dataclasses.dataclass
class StepBundle:
    """Everything the dry-run needs for one cell."""

    name: str
    step: Callable
    abstract_args: Tuple          # positional ShapeDtypeStructs
    in_shardings: Tuple
    out_shardings: Any            # None -> GSPMD-propagated
    model_flops: float            # analytic 6·N·D (or family equivalent)
    notes: str = ""
    donate: Tuple[int, ...] = ()  # donated args (train state, decode cache)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _even(mesh: Mesh, dim: int, axes) -> Any:
    """axes if dim divides evenly over them, else replicated."""
    if axes is None:
        return None
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    size = 1
    for a in axes_t:
        size *= mesh.shape[a]
    return axes if dim % size == 0 else None


def _shardify(mesh, tree_specs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _abstract_params(init_fn, mesh, rules=None):
    """(abstract params, PartitionSpec tree) without materializing.

    init_fn returns (params, logical-axis specs); the specs are static
    strings, so they are smuggled out of the eval_shape trace via a box."""
    spec_box = {}

    def only_params():
        params, logical = init_fn()
        spec_box["s"] = logical
        return params

    a_params = jax.eval_shape(only_params)
    specs = sharding.tree_specs(mesh, a_params, spec_box["s"], rules)
    return a_params, specs


# ===========================================================================
# LM family
# ===========================================================================


def _chunked_nll(params, h, targets, cfg: LMConfig, mesh: Mesh, chunk: int = 512):
    """Cross-entropy over sequence chunks with vocab-sharded logits.

    The full (B, L, V) logits never materialize: each chunk is checkpointed
    (backward recomputes its logits from h) and the model axis stays on the
    VOCAB dim inside the loss region — measured 10 GB/device of f32 logits
    on qwen1.5-110b otherwise."""
    bp = sharding.batch_axes(mesh)
    b, l, d = h.shape
    chunk = min(chunk, l)
    n = l // chunk
    # loss-region layout: d_model on the model axis (contracted by the head)
    h = jax.lax.with_sharding_constraint(h, P(bp, None, _even(mesh, d, "model")))
    hs = jnp.moveaxis(h.reshape(b, n, chunk, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(b, n, chunk), 1, 0)

    @jax.checkpoint
    def one(hc, tc):
        logits = transformer.lm_logits(params, hc, cfg)
        pv = logits.shape[-1]
        logits = jax.lax.with_sharding_constraint(
            logits, P(bp, None, _even(mesh, pv, "model"))
        )
        logp = jax.nn.log_softmax(logits.astype(F32), axis=-1)
        return -jnp.take_along_axis(logp, tc[..., None], axis=-1).sum()

    def body(carry, xs):
        hc, tc = xs
        return carry + one(hc, tc), None

    total, _ = jax.lax.scan(body, jnp.zeros((), F32), (hs, ts))
    return total / (b * l)


def _lm_loss_fn(cfg: LMConfig, moe_fn, mesh: Mesh, act_spec=None, attn_spec=None):
    def loss_fn(params, batch):
        h, aux = transformer.encode(
            params, batch["tokens"], cfg, moe_fn=moe_fn,
            act_spec=act_spec, attn_spec=attn_spec,
        )
        loss = _chunked_nll(params, h, batch["targets"], cfg, mesh)
        if cfg.moe is not None:
            loss = loss + cfg.moe.aux_loss_coef * aux
        return loss

    return loss_fn


def build_lm_train(arch_id: str, cfg: LMConfig, shape: LMShape, mesh: Mesh) -> StepBundle:
    bp = sharding.batch_axes(mesh)
    n_tok_local = shape.global_batch * shape.seq_len
    for a in bp:
        n_tok_local //= mesh.shape[a]
    moe_fn = (
        moe_lib.make_moe_fn(
            mesh, cfg.moe, bp,
            # reduce-scatter the MoE combine straight into the seq-sharded
            # residual layout (perf iteration, EXPERIMENTS.md §Perf)
            scatter_tokens=n_tok_local % mesh.shape["model"] == 0,
        )
        if cfg.moe is not None else None
    )
    opt_cfg = optimizer.AdamWConfig()
    # Megatron sequence sharding of the residual stream (see _encode_layer);
    # attention internals shard by heads instead.
    act_spec = P(bp, _even(mesh, shape.seq_len, "model"), None)
    attn_spec = P(bp, None, _even(mesh, cfg.n_heads, "model"), None)
    loss_fn = _lm_loss_fn(cfg, moe_fn, mesh, act_spec, attn_spec)
    init = lambda: transformer.init_lm(jax.random.PRNGKey(0), cfg)
    # models under ~2B params skip FSDP (TP-only): the per-step param
    # all-gathers outweigh the modest per-device replication cost
    rules = None
    if cfg.n_params() < 2e9:
        rules = dict(sharding.DEFAULT_RULES)
        rules["embed"] = (None,)
    a_params, p_specs = _abstract_params(init, mesh, rules)
    # gradient accumulation for the largest models: token-proportional
    # activation temps (remat carries, attention chunks) scale 1/n_micro
    n_micro = 4 if cfg.n_params() > 4e10 else 1

    def step(params, opt_state, batch):
        if n_micro > 1:
            mb = jax.tree.map(
                lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]),
                batch,
            )
            grads, loss = optimizer.accumulate_grads(
                lambda p, m: loss_fn(p, m), params, mb, n_micro
            )
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # pin gradient layouts to the param shardings — otherwise the embed
        # scatter-add grad materializes the FULL table per device
        grads = jax.lax.with_sharding_constraint(grads, p_specs)
        params, opt_state, metrics = optimizer.adamw_update(
            opt_cfg, params, grads, opt_state
        )
        return params, opt_state, {"loss": loss, **metrics}
    a_opt = jax.eval_shape(optimizer.init_adamw, a_params)
    o_specs = optimizer.AdamWState(P(), p_specs, jax.tree.map(lambda s: s, p_specs))
    b, l = shape.global_batch, shape.seq_len
    batch_sds = {"tokens": _sds((b, l), I32), "targets": _sds((b, l), I32)}
    batch_spec = {"tokens": P(bp, None), "targets": P(bp, None)}
    return StepBundle(
        name=f"{arch_id}:{shape.name}",
        step=step,
        abstract_args=(a_params, a_opt, batch_sds),
        in_shardings=tuple(
            _shardify(mesh, s) for s in (p_specs, o_specs, batch_spec)
        ),
        out_shardings=(
            _shardify(mesh, p_specs), _shardify(mesh, o_specs), None
        ),
        model_flops=6.0 * cfg.n_active_params() * b * l,
        donate=(0, 1),
    )


def build_lm_prefill(arch_id: str, cfg: LMConfig, shape: LMShape, mesh: Mesh) -> StepBundle:
    bp = sharding.batch_axes(mesh)
    moe_fn = (
        moe_lib.make_moe_fn(mesh, cfg.moe, bp) if cfg.moe is not None else None
    )

    act_spec = P(_even(mesh, shape.global_batch, bp), _even(mesh, shape.seq_len, "model"), None)

    def step(params, tokens):
        h, _, (prefix_kv, scan_kv) = transformer.encode(
            params, tokens, cfg, moe_fn=moe_fn, return_kv=True, act_spec=act_spec
        )
        last = transformer.lm_logits(params, h[:, -1:, :], cfg)[:, 0]
        cache = {"k": scan_kv[0], "v": scan_kv[1]}
        if prefix_kv:
            cache["prefix"] = [{"k": k, "v": v} for (k, v) in prefix_kv]
        return last, cache

    init = lambda: transformer.init_lm(jax.random.PRNGKey(0), cfg)
    a_params, p_specs = _abstract_params(init, mesh)
    b, l = shape.global_batch, shape.seq_len
    tokens = _sds((b, l), I32)
    # cache out: seq sharded over model (matches the decode layout)
    kv_spec = P(None, _even(mesh, b, bp), None, _even(mesh, l, "model"), None)
    return StepBundle(
        name=f"{arch_id}:{shape.name}",
        step=step,
        abstract_args=(a_params, tokens),
        in_shardings=(
            _shardify(mesh, p_specs),
            NamedSharding(mesh, P(_even(mesh, b, bp), None)),
        ),
        out_shardings=None,
        model_flops=2.0 * cfg.n_active_params() * b * l,
    )


def build_lm_decode(arch_id: str, cfg: LMConfig, shape: LMShape, mesh: Mesh) -> StepBundle:
    b, s = shape.global_batch, shape.seq_len
    bp = sharding.batch_axes(mesh)
    if shape.name == "long_500k":
        batch_axes: Tuple[str, ...] = ()
        seq_axes = bp + ("model",)        # cache sharded over EVERYTHING
    else:
        batch_axes = tuple(a for a in bp if b % mesh.shape[a] == 0)
        seq_axes = ("model",)
    decode_core = decode_attention.make_decode_core(mesh, batch_axes, seq_axes, s)
    moe_fn = (
        moe_lib.make_moe_fn(mesh, cfg.moe, batch_axes) if cfg.moe is not None else None
    )

    def step(params, cache, token, pos):
        return transformer.decode_step(
            params, cache, token, pos, cfg, moe_fn=moe_fn, decode_core=decode_core
        )

    init = lambda: transformer.init_lm(jax.random.PRNGKey(0), cfg)
    a_params, p_specs = _abstract_params(init, mesh)
    a_cache = jax.eval_shape(lambda: transformer.init_cache(cfg, b, s))
    bspec = tuple(batch_axes) if batch_axes else None
    sspec = tuple(seq_axes)
    stacked = P(None, bspec, sspec, None, None)
    prefix = P(bspec, sspec, None, None)
    cache_spec = jax.tree.map(lambda _: prefix, a_cache)
    cache_spec["k"] = stacked
    cache_spec["v"] = stacked
    token = _sds((b,), I32)
    pos = _sds((), I32)
    return StepBundle(
        name=f"{arch_id}:{shape.name}",
        step=step,
        abstract_args=(a_params, a_cache, token, pos),
        in_shardings=(
            _shardify(mesh, p_specs),
            _shardify(mesh, cache_spec),
            NamedSharding(mesh, P(bspec)),
            NamedSharding(mesh, P()),
        ),
        out_shardings=(None, _shardify(mesh, cache_spec)),
        model_flops=2.0 * cfg.n_active_params() * b,
        notes=f"seq-parallel KV over {seq_axes}",
        donate=(1,),
    )


# ===========================================================================
# GNN family (NequIP)
# ===========================================================================


def _gnn_batch(cfg: GNNConfig, shape: GraphShape, mesh: Mesh,
               receiver_partitioned: bool = False):
    """(abstract batch, shardings, n_graphs).

    ``receiver_partitioned``: edges sharded on the SAME axis as nodes (the
    graph-partitioning contract the sharded interact requires)."""
    all_axes = tuple(mesh.axis_names)
    if shape.kind == "molecule":
        g = shape.batch_graphs
        n = g * shape.n_nodes
        e = g * shape.n_edges
        n_graphs = g
    elif shape.kind == "minibatch":
        # padded fanout subgraph (1024 seeds, fanout 15-10) — static shapes
        n = e = 196608
        n_graphs = 1
    else:
        n, e = shape.n_nodes, shape.n_edges
        n_graphs = 1
    # pad node/edge buffers to a shardable multiple (jraph-style): real graph
    # sizes (e.g. ogb 61,859,140 edges) divide over no mesh axis, which
    # otherwise forces full replication — 2.2 TB/device of edge messages.
    n = (n + 511) // 512 * 512
    e = (e + 511) // 512 * 512
    node_ax = _even(mesh, n, ("data",))
    edge_ax = (
        _even(mesh, e, ("data",)) if receiver_partitioned
        else _even(mesh, e, all_axes)
    )
    batch = {
        "positions": _sds((n, 3), F32),
        "node_attr": _sds((n, shape.d_feat), F32) if shape.d_feat else _sds((n,), I32),
        "senders": _sds((e,), I32),
        "receivers": _sds((e,), I32),
        "edge_mask": _sds((e,), F32),
        "node_mask": _sds((n,), F32),
        "energy": _sds((n_graphs,), F32),
    }
    if shape.kind == "molecule":
        batch["graph_ids"] = _sds((n,), I32)
    specs = {
        "positions": P(node_ax, None),
        "node_attr": P(node_ax, None) if shape.d_feat else P(node_ax),
        "senders": P(edge_ax),
        "receivers": P(edge_ax),
        "edge_mask": P(edge_ax),
        "node_mask": P(node_ax),
        "energy": P(_even(mesh, n_graphs, sharding.batch_axes(mesh))),
    }
    if shape.kind == "molecule":
        specs["graph_ids"] = P(node_ax)
    return batch, specs, n_graphs


def build_gnn_train(arch_id: str, cfg: GNNConfig, shape: GraphShape, mesh: Mesh) -> StepBundle:
    opt_cfg = optimizer.AdamWConfig(lr=1e-3)
    n_graphs_holder = {}
    # pod-scale graphs: receiver-partitioned edges + shard_map interact so
    # the scatter-add never leaves the node shard (see nequip module docs)
    big = shape.n_nodes > 100_000
    interact_fn = nequip.make_sharded_interact(mesh, "data") if big else None

    def loss_fn(params, batch):
        # remat=False deliberately: with channel-TP interact the saved
        # gathered tables are small, and remat's backward RE-gathers cost
        # 3 GB of extra all-gather traffic (235 -> 175 ms collective term;
        # EXPERIMENTS.md §Perf)
        return nequip.energy_mse_loss(
            params, cfg, batch, n_graphs=n_graphs_holder["n"],
            interact_fn=interact_fn, remat=False,
        )

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt_state, metrics = optimizer.adamw_update(
            opt_cfg, params, grads, opt_state
        )
        return params, opt_state, {"loss": loss, **metrics}

    init = lambda: nequip.init_nequip(jax.random.PRNGKey(0), cfg, d_feat=shape.d_feat)
    a_params, p_specs = _abstract_params(init, mesh)   # tiny -> replicated
    a_opt = jax.eval_shape(optimizer.init_adamw, a_params)
    o_specs = optimizer.AdamWState(P(), p_specs, jax.tree.map(lambda s: s, p_specs))
    batch_sds, batch_spec, n_graphs = _gnn_batch(
        cfg, shape, mesh, receiver_partitioned=big
    )
    n_graphs_holder["n"] = n_graphs
    return StepBundle(
        name=f"{arch_id}:{shape.name}",
        step=step,
        abstract_args=(a_params, a_opt, batch_sds),
        in_shardings=tuple(
            _shardify(mesh, s) for s in (p_specs, o_specs, batch_spec)
        ),
        out_shardings=(
            _shardify(mesh, p_specs), _shardify(mesh, o_specs), None
        ),
        # per-edge TP message cost dominates: ~(paths * irrep_dim * h) MACs/edge
        model_flops=2.0 * batch_sds["senders"].shape[0] * 11 * 9 * cfg.d_hidden
        * cfg.n_layers,
        notes=f"{shape.kind}, segment_sum message passing",
        donate=(0, 1),
    )


# ===========================================================================
# RecSys family
# ===========================================================================


def _recsys_init(arch_id: str, cfg: RecSysConfig):
    key = jax.random.PRNGKey(0)
    if cfg.kind == "dlrm":
        return lambda: dlrm.init_dlrm(key, cfg)
    if cfg.kind == "bst":
        return lambda: bst.init_bst(key, cfg)
    if cfg.kind == "bert4rec":
        return lambda: bert4rec.init_bert4rec(key, cfg)
    if cfg.kind == "mind":
        return lambda: mind.init_mind(key, cfg)
    raise KeyError(cfg.kind)


def _recsys_inputs(cfg: RecSysConfig, batch: int, mesh: Mesh, train: bool):
    bp = sharding.batch_axes(mesh)
    bax = _even(mesh, batch, bp)
    if cfg.kind == "dlrm":
        sds = {
            "dense": _sds((batch, cfg.n_dense), F32),
            "sparse": _sds((batch, cfg.n_sparse), I32),
        }
        spec = {"dense": P(bax, None), "sparse": P(bax, None)}
    else:
        sds = {"history": _sds((batch, cfg.seq_len), I32)}
        spec = {"history": P(bax, None)}
        if cfg.kind in ("bst",):
            sds["target"] = _sds((batch,), I32)
            spec["target"] = P(bax)
        if cfg.kind == "bert4rec" and not train:
            sds["target"] = _sds((batch,), I32)
            spec["target"] = P(bax)
    if train:
        if cfg.kind in ("dlrm", "bst"):
            sds["labels"] = _sds((batch,), F32)
            spec["labels"] = P(bax)
        if cfg.kind == "bert4rec":
            sds["target"] = _sds((batch,), I32)
            spec["target"] = P(bax)
        if cfg.kind == "mind":
            sds["target"] = _sds((batch,), I32)
            spec["target"] = P(bax)
            sds["neg_ids"] = _sds((batch, 64), I32)
            spec["neg_ids"] = P(bax, None)
    return sds, spec


def _recsys_loss(cfg: RecSysConfig):
    if cfg.kind == "dlrm":
        return lambda p, b: dlrm.bce_loss(p, b["dense"], b["sparse"], b["labels"], cfg)
    if cfg.kind == "bst":
        return lambda p, b: bst.bce_loss(p, b["history"], b["target"], b["labels"], cfg)
    if cfg.kind == "bert4rec":
        return lambda p, b: bert4rec.mlm_loss(p, b["history"], b["target"], cfg)
    if cfg.kind == "mind":
        return lambda p, b: mind.sampled_softmax_loss(
            p, b["history"], b["target"], b["neg_ids"], cfg
        )
    raise KeyError(cfg.kind)


def _recsys_forward(cfg: RecSysConfig, mesh: Optional[Mesh] = None):
    if cfg.kind == "dlrm":
        return lambda p, b: dlrm.forward(p, b["dense"], b["sparse"], cfg)
    if cfg.kind == "bst":
        return lambda p, b: bst.forward(p, b["history"], b["target"], cfg)
    if cfg.kind == "bert4rec":
        return lambda p, b: bert4rec.score_candidates(
            p, b["history"], b["target"][:, None], cfg
        )[:, 0]
    if cfg.kind == "mind":
        if mesh is None:
            return lambda p, b: mind.retrieve(p, b["history"], 100, cfg)
        # XLA's TopK partitioner all-gathers batch-sharded operands (a
        # 17 GB/device buffer at serve_bulk scale) — run the whole tiled
        # retrieval under shard_map so every top_k is shard-local; the only
        # resharding is one broadcast of the (256 MB) item table.
        bspec = sharding.batch_axes(mesh)

        def fwd(p, b):
            pspec = jax.tree.map(lambda _: P(), p)
            return jax.shard_map(
                lambda pl, h: mind.retrieve(pl, h, 100, cfg),
                mesh=mesh,
                in_specs=(pspec, P(bspec, None)),
                out_specs=(P(bspec, None), P(bspec, None)),
                check_vma=False,
            )(p, b["history"])

        return fwd
    raise KeyError(cfg.kind)


def _recsys_flops(cfg: RecSysConfig, batch: int) -> float:
    if cfg.kind == "dlrm":
        mlp = sum(a * b for a, b in zip(cfg.bot_mlp[:-1], cfg.bot_mlp[1:]))
        n = cfg.n_sparse + 1
        mlp += (n * (n - 1) // 2 + cfg.bot_mlp[-1]) * cfg.top_mlp[1]
        mlp += sum(a * b for a, b in zip(cfg.top_mlp[1:-1], cfg.top_mlp[2:]))
        inter = n * n * cfg.embed_dim
        return 2.0 * batch * (mlp + inter)
    d, L = cfg.embed_dim, cfg.seq_len
    attn = cfg.n_blocks * (4 * L * d * d + 2 * L * L * d)
    ffn = cfg.n_blocks * 2 * L * d * (cfg.mlp_dims[0] if cfg.mlp_dims else 4 * d)
    head = sum(
        a * b
        for a, b in zip(
            (d * (L + 1),) + tuple(cfg.mlp_dims), tuple(cfg.mlp_dims) + (1,)
        )
    ) if cfg.kind == "bst" else d * d
    return 2.0 * batch * (attn + ffn + head)


def build_recsys_train(arch_id: str, cfg: RecSysConfig, shape: RecSysShape, mesh: Mesh) -> StepBundle:
    opt_cfg = optimizer.AdamWConfig(lr=1e-3, weight_decay=0.0)
    loss_fn = _recsys_loss(cfg)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt_state, metrics = optimizer.adamw_update(
            opt_cfg, params, grads, opt_state
        )
        return params, opt_state, {"loss": loss, **metrics}

    a_params, p_specs = _abstract_params(_recsys_init(arch_id, cfg), mesh)
    a_opt = jax.eval_shape(optimizer.init_adamw, a_params)
    o_specs = optimizer.AdamWState(P(), p_specs, jax.tree.map(lambda s: s, p_specs))
    sds, spec, = _recsys_inputs(cfg, shape.batch, mesh, train=True)
    return StepBundle(
        name=f"{arch_id}:{shape.name}",
        step=step,
        abstract_args=(a_params, a_opt, sds),
        in_shardings=tuple(_shardify(mesh, s) for s in (p_specs, o_specs, spec)),
        out_shardings=(_shardify(mesh, p_specs), _shardify(mesh, o_specs), None),
        model_flops=3.0 * _recsys_flops(cfg, shape.batch),  # fwd+bwd ≈ 3x fwd
        donate=(0, 1),
    )


def build_recsys_serve(arch_id: str, cfg: RecSysConfig, shape: RecSysShape, mesh: Mesh) -> StepBundle:
    fwd = _recsys_forward(cfg, mesh)

    def step(params, batch):
        return fwd(params, batch)

    a_params, p_specs = _abstract_params(_recsys_init(arch_id, cfg), mesh)
    sds, spec = _recsys_inputs(cfg, shape.batch, mesh, train=False)
    return StepBundle(
        name=f"{arch_id}:{shape.name}",
        step=step,
        abstract_args=(a_params, sds),
        in_shardings=(_shardify(mesh, p_specs), _shardify(mesh, spec)),
        out_shardings=None,
        model_flops=_recsys_flops(cfg, shape.batch),
    )


def build_recsys_retrieval(arch_id: str, cfg: RecSysConfig, shape: RecSysShape, mesh: Mesh) -> StepBundle:
    """The paper's technique at scale: ADACUR over 1M candidates.

    MIND (dual-encoder) uses its native all-item GEMM retrieval instead —
    DESIGN.md §4.1 — and doubles as ADACUR's first-round retriever."""
    n_cand = shape.n_candidates
    b = shape.batch
    k_q = 500
    all_axes = tuple(mesh.axis_names)
    # pad the candidate axis to a shardable multiple: 1M columns divide over
    # no mesh axis, which otherwise REPLICATES the 2 GB R_anc on every chip
    # (measured 22.4 GB of per-device HBM reads per search)
    n_pad = (n_cand + 511) // 512 * 512
    item_ax = _even(mesh, n_pad, all_axes)

    a_params, p_specs = _abstract_params(_recsys_init(arch_id, cfg), mesh)

    if cfg.kind == "mind":
        def step(params, batch):
            return mind.retrieve(params, batch["history"], 100, cfg)

        sds = {"history": _sds((b, cfg.seq_len), I32)}
        spec = {"history": P(None, None)}
        return StepBundle(
            name=f"{arch_id}:{shape.name}",
            step=step,
            abstract_args=(a_params, sds),
            in_shardings=(_shardify(mesh, p_specs), _shardify(mesh, spec)),
            out_shardings=None,
            model_flops=2.0 * b * cfg.n_interests * cfg.embed_dim * n_cand,
            notes="dual-encoder brute retrieval (ADACUR first-round source)",
        )

    # perf note (EXPERIMENTS.md §Perf): distributed_gather=True (one-hot
    # matmul column gather) was tried and REFUTED here — after padding the
    # candidate axis, XLA's gather partitioning already avoids replicating
    # R_anc, and the one-hot path only added flops + all-gather traffic.
    acfg = AdaCURConfig(
        k_anchor=250, n_rounds=5, budget_ce=500, strategy="topk",
        split_budget=True, k_retrieve=100,
    )

    def make_step():
        def step(params, batch, key):
            if cfg.kind == "dlrm":
                def sf(q, idx):
                    return dlrm.score_candidates(params, q["dense"], q["sparse"], idx, cfg)
                query = {"dense": batch["dense"], "sparse": batch["sparse"]}
            elif cfg.kind == "bst":
                def sf(q, idx):
                    return bst.score_candidates(params, q["history"], idx, cfg)
                query = {"history": batch["history"]}
            else:  # bert4rec
                def sf(q, idx):
                    return bert4rec.score_candidates(params, q["history"], idx, cfg)
                query = {"history": batch["history"]}
            res = adacur.adacur_search(
                sf, batch["r_anc"], query, acfg, key, batch=b,
                n_valid_items=n_cand,
            )
            return res.topk_idx, res.topk_scores

        return step

    sds, spec = _recsys_inputs(cfg, b, mesh, train=False)
    sds.pop("target", None)
    spec.pop("target", None)
    sds["r_anc"] = _sds((k_q, n_pad), F32)
    spec["r_anc"] = P(None, item_ax)
    key = _sds((2,), jnp.uint32)
    return StepBundle(
        name=f"{arch_id}:{shape.name}",
        step=make_step(),
        abstract_args=(a_params, sds, key),
        in_shardings=(
            _shardify(mesh, p_specs), _shardify(mesh, spec),
            NamedSharding(mesh, P(None)),
        ),
        out_shardings=None,
        # dominant: 5 rounds of e_q @ R_anc (B,k_q)x(k_q,N) + 500 CE calls
        model_flops=2.0 * b * k_q * n_cand * acfg.n_rounds
        + _recsys_flops(cfg, acfg.budget_ce),
        notes="ADACUR multi-round retrieval (paper technique at 1M scale)",
    )


def build_lm_adacur_serve(
    arch_id: str, cfg: LMConfig, mesh: Mesh,
    n_items: int = 1_000_000, batch: int = 8,
    item_len: int = 48, query_len: int = 16, k_q: int = 500,
) -> StepBundle:
    """The paper's FULL pipeline on a pod: multi-round ADACUR retrieval where
    the exact scorer is a transformer CROSS-ENCODER from the model zoo.

    Per round, the engine's k_s exact calls become one batched CE prefill of
    (B·k_s) [CLS] query [SEP] item [SEP] sequences through the TP-sharded
    backbone; the item corpus (token table) and R_anc are row/column-sharded
    over the whole mesh.  Extra dry-run target (beyond the 40 assigned
    cells): ``--cell <lm-arch>:adacur_serve``.
    """
    from ..models import cross_encoder

    bp = sharding.batch_axes(mesh)
    all_axes = tuple(mesh.axis_names)
    n_pad = (n_items + 511) // 512 * 512
    item_ax = _even(mesh, n_pad, all_axes)
    pair_len = query_len + item_len + 3
    moe_fn = (
        moe_lib.make_moe_fn(mesh, cfg.moe, ()) if cfg.moe is not None else None
    )
    acfg = AdaCURConfig(
        k_anchor=250, n_rounds=5, budget_ce=500, strategy="topk",
        split_budget=True, k_retrieve=100,
    )

    def step(params, batch_in, key):
        corpus = batch_in["corpus_tokens"]        # (N_pad, item_len)
        queries = batch_in["query_tokens"]        # (B, query_len)

        def score_fn(q_tokens, item_idx):         # (B, Lq) x (B, K) -> (B, K)
            b, k = item_idx.shape
            items = jnp.take(corpus, item_idx.reshape(-1), axis=0)  # (B*K, Li)
            q_rep = jnp.repeat(q_tokens, k, axis=0)                  # (B*K, Lq)
            cls = jnp.full((b * k, 1), 1, jnp.int32)
            sep = jnp.full((b * k, 1), 2, jnp.int32)
            pairs = jnp.concatenate([cls, q_rep, sep, items, sep], axis=1)
            return cross_encoder.score_tokens(
                params, pairs, cfg, moe_fn=moe_fn
            ).reshape(b, k)

        res = adacur.adacur_search(
            score_fn, batch_in["r_anc"], queries, acfg, key,
            batch=batch, n_valid_items=n_items,
        )
        return res.topk_idx, res.topk_scores

    init = lambda: cross_encoder.init_cross_encoder(jax.random.PRNGKey(0), cfg)
    a_params, p_specs = _abstract_params(init, mesh)
    sds = {
        "corpus_tokens": _sds((n_pad, item_len), I32),
        "query_tokens": _sds((batch, query_len), I32),
        "r_anc": _sds((k_q, n_pad), F32),
    }
    spec = {
        "corpus_tokens": P(item_ax, None),
        "query_tokens": P(None, None),
        "r_anc": P(None, item_ax),
    }
    key = _sds((2,), jnp.uint32)
    # CE cost dominates: budget_ce prefill passes per query
    ce_flops = 2.0 * cfg.n_active_params() * batch * acfg.budget_ce * pair_len
    return StepBundle(
        name=f"{arch_id}:adacur_serve",
        step=step,
        abstract_args=(a_params, sds, key),
        in_shardings=(
            _shardify(mesh, p_specs), _shardify(mesh, spec),
            NamedSharding(mesh, P(None)),
        ),
        out_shardings=None,
        model_flops=ce_flops + 2.0 * batch * k_q * n_pad * acfg.n_rounds,
        notes="paper pipeline w/ transformer CE scorer (extra cell)",
    )


# ===========================================================================
# dispatcher
# ===========================================================================


def build_cell(arch_id: str, shape_name: str, mesh: Mesh) -> StepBundle:
    entry = registry.get(arch_id)
    if entry.family == "lm" and shape_name == "adacur_serve":
        return build_lm_adacur_serve(arch_id, entry.config, mesh)
    shape = registry.shapes_for(arch_id)[shape_name]
    if entry.family == "lm":
        cfg = entry.config
        if shape.kind == "train":
            return build_lm_train(arch_id, cfg, shape, mesh)
        if shape.kind == "prefill":
            return build_lm_prefill(arch_id, cfg, shape, mesh)
        return build_lm_decode(arch_id, cfg, shape, mesh)
    if entry.family == "gnn":
        return build_gnn_train(arch_id, entry.config, shape, mesh)
    # recsys
    cfg = entry.config
    if shape.kind == "train":
        return build_recsys_train(arch_id, cfg, shape, mesh)
    if shape.kind == "serve":
        return build_recsys_serve(arch_id, cfg, shape, mesh)
    return build_recsys_retrieval(arch_id, cfg, shape, mesh)
