"""Static-shape multi-round ADACUR engine + the unified Retriever API.

The seed implementation (``core/adacur.py``, kept as the executable spec and
parity oracle) grows every buffer with ``jnp.concatenate``: each round body
has a different trace shape, so changing ``n_rounds`` recompiles the whole
search and nothing can run under ``lax.fori_loop`` — exactly the non-CE
overhead the paper's Fig. 4 warns about.  This module is the production
path:

- **preallocated slabs**: the anchor-id (B, k_i), exact-score (B, k_i),
  anchor-column (B, k_q, k_i) and incremental-pinv (B, k_i, k_q) buffers are
  allocated once at their final size and round r fills slab
  ``[r·k_s, (r+1)·k_s)`` with ``lax.dynamic_update_slice``.  Unfilled pinv
  rows / anchor columns are exact zeros, which contribute exact zeros to
  every contraction, so the padded math equals the growing-shape math;
- **shape-invariant round body**: runs unrolled (``loop_mode='unrolled'``,
  the seed behavior, any score_fn), under ``lax.fori_loop`` with the round
  count as a *runtime operand* (``loop_mode='fori'`` — per-query-batch round
  counts without retracing, cf. arXiv 2405.03651), or under
  ``lax.while_loop`` with an early-exit tolerance (anytime ADACUR: stop when
  the round-over-round provisional top-k set stabilizes);
- **fused score->sample** (``use_fused_topk``): per-round anchor sampling
  and the final split-budget rerank selection go through the Pallas
  ``approx_topk_op`` so the (B, N) approximate score matrix is never
  materialized — TopK sampling needs no (B, N) intermediate at all, SoftMax
  passes Gumbel noise as a kernel input (Kool et al. 2019);
- **one code path for every method**: :class:`AdaCURRetriever` (the paper),
  :class:`ANNCURRetriever` (fixed anchors = one engine round, arXiv
  2210.12579) and :class:`RerankRetriever` (retrieve-and-rerank = one
  retriever-seeded round with no budget split) are thin configurations of
  :func:`engine_search` behind the common :class:`Retriever` protocol;
- **one SPMD program over a (data x items) mesh**: the whole engine — slab
  state, sampling, CE scoring, incremental pinv, rerank — is written as a
  *per-shard math core* in local item coordinates plus a thin *collective
  layer* (:class:`ShardCtx` + the ``_merge_topk``/``_gather_cols``/
  ``_score_once`` helpers below).  :func:`make_sharded_engine` runs that
  core under ``shard_map``: the item axis shards the payload and the
  per-shard slab columns, the data axis shards the query batch, and the
  small pinv/e_q state replicates.  A single-device search is the same core
  on a trivial one-shard context, so the sharded engine is **bit-identical**
  to the single-device engine by construction (see the collective layer's
  docstrings for the three contracts that make this true).
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..configs.base import AdaCURConfig, replace
from ..kernels.approx_topk import quant
from ..kernels.approx_topk.ops import approx_topk_op
from ..kernels.approx_topk.persistent import persistent_round_op
from ..kernels.approx_topk.quant import QuantizedRanc
from ..kernels.backend import on_tpu
from . import cur, sampling, telemetry
from .adacur import AdaCURResult, ScoreFn


def ce_call_plan(cfg: AdaCURConfig, rounds: Optional[int] = None) -> int:
    """Exact CE calls per query for a run executing ``rounds`` rounds.

    Each executed round scores its k_s fresh anchors, plus the split-budget
    rerank (``budget_ce - k_anchor``) once at the end.  This is the single
    source of truth for budget accounting: ``AdaCURResult.ce_calls`` is this
    plan at the full round count, and a counting
    :class:`~repro.core.scorer.Scorer`'s *measured* ``stats.ce_calls`` must
    equal ``ce_call_plan(cfg, rounds_done) * batch`` — asserted per engine
    mode by the property-based invariant suite.
    """
    k_i = cfg.budget_ce if not cfg.split_budget else cfg.k_anchor
    k_s = k_i // cfg.n_rounds
    r = cfg.n_rounds if rounds is None else rounds
    if not 1 <= r <= cfg.n_rounds:
        raise ValueError(f"rounds={r} outside [1, {cfg.n_rounds}]")
    k_r = cfg.budget_ce - k_i if cfg.split_budget else 0
    return k_s * r + k_r


class AnytimeDeadline:
    """Host-side wall-clock deadline the engine's round loop polls.

    The anytime-serving contract: every round boundary of the multi-round
    search is a valid (if coarser) answer, so a search that runs out of
    latency budget should *return the provisional top-k from the rounds it
    completed* instead of nothing.  This object is the host<->trace bridge:
    the serving layer ``arm()``s it with an absolute ``time.monotonic()``
    deadline before a search and the engine's ``lax.while_loop`` cond polls
    :meth:`expired` through a numpy-only ``pure_callback`` (no nested device
    compute — the same mesh-legality class as ``TabulatedScorer``) once per
    round.  Round 0 always runs (it executes before the loop), so an
    already-expired deadline still yields a 1-round answer; the split-budget
    rerank still spends its ``budget_ce - k_anchor`` calls on whatever
    provisional estimate exists, keeping every response exact-CE ranked.

    ``fired`` records whether the deadline actually cut the loop short —
    ``arm()`` resets it, ``disarm()`` leaves it readable, so the serving
    layer can flag the response ``degraded`` after the (blocking) search.

    Single-device only: under the SPMD engine each shard would poll its own
    wall clock, shards could disagree on the iteration count, and the next
    collective would deadlock.  ``make_engine(anytime=True)`` is the one
    construction path; ``make_sharded_engine`` has no such parameter, and
    the serving tier's unit of redundancy is the *replica*, not the shard.
    """

    def __init__(self):
        self.deadline_t = float("inf")
        self.fired = False

    def arm(self, deadline_t: float) -> None:
        self.deadline_t = float(deadline_t)
        self.fired = False

    def disarm(self) -> None:
        """Stop cutting rounds; ``fired`` stays readable for the caller."""
        self.deadline_t = float("inf")

    def _expired_host(self, r) -> np.bool_:
        if time.monotonic() >= self.deadline_t:
            self.fired = True
            return np.bool_(True)
        return np.bool_(False)

    def expired(self, r: jax.Array) -> jax.Array:
        """Traced poll; ``r`` rides along as an operand so each loop
        iteration's callback is distinct (CSE-proof) and runs in order."""
        return jax.pure_callback(
            self._expired_host, jax.ShapeDtypeStruct((), jnp.bool_), r
        )


class EngineState(NamedTuple):
    """Loop-invariant-shaped state threaded through the round body.

    Under the SPMD engine, ``selected`` is the only item-axis buffer — it is
    *local* (B_local, N_local); everything else is small, indexed by global
    item ids, and replicated across item shards."""

    anchor_idx: jax.Array    # (B, k_i) int32, -1 in unfilled slots
    c_test: jax.Array        # (B, k_i) exact CE scores, 0 in unfilled slots
    a_buf: jax.Array         # (B, k_q, k_i) anchor columns, 0 beyond filled
    p: jax.Array             # (B, k_i, k_q) incremental pinv, 0 beyond filled
    e_q: jax.Array           # (B, k_q) latent query embedding
    selected: jax.Array      # (B, N) bool mask of already-selected items


# ---------------------------------------------------------------------------
# The collective layer: ShardCtx + the cross-shard primitives.
#
# The engine's math core runs in LOCAL item coordinates — every per-item
# buffer it touches is this shard's slab.  The helpers below are the only
# places shard boundaries exist.  Three contracts make the sharded program
# bit-identical to the single-device one:
#
# 1. **per-column scores are shard-invariant**: every sampling score is an
#    independent fp32 contraction over one payload column (+ the blocked
#    noise field, a pure function of global (row, item) coordinates — see
#    ``sampling.blocked_gumbel``), so a column scores to the same bits no
#    matter which shard computes it;
# 2. **deterministic global-id tie-break**: per-shard candidate lists break
#    exact score ties by ascending item id (the fused kernel contract), and
#    the cross-shard merge concatenates shard blocks in ascending shard
#    order before an index-stable ``lax.top_k`` — equal values therefore
#    resolve to the ascending *global* id, exactly like a single shard;
# 3. **every contribution has one owner**: anchor-column gathers and CE
#    scores are computed by exactly one shard and ``psum``-broadcast; the
#    other shards contribute exact zeros, and ``x + 0.0`` is exact in fp.
# ---------------------------------------------------------------------------


class ShardCtx(NamedTuple):
    """This program instance's place on the (data x items) mesh.

    ``item_axes is None`` is the trivial single-shard context: every
    collective helper short-circuits to plain local math, which *is* the
    single-device engine."""

    item_axes: Optional[Tuple[str, ...]]  # mesh axes sharding the item axis
    data_axes: Tuple[str, ...]            # mesh axes sharding the query batch
    n_local: int                          # item columns owned by this shard
    n_item_shards: int
    item_shard: Any                       # () int32 shard index (0 unsharded)
    row_offset: Any                       # global row of local batch row 0
    col_map: Any = None                   # (N_local,) global item position of
                                          # each local column (None = identity)


def _local_ctx(n_items: int, col_map=None) -> ShardCtx:
    return ShardCtx(None, (), n_items, 1, 0, 0, col_map)


def _axes_index(axes: Tuple[str, ...]) -> jax.Array:
    """Mixed-radix shard index over ``axes`` (major-to-minor in given order,
    matching ``lax.all_gather``'s tiled concatenation order)."""
    i = jnp.int32(0)
    for a in axes:
        i = i * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return i


def _item_offset(ctx: ShardCtx):
    """Global position of this shard's column 0."""
    return ctx.item_shard * ctx.n_local


def _psum_items(ctx: ShardCtx, x: jax.Array) -> jax.Array:
    return jax.lax.psum(x, ctx.item_axes) if ctx.item_axes else x


def _noise(ctx: ShardCtx, key: jax.Array, rows: int) -> jax.Array:
    """This context's (rows, N_local) rectangle of the canonical noise field.

    A candidate-subset context (``col_map`` set) holds columns gathered from
    scattered corpus positions; it evaluates the field at those *global*
    coordinates (:func:`sampling.gumbel_at`), so every draw matches the bits
    a masked full-corpus search would have seen at the same columns — the
    subset-vs-masked bit-parity contract."""
    if ctx.col_map is not None:
        return sampling.gumbel_at(key, rows, ctx.col_map, ctx.row_offset)
    return sampling.blocked_gumbel(
        key, rows, ctx.n_local, ctx.row_offset, _item_offset(ctx)
    )


def _merge_topk(ctx: ShardCtx, vals: jax.Array, gidx: jax.Array, k: int):
    """Per-shard (B, k) candidates -> global (B, k) top-k, replicated.

    The documented tie-break contract for cross-shard merges: each shard's
    list is value-sorted with exact ties in ascending global id (the fused
    kernel / ``lax.top_k`` index-stability), shard blocks concatenate in
    ascending shard order (= ascending global id ranges), and the final
    ``lax.top_k`` is index-stable over that buffer — so exact score ties
    resolve to the ascending global item id, identically to one shard
    ranking all N columns."""
    if ctx.item_axes is None:
        return vals, gidx
    vg = jax.lax.all_gather(vals, ctx.item_axes, axis=1, tiled=True)
    ig = jax.lax.all_gather(gidx, ctx.item_axes, axis=1, tiled=True)
    v, pos = jax.lax.top_k(vg, k)
    return v, jnp.take_along_axis(ig, pos, axis=1)


def _local_topk_merge(ctx: ShardCtx, logits: jax.Array, k: int) -> jax.Array:
    """top-k of a local (B, N_local) score slab -> global ids."""
    v, i = jax.lax.top_k(logits, k)
    if ctx.item_axes is None:
        return i
    _, gi = _merge_topk(ctx, v, i.astype(jnp.int32) + _item_offset(ctx), k)
    return gi


def _sample_random_ctx(
    ctx: ShardCtx, key: jax.Array, selected: jax.Array, k: int
) -> jax.Array:
    """Uniform w/o replacement over unselected items (global ids) — the
    shard-decomposed twin of ``sampling.sample_random`` (same noise field,
    same masked-Gumbel formula, so the single-shard case is bit-equal)."""
    b, n_local = selected.shape
    g = _noise(ctx, key, b)
    logits = jnp.where(selected, sampling.NEG_INF, 0.0) + g
    return _local_topk_merge(ctx, logits, k)


def _mark_selected(ctx: ShardCtx, selected: jax.Array, gidx: jax.Array) -> jax.Array:
    """Set the (global-id) picks in the local selected mask; ids owned by
    other shards drop out of range (negative locals must be sent PAST the
    slab, not left to Python-wrap onto someone else's column)."""
    rows = jnp.arange(selected.shape[0])[:, None]
    local = gidx - _item_offset(ctx)
    n_local = selected.shape[1]
    local = jnp.where((local >= 0) & (local < n_local), local, n_local)
    return selected.at[rows, local].set(True, mode="drop")


def _gather_cols(
    ctx: ShardCtx, r_anc, gidx: jax.Array, via_onehot: bool = False
) -> jax.Array:
    """R_anc[:, gidx] -> (B, k_q, k) fp32: the global->shard column gather.

    Each shard dequantizes/gathers exactly the columns it owns and the
    results are psum-broadcast (one owner per column, exact zeros
    elsewhere)."""
    if ctx.item_axes is None:
        return quant.gather_columns(r_anc, gidx, via_onehot=via_onehot)
    local = gidx - _item_offset(ctx)
    owned = (local >= 0) & (local < ctx.n_local)
    cols = quant.gather_columns(
        r_anc, jnp.clip(local, 0, ctx.n_local - 1), via_onehot=via_onehot
    )
    return _psum_items(ctx, jnp.where(owned[:, None, :], cols, 0.0))


def _map_item_ids(ctx: ShardCtx, item_ids: jax.Array, gidx: jax.Array) -> jax.Array:
    """Engine positions -> external corpus ids through the sharded id map."""
    if ctx.item_axes is None:
        return jnp.take(item_ids, gidx, axis=0)
    local = gidx - _item_offset(ctx)
    owned = (local >= 0) & (local < ctx.n_local)
    v = jnp.take(item_ids, jnp.clip(local, 0, ctx.n_local - 1), axis=0)
    return _psum_items(ctx, jnp.where(owned, v, 0))


def _score_once(
    ctx: ShardCtx, score_fn: ScoreFn, query, ids: jax.Array, dtype
) -> jax.Array:
    """Exact-CE score a (B, k) id batch EXACTLY ONCE across the system.

    Item shard 0 of each data shard runs the scorer (host callbacks fire on
    that shard only — ``lax.cond`` branches execute per shard at runtime,
    so a counting scorer's measured calls stay equal to the plan); the
    result psum-broadcasts to the item shards that contributed zeros."""
    if ctx.item_axes is None:
        return score_fn(query, ids)
    c = jax.lax.cond(
        ctx.item_shard == 0,
        lambda q, i: score_fn(q, i).astype(dtype),
        lambda q, i: jnp.zeros(i.shape, dtype),
        query, ids,
    )
    return _psum_items(ctx, c)


def _gather_token_rows(ctx: ShardCtx, table: jax.Array, gidx: jax.Array) -> jax.Array:
    """Corpus token rows for GLOBAL item positions -> (..., Li) int32.

    The token-table analogue of :func:`_map_item_ids`: each item shard
    gathers the rows it owns from its local (N_local, Li) slab, zeros
    elsewhere, one psum broadcast."""
    if ctx.item_axes is None:
        return jnp.take(table, gidx, axis=0)
    local = gidx - _item_offset(ctx)
    owned = (local >= 0) & (local < ctx.n_local)
    rows = jnp.take(table, jnp.clip(local, 0, ctx.n_local - 1), axis=0)
    return _psum_items(ctx, jnp.where(owned[..., None], rows, 0))


def _device_ce_score(
    ctx: ShardCtx, scorer, q_tokens, gidx: jax.Array, item_tokens: jax.Array
) -> jax.Array:
    """Device-resident CE scoring of a (B, k) position batch, in-trace.

    Replaces the shard-0 host-callback path for scorers with
    ``device_resident=True`` (:class:`~repro.core.scorer.DeviceCEScorer`):
    gather the selected items' token rows, assemble ``[CLS] q [SEP] i
    [SEP]`` pairs, and run the CE transformer forward inside the caller's
    trace — under the mesh the flattened pair batch is split across the
    *item* shards (each scores an equal contiguous chunk, all_gather
    reassembles), so the CE FLOPs parallelize over the whole mesh while
    every pair is still scored exactly once system-wide.  Measured
    accounting rides a numpy-only callback on item shard 0 (mesh-legal: no
    nested device launch), with the item-shard pad rows excluded.
    """
    rows = _gather_token_rows(ctx, item_tokens, gidx)          # (B, k, Li)
    pairs = scorer.build_pairs(q_tokens, rows)                 # (B, k, Lb)
    b, k, lb = pairs.shape
    n = b * k
    flat = pairs.reshape(n, lb)
    if ctx.item_axes is None:
        scores = scorer.forward(flat)
        dummy = scorer.count(gidx, 0)
    else:
        n_pad = -n % ctx.n_item_shards
        if n_pad:
            flat = jnp.concatenate(
                [flat, jnp.full((n_pad, lb), scorer.pad_id, flat.dtype)], axis=0
            )
        chunk = (n + n_pad) // ctx.n_item_shards
        local = jax.lax.dynamic_slice_in_dim(flat, ctx.item_shard * chunk, chunk, 0)
        s = scorer.forward(local).astype(jnp.float32)
        scores = jax.lax.all_gather(s, ctx.item_axes, axis=0, tiled=True)[:n]
        # one counting callback per data shard; gidx as operand keeps the
        # per-round calls distinct (CSE-proof), the consumed 0.0 keeps it live
        dummy = jax.lax.cond(
            ctx.item_shard == 0,
            lambda g: scorer.count(g, n_pad),
            lambda g: jnp.float32(0.0),
            gidx,
        )
    return scores.reshape(b, k).astype(jnp.float32) + 0.0 * dummy


def _global_frac(ctx: ShardCtx, hit: jax.Array) -> jax.Array:
    """Batch-mean of a boolean (B_local, m) statistic over the GLOBAL batch
    (the early-exit monitor must stop every shard on the same round).
    All partial sums are exact integers in fp32, so the sharded mean is
    bit-equal to the single-device one."""
    if not ctx.data_axes:
        return hit.mean()
    total = jax.lax.psum(jnp.sum(hit.astype(jnp.float32)), ctx.data_axes)
    n_rows = jax.lax.psum(jnp.int32(1), ctx.data_axes) * hit.size
    return total / n_rows.astype(jnp.float32)


def _effective_tile(cfg: AdaCURConfig, r_anc) -> int:
    """Item-tile width of the fused kernel for this payload.

    The compiled TPU kernel keeps the configured column count: its VMEM
    budget is dominated by the (B, T) fp32 score (and noise/mask) blocks,
    which do NOT shrink with the payload dtype — widening T there would
    blow VMEM; the int8 win on TPU is the 4x smaller HBM stream per
    (unchanged) tile.  On the CPU scan backend the binding constraint is
    the payload tile's L2 residency, so ``cfg.fused_tile`` acts as a
    per-tile *byte* budget expressed in fp32 columns: a quantized payload
    streams proportionally more columns in the same footprint (x4 int8,
    x2 bf16) — which is where the ~4x fewer bytes per round turn into
    wall-clock on CPU."""
    if on_tpu():
        return cfg.fused_tile
    dtype = quant.payload_dtype_of(r_anc)
    if dtype == "int4":
        return cfg.fused_tile * 8
    if dtype in ("int8", "fp8"):
        return cfg.fused_tile * 4
    if dtype == "bfloat16":
        return cfg.fused_tile * 2
    return cfg.fused_tile


def _bcast_mask(invalid, b: int, n: int):
    """Normalize an (N,) / (1, N) / (B, N) invalid mask to (B, N)."""
    if invalid is None:
        return None
    inv = invalid if invalid.ndim == 2 else invalid[None, :]
    return jnp.broadcast_to(inv, (b, n))


def _sample_round(
    cfg: AdaCURConfig,
    key: jax.Array,
    state: EngineState,
    r_anc: jax.Array,
    k_eff: int,
    n_valid: Optional[int],
    ctx: ShardCtx,
    monitor: Optional[tuple] = None,
):
    """One adaptive round's anchor pick (Alg. 3) — dense or fused, over this
    shard's payload slab; returns GLOBAL item ids.

    ``r_anc`` is any payload type (fp32/bf16 array or quantized
    int8/int4/fp8 QuantizedRanc); both branches dequantize per column, the
    dense one via :func:`quant.matmul`, the fused one inside the kernel
    tiles.  On a sharded context the per-shard candidates go through the
    tie-break merge (:func:`_merge_topk`).

    ``monitor=(m, invalid)`` additionally returns the provisional top-m ids
    of the *current* ``state.e_q`` estimate (the early-exit monitor) as a
    second value.  Under ``cfg.round_kernel='persistent'`` both lists come
    out of ONE persistent payload sweep (:func:`persistent_round_op`)
    whenever the sample and provisional branches share the estimate GEMM —
    ``topk`` strategy, or ``softmax`` at temperature 1.0 (``e_q / 1.0`` is
    bitwise ``e_q``, so the folded-temperature sample operand equals the
    provisional one); otherwise the monitor falls back to a separate
    :func:`_provisional_topk` pass with identical results.
    """
    sharded = ctx.item_axes is not None
    b, n_local = state.selected.shape
    remapped = ctx.col_map is not None
    persistent = cfg.use_fused_topk and cfg.round_kernel == "persistent"

    def with_monitor(gidx):
        if monitor is None:
            return gidx
        m, invalid = monitor
        return gidx, _provisional_topk(
            cfg, state.e_q, r_anc, m, n_valid, invalid, ctx
        )

    if cfg.strategy == "random" and (sharded or remapped or cfg.use_fused_topk):
        return with_monitor(_sample_random_ctx(ctx, key, state.selected, k_eff))
    if not cfg.use_fused_topk:
        s_hat = quant.matmul(state.e_q, r_anc)
        if not sharded and not remapped:
            return with_monitor(sampling.sample(
                cfg.strategy, key, s_hat, state.selected, k_eff, cfg.softmax_temp
            ))
        logits = sampling._masked_logits(s_hat, state.selected, cfg.softmax_temp)
        if cfg.strategy == "softmax":
            logits = logits + _noise(ctx, key, b)
        return with_monitor(_local_topk_merge(ctx, logits, k_eff))
    # already-selected items are suppressed through the engine's (B, N)
    # ``selected`` mask, streamed tile by tile next to the payload
    tile = _effective_tile(cfg, r_anc)
    nv = None if sharded else n_valid
    if persistent:
        kw = dict(k_sample=k_eff, tile=tile, n_valid=nv, mask=state.selected)
        e_q = state.e_q
        if cfg.strategy == "softmax":
            # temp folds into e_q (scores/temp == (e_q/temp) @ R_anc), as on
            # the staged path.  The Gumbel field is generated INSIDE the
            # sweep from its (key, global row/col) coordinates — the (B, N)
            # noise matrix never exists — except on a remapped candidate
            # subset, whose scattered coordinates need the gathered field.
            e_q = e_q / jnp.asarray(cfg.softmax_temp, e_q.dtype)
            if remapped:
                kw["noise"] = _noise(ctx, key, b)
            else:
                kw.update(
                    noise_key=key, row_offset=ctx.row_offset,
                    col_offset=_item_offset(ctx),
                )
        fuse_prov = monitor is not None and (
            cfg.strategy == "topk" or cfg.softmax_temp == 1.0
        )
        if fuse_prov:
            m, invalid = monitor
            (v, idx), (pv, pidx) = persistent_round_op(
                e_q, r_anc, k_prov=m,
                prov_mask=_bcast_mask(invalid, b, n_local), **kw,
            )
            if not sharded:
                return idx, pidx
            _, gidx = _merge_topk(ctx, v, idx + _item_offset(ctx), k_eff)
            _, pgidx = _merge_topk(ctx, pv, pidx + _item_offset(ctx), m)
            return gidx, pgidx
        (v, idx), _ = persistent_round_op(e_q, r_anc, **kw)
    elif cfg.strategy == "softmax":
        # temp folds into e_q (scores/temp == (e_q/temp) @ R_anc); Gumbel
        # noise enters the kernel as an input, S_hat stays in VMEM.
        g = _noise(ctx, key, b)
        e_q = state.e_q / jnp.asarray(cfg.softmax_temp, state.e_q.dtype)
        v, idx = approx_topk_op(
            e_q, r_anc, k=k_eff, tile=tile, noise=g, n_valid=nv,
            mask=state.selected,
        )
    else:
        # topk: temp > 0 is order-preserving, no noise needed
        v, idx = approx_topk_op(
            state.e_q, r_anc, k=k_eff, tile=tile, n_valid=nv,
            mask=state.selected,
        )
    if not sharded:
        return with_monitor(idx)
    _, gidx = _merge_topk(ctx, v, idx + _item_offset(ctx), k_eff)
    return with_monitor(gidx)


def _make_round_steps(
    scored: ScoreFn,
    r_anc: jax.Array,
    query,
    cfg: AdaCURConfig,
    keys: jax.Array,
    k_s: int,
    n_valid: Optional[int],
    ctx: ShardCtx,
):
    """The shape-invariant adaptive round, split into its two stages.

    ``sample(r, state, monitor=None)`` picks round r's fresh anchors from
    the current estimate (and optionally the provisional monitor top-k, in
    the same persistent sweep — see :func:`_sample_round`);
    ``apply(r, state, idx_new)`` is everything downstream of the pick — the
    ε diversity mix, CE scoring, slab updates and the pinv/e_q refresh.
    ``body = apply ∘ sample`` is the staged round body; the persistent
    monitored loop software-pipelines the stages instead (round r+1's
    ``sample`` rides round r's monitor sweep), which is legal because
    ``sample`` only reads state that ``apply`` finalized: the composition
    order changes, the computed values do not.

    ``r`` may be a python int (unrolled) or a traced int32 (fori/while).
    ``scored`` is the engine's score-once wrapper (id-mapped, one CE call
    per pair system-wide); all item ids in play are global."""
    n_rand = int(round(cfg.round_epsilon * k_s))

    def sample(r, state: EngineState, monitor=None):
        return _sample_round(
            cfg, keys[r], state, r_anc, k_s - n_rand, n_valid, ctx,
            monitor=monitor,
        )

    def apply(r, state: EngineState, idx_new) -> EngineState:
        key_r = keys[r]
        if n_rand:
            # ε-greedy diversity mix (beyond-paper; see AdaCURConfig)
            sel_tmp = _mark_selected(ctx, state.selected, idx_new)
            k_eps = jax.random.fold_in(key_r, 1)
            idx_rand = _sample_random_ctx(ctx, k_eps, sel_tmp, n_rand)
            idx_new = jnp.concatenate([idx_new, idx_rand], axis=1)
        selected = _mark_selected(ctx, state.selected, idx_new)
        start = r * k_s

        # exact CE scores for the new slab (Alg. 1 line 15)
        c_new = scored(query, idx_new)                         # (B, k_s)
        cols_new = _gather_cols(
            ctx, r_anc, idx_new, via_onehot=cfg.distributed_gather
        )                                                      # (B, k_q, k_s)

        anchor_idx = jax.lax.dynamic_update_slice(
            state.anchor_idx, idx_new, (0, start)
        )
        c_test = jax.lax.dynamic_update_slice(state.c_test, c_new, (0, start))

        # APPROXSCORES state update (Alg. 2) over the padded buffers
        if cfg.incremental_pinv:
            p = jax.vmap(cur.block_pinv_extend_static, in_axes=(0, 0, 0, None))(
                state.a_buf, state.p, cols_new, start
            )
            a_buf = jax.lax.dynamic_update_slice(
                state.a_buf, cols_new, (0, 0, start)
            )
        else:
            a_buf = jax.lax.dynamic_update_slice(
                state.a_buf, cols_new, (0, 0, start)
            )
            p = cur.pinv(a_buf, cfg.pinv_rcond)     # zero cols -> zero rows
        e_q = jnp.einsum("bk,bkq->bq", c_test, p, precision=cur.HIGHEST)
        return EngineState(anchor_idx, c_test, a_buf, p, e_q, selected)

    def body(r, state: EngineState) -> EngineState:
        return apply(r, state, sample(r, state))

    return sample, apply, body


def _make_round_body(
    scored: ScoreFn,
    r_anc: jax.Array,
    query,
    cfg: AdaCURConfig,
    keys: jax.Array,
    k_s: int,
    n_valid: Optional[int],
    ctx: ShardCtx,
) -> Callable[[jax.Array, EngineState], EngineState]:
    """The staged round body — ``apply ∘ sample`` (see _make_round_steps)."""
    return _make_round_steps(
        scored, r_anc, query, cfg, keys, k_s, n_valid, ctx
    )[2]


def _provisional_topk(
    cfg: AdaCURConfig, e_q, r_anc, m: int, n_valid, invalid=None,
    ctx: Optional[ShardCtx] = None,
):
    """Top-m candidate ids of S_hat (unmasked) — the early-exit monitor.

    ``invalid`` is the runtime invalid-column mask of a dynamic corpus
    (padded capacity) — (N_local,), or (B, N_local) when a per-query
    eligibility restriction is in play; it replaces the static ``n_valid``
    bound.  Returns global ids (merged on a sharded context)."""
    ctx = ctx or _local_ctx(r_anc.shape[1])
    sharded = ctx.item_axes is not None
    if invalid is not None and invalid.ndim == 1:
        invalid = invalid[None, :]
    if cfg.use_fused_topk:
        mask = (
            None if invalid is None
            else jnp.broadcast_to(invalid, (e_q.shape[0], r_anc.shape[1]))
        )
        v, idx = approx_topk_op(
            e_q, r_anc, m, tile=_effective_tile(cfg, r_anc),
            n_valid=None if sharded else n_valid, mask=mask,
        )
        if not sharded:
            return idx
        return _merge_topk(ctx, v, idx + _item_offset(ctx), m)[1]
    s_hat = quant.matmul(e_q, r_anc)
    if n_valid is not None and not sharded and n_valid < s_hat.shape[1]:
        s_hat = jnp.where(jnp.arange(s_hat.shape[1]) < n_valid, s_hat, sampling.NEG_INF)
    if invalid is not None:
        s_hat = jnp.where(invalid, sampling.NEG_INF, s_hat)
    return _local_topk_merge(ctx, s_hat, m)


def _pad_short_ranking(top_idx: jax.Array, top_s: jax.Array):
    """Keep under-filled rankings well-formed for callers.

    When a runtime ``n_rounds`` override or early exit leaves fewer filled
    candidates than ``k_retrieve``, trailing top-k slots would otherwise
    carry the -1 id sentinel with NEG_INF scores all the way to service
    responses.  Repeat the row-best candidate instead (top_k sorts
    descending, so position 0 is always a valid, exact-scored item)."""
    ok = top_s > 0.5 * sampling.NEG_INF
    return (
        jnp.where(ok, top_idx, top_idx[:, :1]),
        jnp.where(ok, top_s, top_s[:, :1]),
    )


def engine_search(
    score_fn: ScoreFn,
    r_anc: jax.Array,
    query,
    cfg: AdaCURConfig,
    key: jax.Array,
    first_anchors: Optional[jax.Array] = None,
    batch: Optional[int] = None,
    n_valid_items=None,
    n_rounds=None,
    return_scores: Optional[bool] = None,
    item_ids: Optional[jax.Array] = None,
    eligible: Optional[jax.Array] = None,
    pos_map: Optional[jax.Array] = None,
    item_tokens: Optional[jax.Array] = None,
    deadline: Optional[AnytimeDeadline] = None,
    _ctx: Optional[ShardCtx] = None,
) -> AdaCURResult:
    """Run Algorithm 1 (+ retrieval) through the static-shape round engine.

    Mirrors :func:`repro.core.adacur.adacur_search` (same RNG stream, same
    budget accounting) with three extensions:

    - ``n_rounds``: runtime round-count override (``loop_mode='fori'`` only;
      may be a traced int32 ≤ ``cfg.n_rounds``).  Slabs beyond the executed
      rounds stay empty and are masked out of the final ranking, so one
      compiled executable serves every round count.
    - early exit: with ``cfg.early_exit_tol > 0`` the loop stops once the
      batch-mean overlap of consecutive provisional top-``k_retrieve`` sets
      reaches ``1 - tol``; ``AdaCURResult.rounds_done`` reports the count.
    - ``return_scores``: the (B, N) ``approx_scores`` field is only
      materialized on request (defaults to the dense path's behavior; the
      fused path defaults to ``None`` so no (B, N) buffer ever exists).

    Two further extensions serve the :class:`~repro.core.index.AnchorIndex`
    lifecycle: ``n_valid_items`` may be a *traced* int32 (dynamic corpora —
    growing/shrinking the valid prefix of a padded index never retraces),
    and ``item_ids`` (N,) maps engine positions to external corpus ids
    before every ``score_fn`` call.

    ``r_anc`` may be an fp32/bf16 array or an int8
    :class:`~repro.kernels.approx_topk.quant.QuantizedRanc` payload;
    ``cfg.payload_dtype`` converts a plain array up to the configured
    payload inside the trace (an AnchorIndex-backed retriever pre-quantizes
    instead — see ``Retriever.from_index``).

    Multi-stage retrieval (``core/candidates.py``) adds two runtime
    operands.  ``eligible`` — (N,) or per-query (B, N) bool — restricts the
    search to a candidate set over the full corpus: ineligible items are
    never sampled, never reranked, and are excluded from the early-exit
    monitor, while CE accounting is untouched (:func:`ce_call_plan` holds
    verbatim — the first stage spends no CE calls and every round still
    scores exactly k_s items, so callers must supply at least
    ``budget_ce`` eligible items per row).  ``pos_map`` — (N,) int32,
    ascending — declares the engine's columns to be a *candidate subset*
    gathered from those global corpus positions (see
    :func:`quant.subset_columns`): all noise draws then evaluate the
    canonical field at the mapped coordinates, which makes the subset
    search bit-identical to an ``eligible``-masked full-corpus search
    (ascending order preserves the ascending-id tie-break contract).
    Result indices stay in engine-local (subset) coordinates; callers remap
    through ``pos_map`` (as :class:`HybridRetriever` does).

    Device-resident scorers (``score_fn.device_resident``, e.g.
    :class:`~repro.core.scorer.DeviceCEScorer`) score *in-trace* instead of
    through a host callback: ``query`` is then the (B, Lq) query token
    batch and ``item_tokens`` the (N, Li) corpus token table
    (position-indexed, like the payload — ``item_ids`` never applies), from
    which pair rows are gathered and the CE forward runs inside the engine
    program (:func:`_device_ce_score`).  Defaults to the scorer's own
    ``item_tokens`` table when the operand is omitted.

    ``deadline`` (an :class:`AnytimeDeadline`) makes the search *anytime*:
    the round loop additionally polls the armed wall-clock deadline and
    exits early when it expires, returning the provisional top-k built from
    the rounds completed so far (``rounds_done`` reports the count and the
    unfilled slabs are masked out of the ranking exactly as under a runtime
    ``n_rounds`` override).  Requires ``loop_mode='fori'`` and is rejected
    under a shard context (per-shard clocks would disagree on the iteration
    count and deadlock the collectives).

    ``_ctx`` is the shard context when this call is the per-shard body of
    the SPMD engine (:func:`make_sharded_engine`); ``r_anc``/``item_ids``
    are then this shard's LOCAL slabs and ``query`` the local batch rows,
    while ``n_valid_items`` stays the GLOBAL valid count.
    """
    r_anc = quant.as_payload(r_anc, cfg.payload_dtype, cfg.payload_tile)
    k_q, n_items = r_anc.shape
    if pos_map is not None and _ctx is not None:
        raise ValueError(
            "pos_map (candidate-subset search) is single-shard only; under "
            "a mesh use the eligible mask over the sharded full corpus"
        )
    ctx = _ctx or _local_ctx(n_items, pos_map)
    sharded = ctx.item_axes is not None
    n_global = n_items * ctx.n_item_shards
    k_i = cfg.budget_ce if not cfg.split_budget else cfg.k_anchor
    r_max = cfg.n_rounds
    if k_i % r_max != 0:
        raise ValueError(f"k_i={k_i} not divisible by n_rounds={r_max}")
    k_s = k_i // r_max
    if return_scores is None:
        return_scores = not cfg.use_fused_topk and not sharded
    if sharded and return_scores:
        raise ValueError(
            "return_scores is unavailable under the sharded engine: the "
            "(B, N) approximate score matrix is exactly what sharding "
            "refuses to materialize"
        )
    n_valid = None
    invalid = None                        # (N_local,) runtime invalid mask
    if sharded:
        # the sharded engine is always on the dynamic-mask path: validity is
        # a local column mask derived from the (replicated) global bound
        nv = jnp.minimum(
            jnp.asarray(
                n_global if n_valid_items is None else n_valid_items, jnp.int32
            ),
            n_global,
        )
        local_pos = _item_offset(ctx) + jnp.arange(n_items, dtype=jnp.int32)
        invalid = local_pos >= nv
    elif n_valid_items is not None:
        if isinstance(n_valid_items, (int, np.integer)):
            if n_valid_items < n_items:
                n_valid = int(n_valid_items)
        else:
            nv = jnp.minimum(jnp.asarray(n_valid_items, jnp.int32), n_items)
            invalid = jnp.arange(n_items, dtype=jnp.int32) >= nv
    if eligible is not None:
        eligible = jnp.asarray(eligible, bool)
        if eligible.ndim == 1:
            eligible = eligible[None, :]
    # the early-exit monitor's invalid mask: padded tail + ineligible items
    mon_invalid = invalid
    if eligible is not None:
        mon_invalid = (
            ~eligible if invalid is None else (~eligible | invalid[None, :])
        )
    if cfg.loop_mode == "unrolled" and n_rounds is not None:
        raise ValueError("runtime n_rounds override requires loop_mode='fori'")
    if deadline is not None:
        if cfg.loop_mode != "fori":
            raise ValueError(
                "an anytime deadline needs the shape-invariant round loop: "
                "use loop_mode='fori'"
            )
        if _ctx is not None:
            raise ValueError(
                "anytime deadlines are single-device only: per-shard clocks "
                "would disagree on the round count and deadlock the SPMD "
                "program's collectives — the serving tier's unit of "
                "redundancy is the replica, not the shard"
            )

    if first_anchors is not None:
        b = first_anchors.shape[0]
        if first_anchors.shape[1] != k_s:
            raise ValueError(
                f"first_anchors must provide k_s={k_s} items, got {first_anchors.shape}"
            )
    elif batch is not None:
        b = batch
    else:
        b = jax.tree_util.tree_leaves(query)[0].shape[0]

    # the score-once wrapper: positions -> external ids -> exactly one CE
    # call per pair system-wide (item shard 0 scores, psum broadcasts) —
    # or, for device-resident scorers, positions -> token rows -> the CE
    # forward in-trace, split across the item shards
    if getattr(score_fn, "device_resident", False):
        if item_tokens is None:
            item_tokens = getattr(score_fn, "item_tokens", None)
        if item_tokens is None:
            raise ValueError(
                "a device-resident scorer needs the corpus token table: pass "
                "item_tokens= (carried by AnchorIndex.with_item_tokens) or "
                "construct the scorer with one"
            )
        if item_tokens.shape[0] != n_items:
            raise ValueError(
                f"item_tokens rows ({item_tokens.shape[0]}) must match the "
                f"payload's item capacity ({n_items}); the token table is "
                f"position-indexed alongside r_anc"
            )

        def scored(q, gidx, _tok=item_tokens):
            return _device_ce_score(ctx, score_fn, q, gidx, _tok)
    elif sharded:
        score_dtype = jax.eval_shape(
            lambda q, i: score_fn(q, i),
            query, jax.ShapeDtypeStruct((b, k_s), jnp.int32),
        ).dtype

        def scored(q, gidx):
            ids = gidx if item_ids is None else _map_item_ids(ctx, item_ids, gidx)
            return _score_once(ctx, score_fn, q, ids, score_dtype)
    elif item_ids is not None:
        def scored(q, gidx, _f=score_fn, _ids=item_ids):
            return _f(q, jnp.take(_ids, gidx, axis=0))
    else:
        scored = score_fn

    selected = jnp.zeros((b, n_items), dtype=bool)
    if n_valid is not None:
        selected = selected | (jnp.arange(n_items) >= n_valid)
    if invalid is not None:
        selected = selected | invalid[None, :]
    if eligible is not None:
        selected = selected | ~eligible

    # same RNG stream as the seed path: keys[r] drives round r
    keys = jax.random.split(key, r_max + 1)

    # --- round 0 (static): random or retriever-seeded first anchors --------
    if first_anchors is not None and cfg.first_round == "retriever":
        idx0 = first_anchors
    else:
        idx0 = _sample_random_ctx(ctx, keys[0], selected, k_s)
    selected = _mark_selected(ctx, selected, idx0)
    c0 = scored(query, idx0)                                   # (B, k_s)
    cols0 = _gather_cols(ctx, r_anc, idx0, via_onehot=cfg.distributed_gather)

    dtype = c0.dtype
    anchor_idx = jnp.full((b, k_i), -1, jnp.int32)
    anchor_idx = anchor_idx.at[:, :k_s].set(idx0.astype(jnp.int32))
    c_test = jnp.zeros((b, k_i), dtype).at[:, :k_s].set(c0)
    a_buf = jnp.zeros((b, k_q, k_i), cols0.dtype).at[:, :, :k_s].set(cols0)

    # rerank-only configurations (one retriever round, no budget split) never
    # read S_hat: skip the pinv/e_q machinery entirely.
    needs_scores = cfg.split_budget or return_scores or r_max > 1
    if needs_scores:
        p = jnp.zeros((b, k_i, k_q), dtype)
        p0 = (
            cur.incremental_pinv_init(cols0, cfg.pinv_rcond)
            if cfg.incremental_pinv
            else cur.pinv(cols0, cfg.pinv_rcond)
        )
        p = p.at[:, :k_s, :].set(p0)
        e_q = jnp.einsum("bk,bkq->bq", c_test, p, precision=cur.HIGHEST)
    else:
        p = jnp.zeros((b, k_i, k_q), dtype)
        e_q = jnp.zeros((b, k_q), dtype)
    state = EngineState(anchor_idx, c_test, a_buf, p, e_q, selected)

    sample_step, apply_step, body = _make_round_steps(
        scored, r_anc, query, cfg, keys, k_s, n_valid, ctx
    )

    # --- rounds 1..n_rounds-1 ----------------------------------------------
    if cfg.loop_mode == "unrolled":
        for r in range(1, r_max):
            state = body(r, state)
        rounds_done = jnp.asarray(r_max, jnp.int32)
    else:
        r_dyn = jnp.asarray(r_max if n_rounds is None else n_rounds, jnp.int32)
        r_dyn = jnp.clip(r_dyn, 1, r_max)
        if cfg.early_exit_tol > 0.0 and cfg.round_kernel == "persistent":
            # software-pipelined monitored loop: round r+1's anchor sample
            # and round r's provisional monitor ride ONE persistent payload
            # sweep.  Legal because the sample at round r+1 reads exactly
            # the state apply(r) finalized — the same (e_q, selected, key)
            # the staged loop would hand it one iteration later — so every
            # value (and rounds_done) is bit-identical to the staged loop;
            # only the number of payload passes halves.
            m = min(cfg.k_retrieve, n_global)
            pending, prev = sample_step(1, state, monitor=(m, mon_invalid))

            def cond(carry):
                r, frac, _, _, _ = carry
                go = (r < r_dyn) & (frac < 1.0 - cfg.early_exit_tol)
                if deadline is not None:
                    go = go & ~deadline.expired(r)
                return go

            def while_body(carry):
                r, _, st, prev_top, pend = carry
                st = apply_step(r, st, pend)
                pend_next, cur_top = sample_step(
                    r + 1, st, monitor=(m, mon_invalid)
                )
                hit = (cur_top[:, :, None] == prev_top[:, None, :]).any(-1)
                return r + 1, _global_frac(ctx, hit), st, cur_top, pend_next

            rounds_done, _, state, _, _ = jax.lax.while_loop(
                cond, while_body,
                (jnp.int32(1), jnp.float32(0.0), state, prev, pending),
            )
        elif cfg.early_exit_tol > 0.0:
            m = min(cfg.k_retrieve, n_global)
            prev = _provisional_topk(
                cfg, state.e_q, r_anc, m, n_valid, mon_invalid, ctx
            )

            def cond(carry):
                r, frac, _, _ = carry
                go = (r < r_dyn) & (frac < 1.0 - cfg.early_exit_tol)
                if deadline is not None:
                    go = go & ~deadline.expired(r)
                return go

            def while_body(carry):
                r, _, st, prev_top = carry
                st = body(r, st)
                cur_top = _provisional_topk(
                    cfg, st.e_q, r_anc, m, n_valid, mon_invalid, ctx
                )
                hit = (cur_top[:, :, None] == prev_top[:, None, :]).any(-1)
                return r + 1, _global_frac(ctx, hit), st, cur_top

            rounds_done, _, state, _ = jax.lax.while_loop(
                cond, while_body, (jnp.int32(1), jnp.float32(0.0), state, prev)
            )
        elif deadline is not None:
            # anytime loop: same math as the fori path, but the cond also
            # polls the armed wall-clock deadline — a mid-search expiry exits
            # at the next round boundary with the provisional state so far
            def cond(carry):
                r, _ = carry
                return (r < r_dyn) & ~deadline.expired(r)

            def while_body(carry):
                r, st = carry
                return r + 1, body(r, st)

            rounds_done, state = jax.lax.while_loop(
                cond, while_body, (jnp.int32(1), state)
            )
        else:
            state = jax.lax.fori_loop(1, r_dyn, body, state)
            rounds_done = r_dyn

    anchor_idx, c_test = state.anchor_idx, state.c_test
    n_filled = rounds_done * k_s
    valid_slot = jnp.arange(k_i) < n_filled                    # (k_i,)
    anchor_logits = jnp.where(valid_slot[None, :], c_test, sampling.NEG_INF)
    s_hat = quant.matmul(state.e_q, r_anc) if return_scores else None

    # --- retrieval ---------------------------------------------------------
    if not cfg.split_budget:
        # ADACUR^No-Split: rank the anchors by their exact CE scores (free).
        k = min(cfg.k_retrieve, k_i)
        top_s, top_pos = jax.lax.top_k(anchor_logits, k)
        top_idx = jnp.take_along_axis(anchor_idx, top_pos, axis=1)
        top_idx, top_s = _pad_short_ranking(top_idx, top_s)
        return AdaCURResult(
            anchor_idx, c_test, s_hat, top_idx, top_s, ce_call_plan(cfg),
            rounds_done,
        )

    # ADACUR (split): spend the remaining budget on fresh exact CE calls for
    # the top approximate-scoring non-anchor items.
    k_r = cfg.budget_ce - k_i
    if cfg.use_fused_topk:
        v_r, rerank_idx = approx_topk_op(
            state.e_q, r_anc, k=k_r, tile=_effective_tile(cfg, r_anc),
            n_valid=None if sharded else n_valid, mask=state.selected,
        )
        if sharded:
            _, rerank_idx = _merge_topk(
                ctx, v_r, rerank_idx + _item_offset(ctx), k_r
            )
    else:
        full = s_hat if s_hat is not None else quant.matmul(state.e_q, r_anc)
        masked = jnp.where(state.selected, sampling.NEG_INF, full)
        rerank_idx = _local_topk_merge(ctx, masked, k_r)       # (B, k_r)
    rerank_scores = scored(query, rerank_idx)                  # k_r CE calls
    pool_idx = jnp.concatenate([anchor_idx, rerank_idx], axis=1)
    pool_scores = jnp.concatenate([anchor_logits, rerank_scores], axis=1)
    k = min(cfg.k_retrieve, pool_idx.shape[1])
    top_s, top_pos = jax.lax.top_k(pool_scores, k)
    top_idx = jnp.take_along_axis(pool_idx, top_pos, axis=1)
    top_idx, top_s = _pad_short_ranking(top_idx, top_s)
    return AdaCURResult(
        anchor_idx, c_test, s_hat, top_idx, top_s, ce_call_plan(cfg),
        rounds_done,
    )


def make_engine(
    score_fn: ScoreFn,
    cfg: AdaCURConfig,
    n_valid_items=None,
    return_scores: Optional[bool] = None,
    jit_compile: bool = True,
    anytime: bool = False,
):
    """jit-compiled engine closure over a concrete scorer + config.

    In ``fori`` mode the returned callable takes an optional runtime
    ``n_rounds`` (any value in [1, cfg.n_rounds]) *without retracing* — the
    round count is a traced operand of one compiled executable.  ``n_valid``
    and ``item_ids`` are likewise traced operands (AnchorIndex dynamic
    corpora: mutation changes their *values*, never the trace).

    ``jit_compile=False`` runs the engine eagerly (``loop_mode='unrolled'``
    only) so non-traceable scorers — numpy tokenizers, external CE services —
    still go through the one engine code path.

    ``anytime=True`` (``fori`` mode only) threads an :class:`AnytimeDeadline`
    through the round loop and exposes it as ``run.deadline``: arm it with
    an absolute ``time.monotonic()`` deadline before a search and the loop
    exits at the first round boundary past it, returning the provisional
    top-k of the rounds completed (``rounds_done`` + ``deadline.fired``
    tell the serving layer to flag the response degraded).  Costs one
    numpy-only host callback per executed round, so it is opt-in.
    """
    if not jit_compile and cfg.loop_mode != "unrolled":
        raise ValueError("jit_compile=False requires loop_mode='unrolled'")
    deadline = None
    if anytime:
        if cfg.loop_mode != "fori":
            raise ValueError("anytime=True requires loop_mode='fori' (the "
                             "deadline cuts a runtime round loop)")
        deadline = AnytimeDeadline()

    def _run(r_anc, query, key, n_rounds, first_anchors=None, batch=None,
             n_valid=None, item_ids=None, eligible=None, pos_map=None,
             item_tokens=None):
        return engine_search(
            score_fn, r_anc, query, cfg, key,
            first_anchors=first_anchors, batch=batch,
            n_valid_items=n_valid if n_valid is not None else n_valid_items,
            n_rounds=n_rounds, return_scores=return_scores, item_ids=item_ids,
            eligible=eligible, pos_map=pos_map, item_tokens=item_tokens,
            deadline=deadline,
        )

    if jit_compile:
        _run = partial(jax.jit, static_argnames=("batch",))(_run)

    def operands(r_anc, query, key, first_anchors=None, batch=None,
                 n_rounds=None, n_valid=None, item_ids=None, eligible=None,
                 pos_map=None, item_tokens=None):
        if cfg.loop_mode == "fori":
            n_rounds = jnp.asarray(
                cfg.n_rounds if n_rounds is None else n_rounds, jnp.int32
            )
        elif n_rounds is not None:
            raise ValueError("runtime n_rounds override requires loop_mode='fori'")
        if n_valid is not None:
            n_valid = jnp.asarray(n_valid, jnp.int32)
        return (r_anc, query, key, n_rounds, first_anchors, batch,
                n_valid, item_ids, eligible, pos_map, item_tokens)

    def run(*args, **kw):
        return _run(*operands(*args, **kw))

    if jit_compile:
        # the compiled program behind ``run`` for these operands
        run.lower = lambda *args, **kw: _run.lower(*operands(*args, **kw))
    run.deadline = deadline
    return run


def _payload_specs(r_anc, item_axes: Tuple[str, ...]):
    """shard_map in_spec tree for the payload operand: codes column-sharded,
    per-tile scales co-sharded on the same axes.

    The spec tree must carry the operand's static meta (tile, code_dtype,
    n_cols) verbatim or the pytree structures mismatch.  Packed int4 shards
    cleanly because shard slabs are even (whole even tiles), so the packed
    byte axis divides exactly and the ``n_cols=-1`` "2x the packed width"
    sentinel stays correct per shard."""
    if isinstance(r_anc, QuantizedRanc):
        return QuantizedRanc(
            codes=P(None, item_axes), scales=P(item_axes), tile=r_anc.tile,
            code_dtype=r_anc.code_dtype, n_cols=r_anc.n_cols,
        )
    return P(None, item_axes)


def make_sharded_engine(
    score_fn: ScoreFn,
    cfg: AdaCURConfig,
    mesh: Mesh,
    *,
    item_axes: Tuple[str, ...] = ("items",),
    data_axes: Optional[Tuple[str, ...]] = None,
    n_valid_items=None,
    jit_compile: bool = True,
):
    """The SPMD engine: one ``shard_map`` program over a (data x items) mesh.

    The returned callable has :func:`make_engine`'s signature.  Inside, the
    whole multi-round search — estimate, fused score->sample, CE scoring,
    incremental pinv / e_q update, provisional top-k, rerank — is the
    per-shard math core of :func:`engine_search` on a live :class:`ShardCtx`:

    - ``item_axes`` shard the payload (fp32 columns, or int8 codes with
      their co-sharded per-tile scales), the per-shard ``selected`` slab and
      the ``item_ids`` map; per-round candidates cross shards only as
      (B, k) lists through the documented tie-break merge;
    - ``data_axes`` (default: every mesh axis not in ``item_axes`` named
      ``pod``/``data``) shard the query batch; the blocked noise field keys
      off global row ids, so the data split never changes a trajectory;
    - the pinv/e_q state replicates — it is O(B·k_i·k_q), mesh-independent.

    Results are **bit-identical** to the single-device engine for every
    loop mode and payload dtype (the collective-layer contracts; asserted
    by ``tests/test_multidevice.py``).  ``n_rounds``, ``n_valid`` and the
    index's ``item_ids`` are traced operands of the one compiled program:
    runtime round counts and corpus mutation never retrace.

    Constraints checked here: the global batch divides over ``data_axes``;
    the capacity divides over ``item_axes`` into ``NOISE_BLOCK``-aligned
    slabs holding whole payload tiles (``AnchorIndex.shard`` guarantees
    this); and every per-shard candidate list (``k_s``, the rerank budget,
    ``k_retrieve``) fits in one shard's slab.

    Scorer constraint: the real cross-encoder runs as a *device-resident
    stage* of this program — pass a scorer with ``device_resident=True``
    (:class:`~repro.core.scorer.DeviceCEScorer`) plus the corpus token
    table (``item_tokens=``, carried by ``AnchorIndex.with_item_tokens``),
    and each round's pair assembly + transformer forward happen in-trace,
    split across the item shards, with no host round-trip.  Host-callback
    scorers remain acceptable when the callback is NUMPY-ONLY —
    ``TabulatedScorer`` (and ``CachingScorer`` over it) fire on item shard
    0 and psum-broadcast, which is exactly right for matrix lookups and
    tests.  What is *rejected* (at construction, via the scorer's
    ``nested_device_callback`` capability flag) is a host callback that
    launches nested device compute — ``CrossEncoderScorer``'s jitted
    forward deadlocks a single-process multi-device runtime, the nested
    launch contending with shards parked at the score-broadcast psum.
    """
    if not jit_compile:
        raise ValueError("the sharded engine is a compiled SPMD program; "
                         "jit_compile=False is only available unsharded")
    if getattr(score_fn, "nested_device_callback", False):
        raise ValueError(
            "this scorer's host callback launches nested device compute (a "
            "jitted CE forward) and would deadlock the SPMD program's psum "
            "rendezvous; under a mesh run the real CE device-resident "
            "(DeviceCEScorer + an index token table) — numpy-only callback "
            "scorers (TabulatedScorer, CachingScorer over it) stay supported"
        )
    item_axes = (item_axes,) if isinstance(item_axes, str) else tuple(item_axes)
    if data_axes is None:
        data_axes = tuple(
            a for a in mesh.axis_names
            if a not in item_axes and a in ("pod", "data")
        )
    data_axes = tuple(data_axes)
    n_item_shards = math.prod(mesh.shape[a] for a in item_axes)
    n_data_shards = math.prod(mesh.shape[a] for a in data_axes) if data_axes else 1
    k_i = cfg.budget_ce if not cfg.split_budget else cfg.k_anchor
    k_s = k_i // cfg.n_rounds
    k_r = cfg.budget_ce - k_i if cfg.split_budget else 0

    data_spec = P(data_axes) if data_axes else P()

    def _validate(r_anc, b_global):
        capacity = r_anc.shape[1]
        if capacity % n_item_shards:
            raise ValueError(
                f"capacity {capacity} not divisible over {n_item_shards} item "
                f"shards (AnchorIndex.shard aligns this)"
            )
        n_local = capacity // n_item_shards
        if n_item_shards > 1 and n_local % sampling.NOISE_BLOCK:
            raise ValueError(
                f"per-shard slab {n_local} must hold whole NOISE_BLOCK="
                f"{sampling.NOISE_BLOCK} noise blocks"
            )
        if isinstance(r_anc, QuantizedRanc) and n_local % r_anc.tile:
            raise ValueError(
                f"per-shard slab {n_local} must hold whole payload tiles "
                f"({r_anc.tile})"
            )
        need = max(k_s, k_r, min(cfg.k_retrieve, capacity))
        if need > n_local:
            raise ValueError(
                f"per-shard candidate list ({need}) exceeds the per-shard "
                f"slab ({n_local}); use fewer item shards"
            )
        if b_global % n_data_shards:
            raise ValueError(
                f"batch {b_global} not divisible over {n_data_shards} data shards"
            )
        return n_local

    def core(r_anc, query, key, n_rounds, n_valid, item_ids, first_anchors,
             eligible, item_tokens):
        n_local = r_anc.shape[1]
        b_local = jax.tree_util.tree_leaves(query)[0].shape[0]
        ctx = ShardCtx(
            item_axes=item_axes,
            data_axes=data_axes,
            n_local=n_local,
            n_item_shards=n_item_shards,
            item_shard=_axes_index(item_axes),
            row_offset=_axes_index(data_axes) * b_local if data_axes else 0,
        )
        res = engine_search(
            score_fn, r_anc, query, cfg, key,
            first_anchors=first_anchors,
            n_valid_items=n_valid, n_rounds=n_rounds,
            return_scores=False, item_ids=item_ids, eligible=eligible,
            item_tokens=item_tokens, _ctx=ctx,
        )
        return (res.anchor_idx, res.anchor_scores, res.topk_idx,
                res.topk_scores, res.rounds_done)

    compiled = {}          # (has_first, query treedef/ranks) -> jitted fn

    def run(r_anc, query, key, first_anchors=None, batch=None, n_rounds=None,
            n_valid=None, item_ids=None, eligible=None, pos_map=None,
            item_tokens=None):
        if pos_map is not None:
            raise ValueError(
                "pos_map (candidate-subset search) is single-shard only; "
                "pass eligible= to restrict a sharded search"
            )
        if cfg.loop_mode == "fori":
            n_rounds = jnp.asarray(
                cfg.n_rounds if n_rounds is None else n_rounds, jnp.int32
            )
        elif n_rounds is not None:
            raise ValueError("runtime n_rounds override requires loop_mode='fori'")
        if batch is not None:
            raise ValueError(
                "the sharded engine derives the batch from the query leaves "
                "(or first_anchors); the batch= override would leave the "
                "query un-shardable over the data axes — pass batched "
                "query operands instead"
            )
        r_anc = quant.as_payload(r_anc, cfg.payload_dtype, cfg.payload_tile)
        b = (
            first_anchors.shape[0] if first_anchors is not None
            else jax.tree_util.tree_leaves(query)[0].shape[0]
        )
        _validate(r_anc, b)
        capacity = r_anc.shape[1]
        if n_valid is None:
            n_valid = capacity if n_valid_items is None else n_valid_items
        n_valid = jnp.asarray(n_valid, jnp.int32)
        if item_ids is None:
            item_ids = jnp.arange(capacity, dtype=jnp.int32)
        if getattr(score_fn, "device_resident", False):
            if item_tokens is None:
                item_tokens = getattr(score_fn, "item_tokens", None)
            if item_tokens is None:
                raise ValueError(
                    "a device-resident scorer needs the corpus token table "
                    "under the mesh: pass item_tokens= (carried by "
                    "AnchorIndex.with_item_tokens) or construct the scorer "
                    "with one"
                )
            item_tokens = jnp.asarray(item_tokens, jnp.int32)
            if item_tokens.shape[0] != capacity:
                raise ValueError(
                    f"item_tokens rows ({item_tokens.shape[0]}) must match "
                    f"the payload capacity ({capacity}); the token table is "
                    f"position-aligned with r_anc (AnchorIndex keeps them in "
                    f"lockstep through mutation)"
                )
        else:
            item_tokens = None
        query_specs = jax.tree.map(
            lambda leaf: P(data_axes, *([None] * (jnp.ndim(leaf) - 1)))
            if data_axes else P(),
            query,
        )
        if eligible is not None:
            eligible = jnp.asarray(eligible, bool)
        sig = (
            first_anchors is not None,
            jax.tree_util.tree_structure(query),
            tuple(jnp.ndim(l) for l in jax.tree_util.tree_leaves(query)),
            quant.payload_dtype_of(r_anc),
            None if eligible is None else eligible.ndim,
            item_tokens is not None,
        )
        if sig not in compiled:
            if eligible is None:
                eligible_spec = None
            elif eligible.ndim == 1:
                eligible_spec = P(item_axes)
            else:
                eligible_spec = P(data_axes if data_axes else None, item_axes)
            in_specs = (
                _payload_specs(r_anc, item_axes),     # r_anc
                query_specs,                          # query
                P(),                                  # key
                P() if cfg.loop_mode == "fori" else None,  # n_rounds
                P(),                                  # n_valid
                P(item_axes),                         # item_ids
                data_spec if first_anchors is not None else None,
                eligible_spec,                        # eligible
                P(item_axes, None) if item_tokens is not None else None,
            )
            out_specs = (data_spec, data_spec, data_spec, data_spec, P())

            live_specs = tuple(s for s in in_specs if s is not None)

            def entry(r_anc, query, key, n_rounds, n_valid, item_ids,
                      first_anchors, eligible, item_tokens):
                args = (r_anc, query, key, n_rounds, n_valid, item_ids,
                        first_anchors, eligible, item_tokens)
                live = tuple(a for a, s in zip(args, in_specs) if s is not None)

                def body(*live_args):
                    it = iter(live_args)
                    full = tuple(
                        next(it) if s is not None else None for s in in_specs
                    )
                    return core(*full)

                return jax.shard_map(
                    body, mesh=mesh, in_specs=live_specs,
                    out_specs=out_specs, check_vma=False,
                )(*live)

            compiled[sig] = jax.jit(entry, static_argnums=())
        anchor_idx, c_test, top_idx, top_s, rounds_done = compiled[sig](
            r_anc, query, key, n_rounds, n_valid, item_ids, first_anchors,
            eligible, item_tokens,
        )
        return AdaCURResult(
            anchor_idx, c_test, None, top_idx, top_s,
            ce_call_plan(cfg), rounds_done,
        )

    return run


# ---------------------------------------------------------------------------
# Unified Retriever API — ADACUR / ANNCUR / retrieve-and-rerank as
# configurations of the one engine code path.
# ---------------------------------------------------------------------------


@runtime_checkable
class Retriever(Protocol):
    """Anything that answers a k-NN query batch under a CE-call budget."""

    def search(self, query, key: Optional[jax.Array] = None, **kw) -> AdaCURResult:
        ...


class _IndexBacked:
    """Shared plumbing for retrievers that consume an AnchorIndex.

    The index's arrays (``r_anc``, ``n_valid``, ``item_ids``) enter the
    compiled engine as *traced operands* read from ``self.index`` at every
    search, so swapping in a mutated index (``retriever.index = new_index``)
    changes values only — shapes are capacity-constant and nothing retraces.

    The runtime ``n_valid`` bound is only passed when the index is (or was
    constructed) padded: an unpadded index keeps the engine's static
    ``n_valid`` bound.  Removing items from an unpadded index flips it to
    the dynamic path (one retrace, then stable).

    ``cfg.payload_dtype`` is applied to the index ONCE at construction
    (:meth:`_apply_payload_policy`): the engine then receives an already
    bf16/int8 payload operand and never re-converts per call.  An index that
    is already quantized is authoritative and passes through unchanged.

    An index whose item axis is placed over a mesh (``AnchorIndex.shard`` /
    ``load(path, mesh)``) makes the retriever bind the **SPMD engine**
    (:func:`make_sharded_engine`) instead: the full multi-round search runs
    as one ``shard_map`` program with the payload item-sharded and the query
    batch sharded over the mesh's ``data``/``pod`` axes, bit-identical to
    the single-device engine.
    """

    def _build_engine(self, cfg: AdaCURConfig, n_valid_items=None,
                      return_scores: Optional[bool] = None,
                      jit_compile: bool = True,
                      anytime: bool = False) -> Callable:
        """make_engine or make_sharded_engine, by the index's placement."""
        idx = getattr(self, "index", None)
        mesh = axes = None
        if idx is not None:
            mesh, axes = idx._item_sharding()
        if mesh is None:
            self._sharded = False
            return make_engine(
                self.score_fn, cfg, n_valid_items,
                return_scores=return_scores, jit_compile=jit_compile,
                anytime=anytime,
            )
        if anytime:
            raise ValueError(
                "anytime deadlines are single-device only: a sharded engine "
                "polling per-shard clocks would diverge across shards and "
                "deadlock the SPMD collectives"
            )
        self._sharded = True
        return make_sharded_engine(
            self.score_fn, cfg, mesh, item_axes=axes,
            n_valid_items=n_valid_items, jit_compile=jit_compile,
        )

    def _apply_payload_policy(self, cfg: AdaCURConfig) -> None:
        idx = getattr(self, "index", None)
        if idx is None or cfg.payload_dtype == "float32":
            return
        if (idx.payload_dtype == cfg.payload_dtype
                or idx.payload_dtype in quant.CODE_DTYPES):
            # already compliant — or already quantized (int8/int4/fp8),
            # which is authoritative (mirrors quant.as_payload: the policy
            # converts payloads UP, it never requantizes a coded artifact)
            return
        mesh, _ = idx._item_sharding()
        new = idx.quantize(cfg.payload_dtype, tile=cfg.payload_tile)
        if mesh is not None:
            # re-place the converted payload: quantization is a reshaping
            # computation whose output placement XLA chooses freely
            new = new.shard(mesh)
        self.index = new

    def _prep_query(self, query):
        """Device-resident scorers take token operands: map a (B,) query-id
        batch through the scorer's host tokenizer (once, before the round
        loop); every other scorer passes the query through untouched."""
        tok = getattr(self.score_fn, "tokenize_queries", None)
        if tok is None:
            return query
        with telemetry.span("engine.tokenize"):
            return tok(query)

    def _search_operands(self):
        if self.index is None:
            return self.r_anc, {}
        kw = dict(item_ids=self.index.item_ids)
        if not getattr(self, "_dynamic_valid", False):
            # the padded? device->host sync runs once per index object, not
            # per search; once dynamic, the trace stays dynamic forever
            if getattr(self, "_seen_index", None) is not self.index:
                self._seen_index = self.index
                self._dynamic_valid = self.index.capacity > self.index.n_items
        if self._dynamic_valid:
            kw["n_valid"] = self.index.n_valid
        if (getattr(self.score_fn, "device_resident", False)
                and getattr(self.index, "item_tokens", None) is not None):
            # the index's table is authoritative: position-aligned with the
            # payload through every mutation (the scorer's own copy is not)
            kw["item_tokens"] = self.index.item_tokens
        return self.index.r_anc, kw


@dataclass
class AdaCURRetriever(_IndexBacked):
    """The paper's method (Alg. 1) on the static-shape engine."""

    score_fn: ScoreFn
    r_anc: Optional[jax.Array]
    cfg: AdaCURConfig
    n_valid_items: Optional[int] = None
    index: Optional[object] = None       # repro.core.index.AnchorIndex
    jit: bool = True
    anytime: bool = False
    _run: Callable = field(init=False, repr=False)

    def __post_init__(self):
        if self.r_anc is None and self.index is None:
            raise ValueError("need r_anc or an AnchorIndex")
        self._apply_payload_policy(self.cfg)
        self._run = self._build_engine(
            self.cfg, self.n_valid_items, jit_compile=self.jit,
            anytime=self.anytime,
        )
        self.deadline = getattr(self._run, "deadline", None)

    @classmethod
    def from_index(cls, index, score_fn: ScoreFn, cfg: AdaCURConfig,
                   jit: bool = True, anytime: bool = False) -> "AdaCURRetriever":
        """Bind the engine to an :class:`~repro.core.index.AnchorIndex`:
        ``score_fn`` receives *external item ids* (the engine maps positions
        through ``index.item_ids``), padded capacity is masked through the
        runtime ``n_valid`` bound, and index mutation never retraces."""
        return cls(score_fn, None, cfg, index=index, jit=jit, anytime=anytime)

    def lower(self, query, key=None):
        """The engine program :meth:`search` runs for this query batch, as a
        ``jax.stages.Lowered`` — ``.compile().as_text()`` shows which
        kernels it contains.  Single-device compiled engines only."""
        if self._sharded or not self.jit:
            raise ValueError("lower() covers the single-device compiled engine")
        key = jax.random.PRNGKey(0) if key is None else key
        r_anc, kw = self._search_operands()
        return self._run.lower(r_anc, self._prep_query(query), key, **kw)

    def search(self, query, key=None, first_anchors=None, batch=None,
               n_rounds=None, deadline_t=None, **_ignored):
        if deadline_t is not None and self.deadline is None:
            raise ValueError("deadline_t= requires anytime=True at construction")
        with telemetry.span("engine.dispatch"):
            key = jax.random.PRNGKey(0) if key is None else key
            query = self._prep_query(query)
            r_anc, kw = self._search_operands()
            if deadline_t is None:
                return self._run(
                    r_anc, query, key, first_anchors=first_anchors,
                    batch=batch, n_rounds=n_rounds, **kw,
                )
            # arm -> run -> *block* -> disarm: the dispatch is async, so the
            # deadline must stay armed until the round loop has executed (the
            # span then covers the device's run too); ``deadline.fired`` then
            # tells the caller whether the answer is a provisional (degraded)
            # top-k of ``rounds_done`` rounds.
            self.deadline.arm(deadline_t)
            try:
                res = self._run(
                    r_anc, query, key, first_anchors=first_anchors,
                    batch=batch, n_rounds=n_rounds, **kw,
                )
                jax.block_until_ready(res.topk_idx)
                return res
            finally:
                self.deadline.disarm()


@dataclass
class ANNCURRetriever(_IndexBacked):
    """Fixed-anchor one-round special case (Yadav et al. 2022).

    The offline index is just the anchor id set; ``search`` is one
    retriever-seeded engine round followed by the split-budget rerank — the
    identical code path ADACUR uses, at ``n_rounds=1``.  With
    ``budget_ce == k_anchor`` there is no rerank budget left and the final
    ranking is the free exact-score ranking of the anchors themselves
    (the engine's no-split configuration).
    """

    score_fn: ScoreFn
    r_anc: Optional[jax.Array]
    anchor_idx: Optional[jax.Array]      # (k_i,) fixed anchor item positions
    budget_ce: int = 0
    k_retrieve: int = 100
    pinv_rcond: float = 1e-6
    base_cfg: Optional[AdaCURConfig] = None
    index: Optional[object] = None       # repro.core.index.AnchorIndex
    jit: bool = True
    _run: Callable = field(init=False, repr=False)

    def __post_init__(self):
        if self.anchor_idx is None:
            if self.index is None or self.index.anchor_item_pos is None:
                raise ValueError(
                    "need anchor_idx or an AnchorIndex with anchors "
                    "(index.with_anchors() / with_latents())"
                )
            k_i = int(self.index.anchor_item_pos.shape[0])
        else:
            k_i = int(self.anchor_idx.shape[0])
        if self.r_anc is None and self.index is None:
            raise ValueError("need r_anc or an AnchorIndex")
        if self.budget_ce < k_i:
            raise ValueError(f"budget_ce={self.budget_ce} < k_anchor={k_i}")
        base = self.base_cfg or AdaCURConfig()
        split = self.budget_ce > k_i
        self.cfg = replace(
            base, k_anchor=k_i, n_rounds=1, budget_ce=self.budget_ce,
            split_budget=split, first_round="retriever",
            k_retrieve=self.k_retrieve, pinv_rcond=self.pinv_rcond,
            round_epsilon=0.0, early_exit_tol=0.0,
        )
        self._apply_payload_policy(self.cfg)
        self._run = self._build_engine(self.cfg, jit_compile=self.jit)

    @classmethod
    def from_index(cls, index, score_fn: ScoreFn, budget_ce: int,
                   k_retrieve: int = 100, pinv_rcond: float = 1e-6,
                   base_cfg: Optional[AdaCURConfig] = None,
                   jit: bool = True) -> "ANNCURRetriever":
        """ANNCUR over an :class:`~repro.core.index.AnchorIndex` that carries
        latents; anchors are read from the index at every search, so a
        mutated index (whose anchor positions may have been compacted) is
        picked up without retracing."""
        return cls(score_fn, None, None, budget_ce, k_retrieve, pinv_rcond,
                   base_cfg, index=index, jit=jit)

    def search(self, query, key=None, **kw):
        with telemetry.span("engine.dispatch"):
            key = jax.random.PRNGKey(0) if key is None else key
            query = self._prep_query(query)
            anchors = (
                self.index.anchor_item_pos
                if self.anchor_idx is None else self.anchor_idx
            )
            b = jax.tree_util.tree_leaves(query)[0].shape[0]
            first = jnp.broadcast_to(
                anchors[None, :].astype(jnp.int32), (b, anchors.shape[0])
            )
            r_anc, opkw = self._search_operands()
            return self._run(r_anc, query, key, first_anchors=first, **opkw)


@dataclass
class RerankRetriever(_IndexBacked):
    """Retrieve-and-rerank baseline: one retriever-seeded round, no split.

    Every candidate is exact-CE scored (they *are* the anchors) and the
    final ranking is the free top-k over those scores — i.e.
    ``retrieval.rerank_baseline`` expressed as an engine configuration.
    """

    score_fn: ScoreFn
    r_anc: Optional[jax.Array]
    budget_ce: int = 0
    k_retrieve: int = 100
    base_cfg: Optional[AdaCURConfig] = None
    index: Optional[object] = None       # repro.core.index.AnchorIndex
    jit: bool = True
    _run: Callable = field(init=False, repr=False)

    def __post_init__(self):
        if self.r_anc is None and self.index is None:
            raise ValueError("need r_anc or an AnchorIndex")
        base = self.base_cfg or AdaCURConfig()
        self.cfg = replace(
            base, k_anchor=self.budget_ce, n_rounds=1,
            budget_ce=self.budget_ce, split_budget=False,
            first_round="retriever", k_retrieve=self.k_retrieve,
            round_epsilon=0.0, early_exit_tol=0.0,
        )
        self._apply_payload_policy(self.cfg)
        # pure rerank never reads S_hat: skip the pinv/e_q machinery
        self._run = self._build_engine(
            self.cfg, return_scores=False, jit_compile=self.jit
        )

    @classmethod
    def from_index(cls, index, score_fn: ScoreFn, budget_ce: int,
                   k_retrieve: int = 100,
                   base_cfg: Optional[AdaCURConfig] = None,
                   jit: bool = True) -> "RerankRetriever":
        return cls(score_fn, None, budget_ce, k_retrieve, base_cfg,
                   index=index, jit=jit)

    def search(self, query, key=None, candidate_idx=None, **kw):
        if candidate_idx is None:
            raise ValueError("RerankRetriever.search needs candidate_idx (B, >=budget)")
        with telemetry.span("engine.dispatch"):
            key = jax.random.PRNGKey(0) if key is None else key
            query = self._prep_query(query)
            first = candidate_idx[:, : self.budget_ce].astype(jnp.int32)
            r_anc, opkw = self._search_operands()
            return self._run(r_anc, query, key, first_anchors=first, **opkw)


# ---------------------------------------------------------------------------
# Introspection: prove the fused path never materializes (B, N) scores.
# ---------------------------------------------------------------------------


def _iter_sub_jaxprs(params: dict):
    """Jaxprs nested in an eqn's params (scan/while/cond/pallas bodies).

    Duck-typed walk instead of jax.core.jaxprs_in_params — that helper is
    private and has moved across JAX releases."""
    for val in params.values():
        for item in val if isinstance(val, (tuple, list)) else (val,):
            j = getattr(item, "jaxpr", item)   # ClosedJaxpr -> Jaxpr
            if hasattr(j, "eqns"):
                yield j


def _count_bn_floats(jaxpr, b: int, n: int) -> int:
    """Recursively count eqn outputs with float aval of shape (b, n)."""
    count = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if (
                aval is not None
                and getattr(aval, "shape", None) == (b, n)
                and jnp.issubdtype(aval.dtype, jnp.floating)
            ):
                count += 1
        for sub in _iter_sub_jaxprs(eqn.params):
            count += _count_bn_floats(sub, b, n)
    return count


def round_body_bn_intermediates(
    score_fn: ScoreFn,
    r_anc: jax.Array,
    query,
    cfg: AdaCURConfig,
    batch: Optional[int] = None,
) -> int:
    """Number of (B, N) float intermediates in ONE adaptive round body.

    Dense sampling scores every item each round (>= 1); the fused-kernel
    TopK path must report 0 — the per-round claim behind the Fig. 4
    latency argument, checked by jaxpr inspection rather than trust.
    """
    r_anc = quant.as_payload(r_anc, cfg.payload_dtype, cfg.payload_tile)
    k_q, n_items = r_anc.shape
    k_i = cfg.budget_ce if not cfg.split_budget else cfg.k_anchor
    k_s = k_i // cfg.n_rounds
    b = batch or jax.tree_util.tree_leaves(query)[0].shape[0]
    keys = jax.random.split(jax.random.PRNGKey(0), cfg.n_rounds + 1)
    body = _make_round_body(
        score_fn, r_anc, query, cfg, keys, k_s, None, _local_ctx(n_items)
    )
    dtype = jnp.float32
    state = EngineState(
        anchor_idx=jnp.zeros((b, k_i), jnp.int32),
        c_test=jnp.zeros((b, k_i), dtype),
        a_buf=jnp.zeros((b, k_q, k_i), dtype),
        p=jnp.zeros((b, k_i, k_q), dtype),
        e_q=jnp.zeros((b, k_q), dtype),
        selected=jnp.zeros((b, n_items), bool),
    )
    closed = jax.make_jaxpr(lambda st: body(jnp.int32(1), st))(state)
    return _count_bn_floats(closed.jaxpr, b, n_items)


def engine_slab_bytes(
    cfg: AdaCURConfig, batch: int, n_items: int, k_q: int,
    n_data_shards: int = 1, n_item_shards: int = 1,
    payload=None,
) -> dict:
    """Device bytes of the engine's preallocated per-search state slabs —
    PER SHARD when a (data x items) decomposition is given.

    The engine's whole working set is these six buffers plus the payload it
    streams; reporting them next to the index payload in BENCH_engine.json /
    BENCH_sharded.json tracks the memory story alongside latency as N and
    the mesh scale.  Under the SPMD engine the batch dimension divides over
    ``n_data_shards`` everywhere, and the item axis — which only the
    ``selected`` mask carries — further divides over ``n_item_shards``; the
    pinv/e_q state replicates across item shards by design.

    ``payload``, when given, adds a ``"payload"`` entry with the REAL
    per-shard byte footprint of the streamed operand — either a concrete
    payload (fp32/bf16 array or QuantizedRanc, measured via ``nbytes`` so
    packed int4 columns count 0.5 bytes/row, not element counts) or a
    payload dtype string, sized analytically from ``(k_q, n_items)`` plus
    the per-tile scale vector for coded dtypes.
    """
    k_i = cfg.budget_ce if not cfg.split_budget else cfg.k_anchor
    b = batch // n_data_shards
    slabs = {
        "anchor_idx": b * k_i * 4,
        "c_test": b * k_i * 4,
        "a_buf": b * k_q * k_i * 4,
        "p": b * k_i * k_q * 4,
        "e_q": b * k_q * 4,
        "selected_mask": b * (n_items // n_item_shards) * 1,
    }
    if payload is not None:
        if isinstance(payload, str):
            nb = quant.payload_nbytes(payload, k_q, n_items, cfg.payload_tile)
        else:
            nb = int(payload.nbytes)
        slabs["payload"] = nb // n_item_shards
    slabs["total"] = sum(slabs.values())
    return slabs
