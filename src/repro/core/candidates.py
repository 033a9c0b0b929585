"""First-stage candidate generation + candidate-subset hybrid retrieval.

Production k-NN with cross-encoders is multi-stage: a cheap first stage
proposes a shortlist and the expensive CE decides (cf. multi-stage dense
retrieval, arXiv 2108.11480).  This module supplies both halves on top of
the engine:

- :class:`CandidateGenerator` providers — a dual-encoder dot-product top-k
  over corpus embeddings through the fused ``approx_topk`` kernel
  (:class:`DualEncoderCandidates`), a BM25 sparse-lexical provider running
  host-side behind ``jax.pure_callback`` with *runtime* accounting, the
  same idiom as ``TabulatedScorer`` (:class:`BM25Candidates`), and an
  oracle provider for tests (:class:`OracleCandidates`);
- candidate-subset search — :func:`union_candidates` unions a batch's
  shortlists into a sorted, padded position vector *inside the trace*, the
  payload columns at those positions are gathered into a compact sub-index
  (:func:`quant.subset_columns` — coded payloads (int8 / packed int4 / fp8)
  keep their code bytes and carry per-column source-tile scales, so no
  re-quantization), and the engine
  runs over the sub-index with ``pos_map`` remapping every noise draw to
  the original corpus coordinates.  The subset search is **bit-identical**
  to the same engine search over the full corpus with an ``eligible``
  candidate mask (asserted across loop modes x payload dtypes by
  ``tests/test_candidates.py``), and because the union/gather/search
  pipeline is one jitted program over value operands, queries with
  different candidate sets never retrace;
- :class:`HybridRetriever` — first stage -> ADACUR over the candidates,
  behind the same :class:`~repro.core.engine.Retriever` protocol as the
  other methods.  ``mode='subset'`` streams only the shortlist's columns
  per round (the perf path); ``mode='mask'`` restricts each query to its
  own candidates over the full (possibly mesh-sharded) corpus via the
  engine's ``eligible`` operand (the quality/SPMD path).

Budget accounting is untouched by the first stage: candidate generation
spends zero CE calls, and the engine still scores exactly
:func:`~repro.core.engine.ce_call_plan` pairs per query — measured ==
planned holds verbatim under a first stage (property suite + CI gate).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import AdaCURConfig
from ..kernels.approx_topk import quant
from ..kernels.approx_topk.ops import approx_topk_op
from . import telemetry
from .adacur import AdaCURResult, ScoreFn
from .engine import _IndexBacked, ce_call_plan, engine_search


@dataclass
class GeneratorStats:
    """Measured first-stage accounting (host-side for host providers)."""

    requests: int = 0        # generator invocations observed
    candidates: int = 0      # candidate slots returned

    def copy(self) -> "GeneratorStats":
        return dataclasses.replace(self)

    def __sub__(self, other: "GeneratorStats") -> "GeneratorStats":
        return GeneratorStats(
            requests=self.requests - other.requests,
            candidates=self.candidates - other.candidates,
        )


@runtime_checkable
class CandidateGenerator(Protocol):
    """First-stage provider: query batch -> (B, k) candidate positions.

    Returned positions index the *corpus axis* (engine positions, not
    external ids), are ordered by descending first-stage score, and must
    lie in ``[0, n_valid)`` of the index being searched.
    """

    stats: GeneratorStats

    def __call__(self, query, k: int) -> jax.Array: ...


@dataclass
class DualEncoderCandidates:
    """Dual-encoder dot-product shortlist via the fused approx_topk kernel.

    ``i_emb`` (N, d) corpus embeddings are held transposed as a (d, N)
    "payload" so the kernel streams item tiles exactly like an anchor
    payload — no (B, N) score matrix is ever formed.  Deterministic: exact
    dot-product ties break by ascending item position (kernel contract).
    Pure-traced (fuses into a jitted pipeline), so stats are counted at
    trace time like :class:`~repro.core.scorer.SyntheticScorer`'s.
    """

    q_emb: jax.Array                    # (n_queries, d) query embeddings
    i_emb: jax.Array                    # (N, d) corpus item embeddings
    n_valid: Optional[int] = None       # static valid-prefix bound
    tile: int = 1024
    stats: GeneratorStats = field(default_factory=GeneratorStats)

    def __post_init__(self):
        self._i_emb_t = jnp.asarray(self.i_emb, jnp.float32).T   # (d, N)
        self._q_emb = jnp.asarray(self.q_emb, jnp.float32)

    def reset_stats(self) -> None:
        self.stats = GeneratorStats()

    def __call__(self, query, k: int) -> jax.Array:
        qids = jnp.asarray(query)
        self.stats.requests += 1
        self.stats.candidates += int(qids.shape[0]) * k
        e = jnp.take(self._q_emb, qids, axis=0)
        _, idx = approx_topk_op(
            e, self._i_emb_t, k, tile=self.tile, n_valid=self.n_valid,
        )
        return idx


class BM25Candidates:
    """BM25 sparse-lexical shortlist, host-side behind ``pure_callback``.

    The corpus statistics (term frequencies, document lengths, idf) are
    folded at construction into one (N, V) weight matrix ``W`` with
    ``W[d, t] = idf[t] * tf[d, t] * (k1 + 1) / (tf[d, t] + k1 * (1 - b +
    b * dl[d] / avgdl))`` — Robertson/Sparck-Jones BM25 — so scoring a
    query is one ``qtf @ W.T`` contraction over its term counts.  Ties
    break by ascending document position (stable argsort), matching the
    engine's tie-break convention.

    Like :class:`~repro.core.scorer.TabulatedScorer`, the callback counts
    at *runtime*: every jitted pipeline invocation increments the stats,
    so first-stage work is measured, not assumed.  The callback is
    numpy-only and therefore safe under the SPMD engine's host-callback
    constraint.
    """

    def __init__(
        self,
        corpus_tokens,
        query_tokens,
        k1: float = 1.5,
        b: float = 0.75,
        pad_id: int = 0,
        n_valid: Optional[int] = None,
    ):
        corpus_tokens = np.asarray(corpus_tokens)
        self.query_tokens = np.asarray(query_tokens)
        self.pad_id = pad_id
        self.stats = GeneratorStats()
        n_docs = corpus_tokens.shape[0]
        self.n_valid = n_docs if n_valid is None else int(n_valid)
        vocab = int(max(corpus_tokens.max(), self.query_tokens.max())) + 1
        self.vocab = vocab

        tf = np.zeros((n_docs, vocab), np.float32)
        np.add.at(
            tf,
            (np.repeat(np.arange(n_docs), corpus_tokens.shape[1]),
             corpus_tokens.ravel()),
            1.0,
        )
        tf[:, pad_id] = 0.0
        dl = tf.sum(axis=1)
        avgdl = max(float(dl[: self.n_valid].mean()), 1e-9)
        df = (tf[: self.n_valid] > 0).sum(axis=0).astype(np.float32)
        idf = np.log(1.0 + (self.n_valid - df + 0.5) / (df + 0.5))
        denom = tf + k1 * (1.0 - b + b * dl[:, None] / avgdl)
        self._w = np.where(tf > 0, idf[None, :] * tf * (k1 + 1.0) / denom, 0.0)
        self._w = self._w.astype(np.float32)

    def reset_stats(self) -> None:
        self.stats = GeneratorStats()

    def _host(self, qids: np.ndarray, k: int) -> np.ndarray:
        qids = np.asarray(qids)
        self.stats.requests += 1
        self.stats.candidates += int(qids.size) * k
        toks = self.query_tokens[qids]                          # (B, L)
        qtf = np.zeros((qids.size, self.vocab), np.float32)
        np.add.at(
            qtf,
            (np.repeat(np.arange(qids.size), toks.shape[1]), toks.ravel()),
            1.0,
        )
        qtf[:, self.pad_id] = 0.0
        scores = qtf @ self._w.T                                # (B, N)
        scores[:, self.n_valid:] = -np.inf
        order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
        return order.astype(np.int32)

    def __call__(self, query, k: int) -> jax.Array:
        qids = jnp.asarray(query)
        return jax.pure_callback(
            lambda q: self._host(q, k),
            jax.ShapeDtypeStruct((qids.shape[0], k), jnp.int32),
            qids,
        )


@dataclass
class OracleCandidates:
    """Candidates from the exact CE score matrix — the testing upper bound.

    A first stage with perfect recall@k: isolates the engine's contribution
    to hybrid quality from the generator's (and gives invariant tests a
    deterministic, trivially checkable candidate set).
    """

    exact_scores: jax.Array             # (n_queries, N)
    n_valid: Optional[int] = None
    stats: GeneratorStats = field(default_factory=GeneratorStats)

    def reset_stats(self) -> None:
        self.stats = GeneratorStats()

    def __call__(self, query, k: int) -> jax.Array:
        qids = jnp.asarray(query)
        self.stats.requests += 1
        self.stats.candidates += int(qids.shape[0]) * k
        s = jnp.take(jnp.asarray(self.exact_scores), qids, axis=0)
        if self.n_valid is not None and self.n_valid < s.shape[1]:
            s = jnp.where(
                jnp.arange(s.shape[1]) < self.n_valid, s, -jnp.inf
            )
        return jax.lax.top_k(s, k)[1]


# ---------------------------------------------------------------------------
# Candidate-subset machinery
# ---------------------------------------------------------------------------


def union_candidates(cand: jax.Array, capacity: int, n_corpus: int):
    """Sorted union of a batch's candidate positions, padded to ``capacity``.

    Runs inside the trace (``jnp.unique`` with a static size), so varying
    candidate sets never retrace.  Returns ``(pos, valid, n_sub)``: ``pos``
    (capacity,) int32 ascending with padded slots clamped to position 0
    (their ``valid`` is False — :func:`quant.subset_columns` zeroes them),
    and ``n_sub`` the traced union size.  Entries >= ``n_corpus`` are
    treated as padding.  If the true union exceeds ``capacity`` the largest
    positions are dropped — size the capacity to ``B * shortlist_k`` (as
    :class:`HybridRetriever` does) and that never happens.
    """
    u = jnp.unique(
        jnp.asarray(cand, jnp.int32).ravel(), size=capacity,
        fill_value=n_corpus,
    )
    n_sub = jnp.sum(u < n_corpus).astype(jnp.int32)
    valid = jnp.arange(capacity, dtype=jnp.int32) < n_sub
    pos = jnp.where(valid, u, 0).astype(jnp.int32)
    return pos, valid, n_sub


def candidate_eligibility(
    cand: jax.Array, n_items: int, per_query: bool = True
) -> jax.Array:
    """Scatter (B, M) candidate positions into the engine's ``eligible``
    mask — (B, N) when ``per_query`` (each row restricted to its own
    shortlist), else the (N,) batch union.  Out-of-range positions drop."""
    b, _ = cand.shape
    cand = jnp.asarray(cand, jnp.int32)
    if per_query:
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        base = jnp.zeros((b, n_items), bool)
        return base.at[rows, cand].set(True, mode="drop")
    return jnp.zeros(n_items, bool).at[cand.ravel()].set(True, mode="drop")


@dataclass
class HybridRetriever(_IndexBacked):
    """First-stage shortlist -> ADACUR over the candidates, one jit.

    ``mode='subset'`` (default): the batch's shortlists are unioned and
    their payload columns gathered into a compact padded sub-index; the
    multi-round engine then streams C = O(B * shortlist_k) columns per
    round instead of N, with ``pos_map`` keeping every noise draw on the
    original corpus coordinates (bit-identical to the masked full-corpus
    search).  Single-device only.

    ``mode='mask'``: each query is restricted to its *own* shortlist via
    the engine's per-query ``eligible`` mask over the full corpus — no
    payload gather, works under the SPMD sharded engine, and typically
    higher quality (row i never spends budget on row j's candidates).

    Either way the engine's CE budget accounting is exact:
    :meth:`ce_call_plan` is the engine's plan verbatim (the first stage is
    CE-free), and ``shortlist_k`` must cover it so sampling never runs out
    of eligible items.
    """

    score_fn: ScoreFn
    generator: CandidateGenerator
    cfg: AdaCURConfig
    r_anc: Optional[jax.Array] = None
    index: Optional[object] = None       # repro.core.index.AnchorIndex
    shortlist_k: int = 0
    subset_capacity: Optional[int] = None
    mode: str = "subset"
    jit: bool = True
    _run: Callable = field(init=False, repr=False)

    def __post_init__(self):
        if self.r_anc is None and self.index is None:
            raise ValueError("need r_anc or an AnchorIndex")
        if self.mode not in ("subset", "mask"):
            raise ValueError(f"unknown mode '{self.mode}' (subset|mask)")
        if self.shortlist_k < self.cfg.budget_ce:
            raise ValueError(
                f"shortlist_k={self.shortlist_k} < budget_ce="
                f"{self.cfg.budget_ce}: every query must propose at least "
                f"budget_ce candidates or the engine would sample "
                f"ineligible items"
            )
        self._apply_payload_policy(self.cfg)
        if self.r_anc is not None:
            # pre-apply the payload policy so subset gathers slice the SAME
            # payload a full-corpus search would stream (bit-parity)
            self.r_anc = quant.as_payload(
                self.r_anc, self.cfg.payload_dtype, self.cfg.payload_tile
            )
        sharded = False
        if self.index is not None:
            sharded = self.index._item_sharding()[0] is not None
        if self.mode == "subset":
            if sharded:
                raise ValueError(
                    "mode='subset' is single-device (pos_map); use "
                    "mode='mask' over a sharded index"
                )
            self._run = self._make_subset_run()
        else:
            self._run = self._build_engine(self.cfg, jit_compile=self.jit)

    def ce_call_plan(self, rounds: Optional[int] = None) -> int:
        """Planned CE calls per query — the engine plan, first stage free."""
        return ce_call_plan(self.cfg, rounds)

    def _operands(self):
        """(payload, item_ids (capacity,), n_valid traced int32)."""
        if self.index is not None:
            return (
                self.index.r_anc,
                self.index.item_ids,
                jnp.asarray(self.index.n_valid, jnp.int32),
            )
        n = self.r_anc.shape[1]
        return (
            self.r_anc,
            jnp.arange(n, dtype=jnp.int32),
            jnp.asarray(n, jnp.int32),
        )

    def _capacity(self, b: int) -> int:
        full = (
            self.index.capacity if self.index is not None
            else self.r_anc.shape[1]
        )
        if self.subset_capacity is not None:
            return min(self.subset_capacity, full)
        want = max(b * self.shortlist_k, self.cfg.budget_ce, self.cfg.k_retrieve)
        return min(-(-want // 128) * 128, full)

    def _make_subset_run(self):
        cfg, score_fn = self.cfg, self.score_fn

        def run(r_anc, item_ids, n_valid, query, cand, key, n_rounds,
                capacity: int):
            n_full = r_anc.shape[1]
            # positions outside the valid prefix become padding
            cand = jnp.where(cand < n_valid, cand, n_full)
            pos, valid, n_sub = union_candidates(cand, capacity, n_full)
            sub = quant.subset_columns(r_anc, pos, valid)
            sub_ids = jnp.where(valid, jnp.take(item_ids, pos), -1)
            res = engine_search(
                score_fn, sub, query, cfg, key, n_valid_items=n_sub,
                n_rounds=n_rounds, return_scores=False, item_ids=sub_ids,
                pos_map=pos,
            )
            # results leave in full-corpus positions, like every retriever
            return dataclasses.replace(
                res,
                anchor_idx=jnp.where(
                    res.anchor_idx >= 0, pos[res.anchor_idx], -1
                ),
                topk_idx=pos[res.topk_idx],
            )

        if self.jit:
            run = jax.jit(run, static_argnames=("capacity",))
        return run

    def search(self, query, key=None, n_rounds=None, **_ignored) -> AdaCURResult:
        with telemetry.span("engine.dispatch"):
            key = jax.random.PRNGKey(0) if key is None else key
            cand = self.generator(query, self.shortlist_k)
            if self.cfg.loop_mode == "fori":
                n_rounds = jnp.asarray(
                    self.cfg.n_rounds if n_rounds is None else n_rounds, jnp.int32
                )
            elif n_rounds is not None:
                raise ValueError("runtime n_rounds override requires loop_mode='fori'")
            if self.mode == "subset":
                r_anc, item_ids, n_valid = self._operands()
                b = jax.tree_util.tree_leaves(query)[0].shape[0]
                return self._run(
                    r_anc, item_ids, n_valid, query, cand, key, n_rounds,
                    capacity=self._capacity(b),
                )
            r_anc, kw = self._search_operands()
            n_items = r_anc.shape[1]
            eligible = candidate_eligibility(cand, n_items, per_query=True)
            return self._run(
                r_anc, query, key, n_rounds=n_rounds, eligible=eligible, **kw
            )
