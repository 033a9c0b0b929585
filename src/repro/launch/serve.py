"""ADACUR retrieval service: batched request serving over a CE scorer.

The production serving loop the paper's technique plugs into:

- an offline :class:`repro.core.index.AnchorIndex` artifact (built by the
  resumable block-streaming job, persisted/loaded from disk, mutable at
  runtime via ``add_items``/``remove_items`` without retracing);
- any :class:`repro.core.engine.Retriever` behind the unified search API —
  the default is :class:`AdaCURRetriever` on the static-shape round engine
  (``loop_mode='fori'``), so per-batch round-count overrides do not retrace;
- continuous micro-batching: queries accumulate to a batch or a deadline.
  Batches fire from ``submit`` when full/overdue AND from ``poll`` — an
  idle queue with one straggler request is flushed by the event loop's
  periodic ``poll`` even if no further request ever arrives.  Every fired
  batch is padded up to one of a small set of static *batch buckets*
  (partial fills repeat the last row; padded rows are computed and
  discarded, exactly like the engine's ``n_valid`` item padding), so a
  deadline straggler reuses a compiled executable instead of retracing at
  its odd batch size;
- scorer-measured accounting: when the retriever's score_fn is a
  :class:`repro.core.scorer.Scorer` (e.g. ``CachingScorer`` around a
  ``CrossEncoderScorer``), responses carry the *measured* CE calls and
  cache hits of their batch window — the budget is observed, not assumed;
- per-request k-NN results with exact CE scores.

CLI:  PYTHONPATH=src python -m repro.launch.serve --requests 64 \
          --retriever {adacur,anncur,rerank} [--first-stage {none,de,bm25}] \
          [--index-path DIR] [--scorer {synthetic,real-ce}] [--cache] \
          [--payload-dtype {float32,bfloat16,int8,int4,fp8}] \
          [--round-kernel {staged,persistent}] [--mesh DATAxITEMS]

``--scorer real-ce`` serves the CE_TINY transformer cross-encoder at its
registry widths (random weights from ``--seed``) over a ZESHEL-like corpus
(``--n-items``, ``--item-len``, ``--query-len``): the AnchorIndex is built
from ``--anchor-queries`` x n_items CE scores computed on device, and every
request is scored *device-resident* — pair assembly and the CE forward run
inside the engine's one compiled program (``DeviceCEScorer`` over the
index's token table), on one device as under ``--mesh``.  ``chip_smoke.py``
at the repository root runs this path on a TPU.

``--first-stage de|bm25`` serves the multi-stage hybrid: a dual-encoder or
BM25 generator proposes a per-query shortlist and the ADACUR search is
restricted to those candidates via the engine's ``eligible`` mask (the
generator runs outside the compiled search, so it composes with ``--mesh``
— candidates are computed once per batch, host- or device-side, and the
sharded engine only sees a boolean operand).

``--mesh 2x4`` serves over a (data x items) mesh: the index payload shards
over 8 devices' "items" axis, request batches data-parallel over "data", and
the FULL multi-round engine runs as one shard_map program (bit-identical to
single-device serving).  The device count must match — on a CPU host export
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` first.  ``--mesh``
composes with every scorer: synthetic/tabulated/cached ones run as before,
and ``--scorer real-ce`` serves through the *device-resident* CE stage —
the corpus token table rides on the index (``AnchorIndex.with_item_tokens``)
and the transformer forward runs inside the shard_map program, split across
the item shards (see ``engine.make_sharded_engine``).  The one exclusion is
``--cache`` under a real-CE mesh: the pair cache intercepts host callbacks,
and the device-resident CE never leaves the device.
"""

from __future__ import annotations

import argparse
import functools
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import AdaCURConfig
from ..core import telemetry
from ..core.engine import (
    AdaCURRetriever,
    ANNCURRetriever,
    RerankRetriever,
    Retriever,
)
from ..core.index import AnchorIndex, clear_build_checkpoints
from ..core.scorer import ScorerStats, scorer_stats
from ..kernels.backend import on_tpu
from .compile_cache import enable_compile_cache


@dataclass
class RetrievalRequest:
    query_id: int
    arrival_t: float = field(default_factory=time.monotonic)
    deadline_t: Optional[float] = None   # absolute time.monotonic() budget;
                                         # past it the search returns the
                                         # provisional top-k (degraded=True)


@dataclass
class RetrievalResponse:
    query_id: int
    item_ids: Optional[np.ndarray] = None      # None on status="error"
    scores: Optional[np.ndarray] = None
    latency_s: float = 0.0
    ce_calls: int = 0                          # planned budget (upper bound)
    measured_ce_calls: Optional[int] = None    # scorer-measured, per batch row
    cache_hits: Optional[int] = None           # pairs served from cache (batch)
    status: str = "ok"                         # "ok" | "error"
    degraded: bool = False                     # deadline cut the round loop;
                                               # results are the anytime top-k
    rounds_completed: Optional[int] = None     # rounds actually executed
    error: Optional[str] = None                # failure detail (status="error")


class AdaCURService:
    """Batched retrieval over an AnchorIndex via any Retriever.

    The offline side always enters through the :class:`AnchorIndex`
    artifact: pass one directly (or an on-disk index path), or pass a bare
    ``r_anc`` score matrix and the service wraps it.  Swap in a mutated
    index between batches with :meth:`swap_index` — capacity-padded shapes
    mean the compiled search is reused as-is.

    ``batch_buckets`` are the static batch sizes the engine compiles for:
    every flush pads its requests up to the smallest bucket that fits
    (repeating the last row) and slices the padding off the responses.
    Padded rows never reach a response; note the engine's batched RNG
    draws depend on the batch shape, so a padded flush is the same search
    under a different (equally arbitrary) seed realization rather than a
    bit-identical rerun of the unpadded one.  Defaults to
    quarter/half/full of ``max_batch``.
    """

    def __init__(
        self,
        score_fn: Optional[Callable] = None,
        r_anc: Optional[jax.Array] = None,
        cfg: Optional[AdaCURConfig] = None,
        max_batch: int = 32,
        max_wait_s: float = 0.01,
        seed: int = 0,
        retriever: Optional[Retriever] = None,
        index: Optional[Union[AnchorIndex, str, os.PathLike]] = None,
        candidate_fn: Optional[Callable] = None,
        batch_buckets: Optional[List[int]] = None,
        deterministic: bool = False,
    ):
        if index is not None and not isinstance(index, AnchorIndex):
            index = AnchorIndex.load(os.fspath(index))
        if retriever is None:
            if index is None:
                if score_fn is None or r_anc is None or cfg is None:
                    raise ValueError(
                        "need an index (AnchorIndex or path), (score_fn, r_anc, "
                        "cfg), or a retriever"
                    )
                index = AnchorIndex.from_r_anc(r_anc)
            if score_fn is None or cfg is None:
                raise ValueError("need score_fn and cfg to build the retriever")
            retriever = AdaCURRetriever.from_index(index, score_fn, cfg)
        elif index is None:
            index = getattr(retriever, "index", None)
        self.retriever = retriever
        self.index = index
        self.candidate_fn = candidate_fn    # qids (B,) -> (B, M) first-stage order
        self.max_batch = max_batch
        self.max_wait_s = max_wait_s
        if batch_buckets is None:
            batch_buckets = {max(1, max_batch // 4), max(1, max_batch // 2),
                             max_batch}
        self.batch_buckets = sorted(set(int(b) for b in batch_buckets))
        if self.batch_buckets[-1] != max_batch:
            raise ValueError(
                f"largest bucket {self.batch_buckets[-1]} must equal "
                f"max_batch={max_batch}"
            )
        # measured accounting source: the retriever's scorer, if it is one
        self._scorer = getattr(retriever, "score_fn", None)
        # deterministic: every flush reuses the seed key, so a query's search
        # trajectory is a function of (batch row, query_id) only.  With the
        # noise-free "topk" strategy, repeat queries then re-request exactly
        # the pairs already in a CachingScorer — what makes the cross-request
        # score cache effective (at the cost of per-flush anchor diversity).
        self.deterministic = deterministic
        self._key = jax.random.PRNGKey(seed)
        self._pending: List[RetrievalRequest] = []
        # one lock over queue + index mutation + flush: submit()/poll() from
        # request threads may race swap_index() from a control thread, and a
        # batch must be popped, searched, and answered under the index that
        # admitted it (reentrant: swap_index drains via flush)
        self._lock = threading.RLock()

    @property
    def scorer_stats(self) -> Optional[ScorerStats]:
        """Live measured stats of the underlying Scorer (None for bare fns)."""
        return scorer_stats(self._scorer) if self._scorer is not None else None

    def swap_index(self, index: AnchorIndex) -> List[RetrievalResponse]:
        """Serve a mutated (add/remove) index from the next batch on.  The
        index's capacity-constant shapes mean no recompilation happens.

        Requests already queued were admitted under the live index, so they
        are flushed against it *before* the swap (their responses are
        returned) — a swap racing queued requests can never serve a request
        with ids from an index it was not admitted under."""
        if getattr(self.retriever, "index", None) is None:
            raise ValueError(
                "swap_index needs an index-backed retriever (Retriever."
                "from_index); this retriever was built on a bare r_anc and "
                "would keep searching the old scores"
            )
        with self._lock:
            drained: List[RetrievalResponse] = []
            while self._pending:
                drained += self.flush()
            self.index = index
            self.retriever.index = index
            return drained

    def _due(self) -> bool:
        if not self._pending:
            return False
        oldest = self._pending[0].arrival_t
        return (
            len(self._pending) >= self.max_batch
            or time.monotonic() - oldest >= self.max_wait_s
        )

    def submit(self, req: RetrievalRequest) -> Optional[List[RetrievalResponse]]:
        """Queue a request; returns responses when a batch fires."""
        with self._lock:
            self._pending.append(req)
            return self.flush() if self._due() else None

    def poll(self) -> List[RetrievalResponse]:
        """Deadline check for stragglers: flush if the oldest queued request
        has waited past ``max_wait_s``.  Call from the serving event loop —
        without this, a lone queued request was only served when *another*
        request happened to arrive."""
        with self._lock:
            return self.flush() if self._due() else []

    def _bucket(self, n: int) -> int:
        for b in self.batch_buckets:
            if b >= n:
                return b
        return self.batch_buckets[-1]

    def flush(self) -> List[RetrievalResponse]:
        with self._lock:
            if not self._pending:
                return []
            batch, self._pending = self._pending[: self.max_batch], self._pending[self.max_batch :]
            try:
                return self._flush_batch(batch)
            except Exception as e:  # noqa: BLE001 — the flush boundary
                # A scorer exception (pure_callback -> XlaRuntimeError, or a
                # host scorer raising eagerly) fails exactly this batch: each
                # popped request gets a terminal error response, the rest of
                # the queue and the service loop keep running.
                msg = f"{type(e).__name__}: {e}"
                try:
                    # drain the poisoned effects token of the failed callback
                    # so it does not resurface at the next barrier/atexit
                    jax.effects_barrier()
                except Exception:  # noqa: BLE001
                    pass
                now = time.monotonic()
                return [
                    RetrievalResponse(
                        query_id=r.query_id,
                        latency_s=now - r.arrival_t,
                        status="error",
                        error=msg,
                    )
                    for r in batch
                ]

    def _flush_batch(self, batch: List[RetrievalRequest]) -> List[RetrievalResponse]:
        n_valid = len(batch)
        bucket = self._bucket(n_valid)
        with telemetry.span("serve.flush", bucket=bucket, n_real=n_valid,
                            arrival_t=[r.arrival_t for r in batch]) as flush:
            with telemetry.span("serve.prepare"):
                # partial fill: pad to the static bucket by repeating the last
                # row; the padding is sliced off before responses are built
                raw = [r.query_id for r in batch]
                raw += [batch[-1].query_id] * (bucket - n_valid)
                qids = jnp.asarray(raw)
                if self.deterministic:
                    sub = self._key
                else:
                    self._key, sub = jax.random.split(self._key)
                kw = {}
                if self.candidate_fn is not None:
                    kw["candidate_idx"] = self.candidate_fn(qids)
                # anytime serving: an armed deadline is batch-global (one
                # round loop serves all rows), so the tightest one governs
                holder = getattr(self.retriever, "deadline", None)
                budgets = [r.deadline_t for r in batch if r.deadline_t is not None]
                if budgets and holder is not None:
                    kw["deadline_t"] = min(budgets)
                before = self.scorer_stats
                before = before.copy() if before is not None else None
            res = self.retriever.search(qids, sub, **kw)    # span engine.dispatch
            with telemetry.span("serve.device_wait"):
                res = jax.block_until_ready(res)
            with telemetry.span("serve.respond"):
                degraded = bool(holder.fired) if "deadline_t" in kw else False
                rounds = res.rounds_done
                rounds = int(np.asarray(rounds)) if rounds is not None else None
                flush["rounds"] = rounds
                measured = cache_hits = None
                if before is not None:
                    delta = self.scorer_stats - before
                    # amortized over the REAL requests: padded filler rows are a
                    # cost of serving them, so their calls are not averaged away
                    measured = delta.ce_calls // n_valid
                    cache_hits = delta.cache_hits
                # single source of truth: an index-backed retriever may have been
                # mutated directly (retriever.index = ...), so map positions through
                # ITS index, not a possibly-stale service copy
                idx = getattr(self.retriever, "index", None)
                if idx is None:
                    idx = self.index
                item_ids = (
                    np.asarray(idx.gather_item_ids(res.topk_idx))
                    if idx is not None else np.asarray(res.topk_idx)
                )
                out = []
                for i, r in enumerate(batch):
                    out.append(
                        RetrievalResponse(
                            query_id=r.query_id,
                            item_ids=item_ids[i],
                            scores=np.asarray(res.topk_scores[i]),
                            latency_s=time.monotonic() - r.arrival_t,
                            ce_calls=res.ce_calls,
                            measured_ce_calls=measured,
                            cache_hits=cache_hits,
                            degraded=degraded,
                            rounds_completed=rounds,
                        )
                    )
                return out


def make_retriever(
    kind: str,
    index: AnchorIndex,
    score_fn: Callable,
    cfg: AdaCURConfig,
    anchor_key: Optional[jax.Array] = None,
    anytime: bool = False,
) -> Retriever:
    """CLI retriever factory: every method consumes the same AnchorIndex."""
    if kind == "adacur":
        return AdaCURRetriever.from_index(index, score_fn, cfg, anytime=anytime)
    if kind == "anncur":
        if index.anchor_item_pos is None:
            index = index.with_anchors(
                k_anchor=cfg.k_anchor,
                key=anchor_key if anchor_key is not None else jax.random.PRNGKey(2),
            )
        return ANNCURRetriever.from_index(
            index, score_fn, budget_ce=cfg.budget_ce, k_retrieve=cfg.k_retrieve
        )
    if kind == "rerank":
        return RerankRetriever.from_index(
            index, score_fn, budget_ce=cfg.budget_ce, k_retrieve=cfg.k_retrieve
        )
    raise ValueError(f"unknown retriever '{kind}' (adacur|anncur|rerank)")


def build_arg_parser() -> argparse.ArgumentParser:
    """The serving CLI; ``chip_smoke.py`` drives the same path through it."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--n-items", type=int, default=10000)
    ap.add_argument("--budget", type=int, default=200)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--fused", action="store_true",
                    help="fused Pallas score->top-k sampling")
    ap.add_argument("--retriever", choices=("adacur", "anncur", "rerank"),
                    default="adacur", help="search method over the index")
    ap.add_argument("--first-stage", choices=("none", "de", "bm25"),
                    default="none",
                    help="multi-stage hybrid retrieval: a dual-encoder or "
                         "BM25 first stage proposes a per-query shortlist "
                         "and ADACUR spends the CE budget only on those "
                         "candidates (engine 'eligible' mask; composes "
                         "with --mesh). Requires --retriever adacur")
    ap.add_argument("--index-path", default=None,
                    help="AnchorIndex directory: loaded when present, else "
                         "built once and saved there")
    ap.add_argument("--scorer", choices=("synthetic", "real-ce"),
                    default="synthetic",
                    help="real-ce: a transformer CrossEncoderScorer over a "
                         "ZESHEL-like corpus (bucketed micro-batching through "
                         "the flash-attention path)")
    ap.add_argument("--cache", action="store_true",
                    help="wrap the scorer in a (query, item) score cache")
    ap.add_argument("--payload-dtype",
                    choices=("float32", "bfloat16", "int8", "int4", "fp8"),
                    default="float32",
                    help="storage/streaming dtype of the R_anc payload: the "
                         "coded dtypes store per-tile codes+scales with fused "
                         "dequant in the kernel (int8/fp8 ~4x smaller index, "
                         "packed int4 ~8x)")
    ap.add_argument("--round-kernel", choices=("staged", "persistent"),
                    default="staged",
                    help="persistent: one fused payload sweep per round "
                         "(estimate + Gumbel top-k + provisional monitor in "
                         "a single pass; requires --fused). Bit-identical "
                         "rankings to staged")
    ap.add_argument("--mesh", default=None, metavar="DATAxITEMS",
                    help="serve over a (data x items) mesh, e.g. 2x4: the "
                         "items axis shards the index payload, the data axis "
                         "shards request batches; the full engine runs as one "
                         "shard_map program (device count must match)")
    ap.add_argument("--seed", type=int, default=0,
                    help="real-ce: seed of the corpus and the CE's random "
                         "weights")
    ap.add_argument("--item-len", type=int, default=128,
                    help="real-ce: tokens per entity description")
    ap.add_argument("--query-len", type=int, default=64,
                    help="real-ce: tokens per mention (pairs are "
                         "item_len + query_len + 3 tokens, bucketed)")
    ap.add_argument("--anchor-queries", type=int, default=100,
                    help="real-ce: k_q anchor queries the index is built "
                         "from (k_q x n_items CE calls offline)")
    return ap


def main() -> None:
    args = build_arg_parser().parse_args()
    enable_compile_cache()

    from ..data.synthetic import make_synthetic_ce

    if (args.scorer == "real-ce" and args.cache
            and len(os.sched_getaffinity(0)) < 2):
        # single-core host: the async CPU client has one execute thread, so
        # the host CE callback's nested jit would self-block (the
        # single-device twin of the mesh deadlock). Must be set before the
        # first jax computation instantiates the client.
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    if args.scorer == "real-ce":
        _serve_real_ce(args)
        return

    index = None
    if args.index_path and os.path.exists(
        os.path.join(args.index_path, "index_meta.json")
    ):
        print(f"loading AnchorIndex from {args.index_path}...")
        index = AnchorIndex.load(args.index_path)
        if index.n_items != args.n_items:
            print(f"  index holds {index.n_items} items; overriding "
                  f"--n-items {args.n_items} to match")
            args.n_items = index.n_items

    print(f"building synthetic CE domain (|I|={args.n_items})...")
    ce = make_synthetic_ce(jax.random.PRNGKey(0), n_queries=600, n_items=args.n_items)

    if index is None:
        print("building AnchorIndex (block-streamed, resumable)...")
        index = AnchorIndex.build(
            ce.score_block, jnp.arange(500), jnp.arange(args.n_items),
            block_rows=128, checkpoint_dir=args.index_path,
        )
        if args.index_path:
            index.save(args.index_path)
            # the committed artifact supersedes the row-block checkpoints
            clear_build_checkpoints(args.index_path)
            print(f"saved AnchorIndex to {args.index_path}")

    cfg = serving_config(args)
    if args.payload_dtype != "float32":
        fp32_bytes = index.payload_nbytes
        index = index.quantize(args.payload_dtype, tile=cfg.payload_tile)
        print(f"payload {args.payload_dtype}: {index.payload_nbytes / 1e6:.1f} MB "
              f"(fp32 would be {fp32_bytes / 1e6:.1f} MB)")
    if args.mesh:
        index = _shard_for_serving(index, args)
    from ..core.scorer import CachingScorer, SyntheticScorer, TabulatedScorer

    if args.cache:
        # caching requires a host-backed scorer; tabulate the synthetic CE
        m = ce.full_matrix(jnp.arange(600))
        score_fn = CachingScorer(TabulatedScorer(np.asarray(m)))
    else:
        score_fn = SyntheticScorer(ce)
    if args.first_stage != "none":
        if args.retriever != "adacur":
            raise SystemExit(
                "--first-stage composes the hybrid on top of ADACUR; use "
                "--retriever adacur (rerank already IS a first-stage method)"
            )
        from ..core.candidates import (
            BM25Candidates, DualEncoderCandidates, HybridRetriever,
        )

        if args.first_stage == "de":
            generator = DualEncoderCandidates(
                ce.q_emb, ce.i_emb, n_valid=index.n_items
            )
        else:
            from ..data.synthetic import lexical_signatures

            generator = BM25Candidates(
                lexical_signatures(ce.i_emb, seed=3),
                lexical_signatures(ce.q_emb, seed=3),
                n_valid=index.n_items,
            )
        shortlist = min(4 * cfg.budget_ce, index.n_items)
        retriever = HybridRetriever(
            score_fn=score_fn, generator=generator, cfg=cfg, index=index,
            shortlist_k=shortlist, mode="mask",
        )
        print(f"first stage: {args.first_stage} shortlist_k={shortlist} "
              f"(CE budget restricted to each query's candidates)")
    else:
        retriever = make_retriever(args.retriever, index, score_fn, cfg)
    candidate_fn = None
    if args.retriever == "rerank":
        # stand-in first-stage retriever: dual-encoder dot-product order
        def candidate_fn(qids):
            scores = ce.q_emb[qids] @ ce.i_emb.T
            _, order = jax.lax.top_k(scores, cfg.budget_ce)
            return order

    svc = AdaCURService(
        retriever=retriever, max_batch=args.batch, candidate_fn=candidate_fn
    )
    exit_if_all_failed(drive_requests(svc, args, cfg, brute_n=args.n_items))


def serving_mesh(args):
    """The (data x items) mesh ``--mesh DxI`` asks for, checked against the
    visible devices and the batch."""
    from .mesh import make_serving_mesh

    try:
        d, i = (int(x) for x in args.mesh.lower().split("x"))
    except ValueError as e:
        raise SystemExit(f"--mesh must be DATAxITEMS (e.g. 2x4): {e}")
    n_dev = len(jax.devices())
    if d * i != n_dev:
        raise SystemExit(
            f"--mesh {args.mesh} needs {d * i} devices but jax sees {n_dev}; "
            "on CPU export XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{d * i}"
        )
    if args.batch % (4 * d):
        raise SystemExit(
            f"--batch {args.batch} must divide into the service's batch "
            f"buckets over {d} data shards (make it a multiple of {4 * d})"
        )
    return make_serving_mesh(d, i)


def _shard_for_serving(index: AnchorIndex, args) -> AnchorIndex:
    """``--mesh DxI`` -> place the index over a (data x items) mesh; the
    retriever then auto-binds the SPMD engine (engine.make_sharded_engine)."""
    mesh = serving_mesh(args)
    i = mesh.shape["items"]
    print(f"sharding index over mesh {dict(mesh.shape)} "
          f"(payload per item-shard ~{index.payload_nbytes // i / 1e6:.1f} MB)")
    return index.shard(mesh)


def serving_config(args) -> AdaCURConfig:
    """The engine configuration the CLI serves with."""
    return AdaCURConfig(
        k_anchor=args.budget // 2, n_rounds=args.rounds, budget_ce=args.budget,
        strategy="topk", k_retrieve=50 if args.scorer == "real-ce" else 100,
        loop_mode="fori", use_fused_topk=args.fused,
        payload_dtype=args.payload_dtype, round_kernel=args.round_kernel,
    )


def drive_requests(svc: AdaCURService, args, cfg: AdaCURConfig,
                   qid_range=(500, 600), label: Optional[str] = None,
                   brute_n: Optional[int] = None) -> List[RetrievalResponse]:
    """Submit ``args.requests`` random queries through the event loop;
    prints p50/p99 latency and returns the responses in serving order."""
    served = []
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        qid = int(rng.integers(*qid_range))
        served += svc.submit(RetrievalRequest(query_id=qid)) or []
        served += svc.poll()   # the event loop's deadline sweep
    served += svc.flush()
    lat = np.array([r.latency_s for r in served])
    ratio = (
        f" | {cfg.budget_ce} CE calls/request (vs {brute_n} brute force = "
        f"{brute_n / cfg.budget_ce:.0f}x fewer)"
        if brute_n else ""
    )
    print(
        f"[{label or args.retriever}] served {len(served)} requests | "
        f"p50={np.percentile(lat, 50)*1e3:.1f}ms "
        f"p99={np.percentile(lat, 99)*1e3:.1f}ms{ratio}"
    )
    stats = svc.scorer_stats
    if stats is not None:
        print(
            f"measured: {stats.ce_calls} CE calls, {stats.cache_hits} cache "
            f"hits ({stats.cache_size} resident pairs)"
        )
    return served


def exit_if_all_failed(served: List[RetrievalResponse]) -> None:
    """A run whose every response is an error exits non-zero, naming the
    first error (a service answers failures as responses, so nothing else
    would)."""
    errors = [r for r in served if r.status != "ok"]
    if served and len(errors) == len(served):
        raise SystemExit(
            f"all {len(served)} responses were errors; first: {errors[0].error}"
        )


# served (non-anchor) queries of the real-CE corpus
REAL_CE_SERVE_QUERIES = 100


@dataclass
class RealCEStack:
    """What real-CE serving is built on: the corpus, the CE and the index.

    Built once (the offline side); every serving configuration over it is
    :func:`real_ce_service`."""

    ds: Any                    # data.synthetic.ZeshelLikeDataset
    lm_cfg: Any                # configs.base.LMConfig
    params: Any
    scorer: Any                # core.scorer.DeviceCEScorer
    index: AnchorIndex         # carries the corpus token table
    build_s: float             # wall seconds of the index build

    @property
    def serve_qids(self):
        k_q = self.index.k_q
        return (k_q, k_q + REAL_CE_SERVE_QUERIES)


def build_real_ce_stack(args) -> RealCEStack:
    """ZESHEL-like corpus + the CE_TINY cross-encoder at its registry widths
    (random weights from ``--seed``) + an AnchorIndex whose k_q x N anchor
    scores the CE itself computes, on device."""
    from ..configs.registry import CE_TINY
    from ..core.scorer import DeviceCEScorer
    from ..data.synthetic import make_zeshel_like
    from ..models import cross_encoder

    k_q = args.anchor_queries
    print(f"building ZESHEL-like corpus (|I|={args.n_items}, item_len="
          f"{args.item_len}, query_len={args.query_len}) + {CE_TINY.name} CE "
          f"({CE_TINY.n_layers} layers, d_model {CE_TINY.d_model}, "
          f"{CE_TINY.dtype})...")
    ds = make_zeshel_like(
        args.seed, n_items=args.n_items,
        n_queries=k_q + REAL_CE_SERVE_QUERIES, vocab=CE_TINY.vocab_size,
        item_len=args.item_len, query_len=args.query_len,
    )
    params, _ = cross_encoder.init_cross_encoder(
        jax.random.PRNGKey(args.seed), CE_TINY
    )
    scorer = DeviceCEScorer(
        params, CE_TINY, query_token_fn=lambda q: ds.query_tokens[q],
        item_tokens=ds.item_tokens,
    )
    # under --mesh every device scores its share of the items
    mesh = serving_mesh(args) if args.mesh else None
    where = f"over {mesh.size} devices" if mesh else "on device"
    print(f"building AnchorIndex from the CE itself ({k_q} x {args.n_items} "
          f"pairs, {where})...")
    t0 = time.perf_counter()
    index = AnchorIndex.build(
        functools.partial(scorer.bulk_score, mesh=mesh), jnp.arange(k_q),
        jnp.arange(args.n_items), block_rows=k_q,
    )
    build_s = time.perf_counter() - t0
    return RealCEStack(ds, CE_TINY, params, scorer,
                       index.with_item_tokens(ds.item_tokens), build_s)


def real_ce_service(args, stack: RealCEStack):
    """(service, config) serving ``stack`` as the CLI arguments say.

    The CE scores device-resident (DeviceCEScorer over the index's token
    table) — on one device and under ``--mesh`` alike.  ``--cache`` instead
    scores through the host-callback CrossEncoderScorer behind the pair
    cache (single device only)."""
    from ..core.scorer import CachingScorer, CrossEncoderScorer

    refuse_real_ce_cache(args)
    cfg = serving_config(args)
    index = stack.index
    if args.cache:
        scorer = CachingScorer(CrossEncoderScorer(
            stack.params, stack.lm_cfg, stack.ds.pair_tokens, micro_batch=64
        ))
    else:
        scorer = stack.scorer
    if args.mesh:
        index = _shard_for_serving(index, args)
    retriever = make_retriever(args.retriever, index, scorer, cfg)
    return AdaCURService(retriever=retriever, max_batch=args.batch), cfg


def refuse_real_ce_cache(args) -> None:
    """``--cache`` serves the real CE from a host callback; refuse the
    combinations where that cannot work, before anything is built."""
    if not args.cache:
        return
    if args.mesh:
        raise SystemExit(
            "--cache intercepts host-callback scorers; under --mesh the real "
            "CE scores device-resident inside the shard_map program and its "
            "pairs never cross the host boundary — drop --cache"
        )
    if on_tpu():
        raise SystemExit(
            "--cache scores the real CE from a host callback, whose nested "
            "forward is lowered for the CPU; on a TPU that forward holds the "
            "compiled flash kernel, which cannot lower there — drop --cache "
            "(the CE then scores device-resident)"
        )


def _serve_real_ce(args) -> None:
    """End-to-end serving with the REAL transformer cross-encoder: offline
    index built by the CE on device, online scoring inside the engine's
    compiled program (see :func:`real_ce_service`)."""
    refuse_real_ce_cache(args)
    stack = build_real_ce_stack(args)
    print(f"index built in {stack.build_s:.1f}s")
    svc, cfg = real_ce_service(args, stack)
    exit_if_all_failed(drive_requests(
        svc, args, cfg, qid_range=stack.serve_qids,
        label=f"real-ce/{args.retriever}" + ("/mesh" if args.mesh else ""),
    ))
    scorer = svc.retriever.score_fn
    if args.cache:
        inner = scorer.inner
        print(f"compiled CE shapes: {inner.n_traces} (static buckets — no "
              f"retraces); {inner.stats.batch_pad} padded micro-batch rows")
    else:
        print(f"device-resident CE: {scorer.n_traces} in-trace forwards "
              f"compiled (stable across batches); "
              f"{scorer.stats.batch_pad} item-shard pad rows excluded "
              f"from {scorer.stats.ce_calls} measured CE calls")


if __name__ == "__main__":
    main()
