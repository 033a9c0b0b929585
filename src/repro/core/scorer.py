"""Cross-encoder scorer subsystem: the third pillar next to the engine
(online) and the AnchorIndex (offline).

Everything the engine scores goes through a :class:`Scorer` — a ScoreFn
with *measured* CE-call accounting.  Three production providers:

- :class:`SyntheticScorer` — the closed-form synthetic CE, pure-traced
  (fuses into the jitted engine; the seed behavior);
- :class:`TabulatedScorer` — exact-matrix lookup routed through
  ``jax.pure_callback``, so every call is counted *at runtime* even inside
  ``lax.fori_loop``/``while_loop`` bodies.  The engine's per-round budget
  becomes measured, not assumed: tests assert measured == planned
  (:func:`repro.core.engine.ce_call_plan`);
- :class:`CrossEncoderScorer` — the real transformer CE
  (``models/cross_encoder.py``).  Host-side pair tokenization, token-length
  bucketing and micro-batch padding to a *small static shape set* (repeated
  calls never retrace), scored through the Pallas flash-attention kernel
  whose per-example SMEM valid-length masks make one padded bucket serve
  every pair length;
- :class:`DeviceCEScorer` — the same transformer CE as a *device-resident*
  stage of the engine's program.  The corpus token table lives on device
  (carried by ``AnchorIndex.item_tokens``), queries are tokenized on the
  host once per batch, and pair assembly + the CE forward happen *in-trace*
  — so the SPMD ``shard_map`` engine runs the real CE with no host
  callback, no nested jit, and no psum rendezvous to deadlock.

Capability flags the engine keys on (duck-typed, no imports needed):

- ``device_resident`` — the scorer scores token operands in-trace and needs
  the corpus token table (``item_tokens``) instead of item *ids*;
- ``nested_device_callback`` — the scorer's host callback launches device
  compute (a nested jit).  Safe single-device; **rejected** by
  ``make_sharded_engine`` because it deadlocks a single-process
  multi-device runtime.  Numpy-only callbacks (TabulatedScorer,
  CachingScorer over one) stay mesh-legal.

Layered on top, :class:`CachingScorer` adds a (query_id, item_id) score
cache: scores computed for one request's anchors are exactly the R_anc
rows future requests reconstruct from, so popular pairs are scored once
process-wide (cf. the test-time index-growth direction of arXiv 2405.03651).

Every host-backed scorer rides ``jax.pure_callback``: the engine stays one
jit-compiled executable in every loop mode while tokenization, caching and
accounting run host-side (each callback fires exactly once per executed
round — verified by the property suite).
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import LMConfig
from ..kernels.backend import resolve_attn_impl


@dataclass
class ScorerStats:
    """Measured CE-call accounting (host-side, survives jit)."""

    requests: int = 0        # score() invocations observed
    pairs: int = 0           # (query, item) pairs requested
    ce_calls: int = 0        # pairs actually scored by the underlying model
    cache_hits: int = 0      # pairs served from the score cache
    cache_size: int = 0      # resident cached pairs
    batch_pad: int = 0       # padded filler rows scored for static shapes

    def copy(self) -> "ScorerStats":
        return dataclasses.replace(self)

    def __sub__(self, other: "ScorerStats") -> "ScorerStats":
        """Per-window delta (cache_size stays absolute)."""
        return ScorerStats(
            requests=self.requests - other.requests,
            pairs=self.pairs - other.pairs,
            ce_calls=self.ce_calls - other.ce_calls,
            cache_hits=self.cache_hits - other.cache_hits,
            cache_size=self.cache_size,
            batch_pad=self.batch_pad - other.batch_pad,
        )


@runtime_checkable
class Scorer(Protocol):
    """A ScoreFn with measured accounting: callable as score_fn(query, idx)."""

    stats: ScorerStats

    def __call__(self, query, item_idx) -> jax.Array: ...

    def reset_stats(self) -> None: ...


def scorer_stats(score_fn) -> Optional[ScorerStats]:
    """The live stats of a ScoreFn if it is a Scorer, else None."""
    s = getattr(score_fn, "stats", None)
    return s if isinstance(s, ScorerStats) else None


def bucket_for(length: int, len_buckets: Tuple[int, ...], what: str = "pair") -> int:
    """Smallest bucket >= length, validated *eagerly* with an actionable error.

    Called at tokenization/enqueue time (host scorers) and at trace time
    (device scorers) so an oversized pair fails as a plain ``ValueError``
    where it was caused — never as an opaque XLA runtime error surfacing
    from inside ``jax.pure_callback``.
    """
    for b in len_buckets:
        if b >= length:
            return b
    raise ValueError(
        f"{what} length {length} exceeds the largest length bucket "
        f"{max(len_buckets)} (len_buckets={tuple(len_buckets)}); extend "
        f"len_buckets to cover it, or shorten the query/item token budget "
        f"so tokenized pairs fit an existing bucket"
    )


# ---------------------------------------------------------------------------
# pure-traced provider
# ---------------------------------------------------------------------------


@dataclass
class SyntheticScorer:
    """Closed-form synthetic CE as a Scorer — pure-traced, zero overhead.

    The scoring math inlines into the engine's jit trace (the seed
    behavior), so per-call accounting cannot be observed at runtime; only
    ``requests``/``pairs`` seen at *trace* time are recorded.  Wrap in
    :class:`TabulatedScorer`/:class:`CachingScorer` when measurement
    matters more than fusion.
    """

    ce: object                    # repro.data.synthetic.SyntheticCE
    stats: ScorerStats = field(default_factory=ScorerStats)

    def __call__(self, query, item_idx) -> jax.Array:
        self.stats.requests += 1
        self.stats.pairs += int(np.prod(item_idx.shape))
        return self.ce.score_pairs(query, item_idx)

    def reset_stats(self) -> None:
        self.stats = ScorerStats()


# ---------------------------------------------------------------------------
# host-backed providers (jax.pure_callback)
# ---------------------------------------------------------------------------


class _HostScorer:
    """Base: route scoring through a host callback with runtime accounting.

    ``record_pairs=True`` keeps a per-call log of (query_ids, item_idx)
    numpy copies — the dedup/suppression invariant suite reconstructs every
    search's scored-pair multiset from it.
    """

    def __init__(self, record_pairs: bool = False):
        self.stats = ScorerStats()
        self.record_pairs = record_pairs
        self.call_log: List[Tuple[np.ndarray, np.ndarray]] = []

    def reset_stats(self) -> None:
        self.stats = ScorerStats()
        self.call_log = []

    def _host(self, qids: np.ndarray, idx: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def _host_entry(self, qids, idx):
        qids = np.asarray(qids)
        idx = np.asarray(idx)
        self.stats.requests += 1
        self.stats.pairs += int(idx.size)
        if self.record_pairs:
            self.call_log.append((qids.copy(), idx.copy()))
        return np.asarray(self._host(qids, idx), dtype=np.float32)

    def __call__(self, query, item_idx) -> jax.Array:
        return jax.pure_callback(
            self._host_entry,
            jax.ShapeDtypeStruct(item_idx.shape, jnp.float32),
            query, item_idx,
        )


class TabulatedScorer(_HostScorer):
    """Exact-matrix lookup: ``score(q, i) = matrix[q, i]``.

    The reference scorer for tests and benchmarks: free to evaluate, exact,
    and *counting* — every scored pair increments ``stats.ce_calls`` at
    runtime, inside any engine loop mode.
    """

    def __init__(self, matrix, record_pairs: bool = False):
        super().__init__(record_pairs)
        self.matrix = np.asarray(matrix, dtype=np.float32)

    def _host(self, qids, idx):
        self.stats.ce_calls += int(idx.size)
        return self.matrix[qids[:, None], idx]


class CrossEncoderScorer(_HostScorer):
    """The real transformer CE on the engine's hot path.

    Host side: ``pair_fn(query_ids (B,), item_idx (B, k)) -> (B, k, L)``
    int32 pair tokens ([CLS] q [SEP] i [SEP], trailing ``pad_id`` padding).
    Pairs are flattened, padded to the smallest length bucket, and scored
    in fixed ``micro_batch``-row chunks, so the jitted compute sees only
    ``len(len_buckets)`` static shapes — ``n_traces`` proves repeated calls
    never retrace.  On a TPU attention runs through the Pallas flash kernel
    with per-example SMEM valid lengths (the platform picks ``attn_impl``;
    see ``kernels/backend.py``).

    Pair lengths are validated *eagerly*: at construction the ``pair_fn``
    is probed with a one-pair dummy call, so a pair that overflows the
    largest length bucket raises an actionable ``ValueError`` immediately
    instead of an opaque XLA error from inside ``jax.pure_callback`` on
    the first search (set ``probe_pair_len=False`` for pair_fns that
    cannot tokenize id 0; lengths are then validated per enqueue).
    """

    # host callback launches a nested jit (the CE forward): deadlocks the
    # SPMD mesh (see make_sharded_engine) and, on a single-core host, the
    # async CPU client's one execute thread — run with
    # ``jax_cpu_enable_async_dispatch=False`` there, or use DeviceCEScorer
    nested_device_callback = True

    def __init__(
        self,
        params,
        cfg: LMConfig,
        pair_fn: Callable[[np.ndarray, np.ndarray], np.ndarray],
        pad_id: int = 0,
        micro_batch: int = 64,
        len_buckets: Tuple[int, ...] = (32, 64, 128, 256, 512),
        attn_impl: Optional[str] = None,
        flash_block: Tuple[int, int] = (128, 128),
        record_pairs: bool = False,
        probe_pair_len: bool = True,
    ):
        super().__init__(record_pairs)
        from ..models import cross_encoder

        self.params = params
        self.cfg = cfg
        self.pair_fn = pair_fn
        attn_impl = resolve_attn_impl(attn_impl)
        self.pad_id = pad_id
        self.micro_batch = micro_batch
        self.len_buckets = tuple(sorted(len_buckets))
        self._n_traces = 0

        if probe_pair_len:
            try:
                probe = np.asarray(
                    pair_fn(np.zeros(1, np.int64), np.zeros((1, 1), np.int64))
                )
            except Exception:
                probe = None     # pair_fn rejects the dummy ids; validate per call
            if probe is not None:
                bucket_for(int(probe.shape[-1]), self.len_buckets)

        def scored(tokens):
            self._n_traces += 1          # trace-time side effect
            return cross_encoder.score_tokens(
                params, tokens, cfg, pad_id=pad_id, attn_impl=attn_impl,
                flash_block=flash_block,
            )

        self._jit_scored = jax.jit(scored)

    @property
    def n_traces(self) -> int:
        """Distinct (micro_batch, bucket) shapes compiled so far."""
        return self._n_traces

    def _bucket(self, length: int) -> int:
        return bucket_for(length, self.len_buckets)

    def _host(self, qids, idx):
        b, k = idx.shape
        tokens = np.asarray(self.pair_fn(qids, idx), dtype=np.int32)  # (B,k,L)
        n, length = b * k, tokens.shape[-1]
        bucket = self._bucket(length)
        n_pad = -n % self.micro_batch
        flat = np.full((n + n_pad, bucket), self.pad_id, dtype=np.int32)
        flat[:n, :length] = tokens.reshape(n, length)
        self.stats.ce_calls += n
        self.stats.batch_pad += n_pad
        out = np.empty(n + n_pad, dtype=np.float32)
        for lo in range(0, n + n_pad, self.micro_batch):
            chunk = jnp.asarray(flat[lo : lo + self.micro_batch])
            out[lo : lo + self.micro_batch] = np.asarray(self._jit_scored(chunk))
        return out[:n].reshape(b, k)


class DeviceCEScorer:
    """The real transformer CE as a *device-resident* stage of the engine.

    Where :class:`CrossEncoderScorer` tokenizes and launches the CE from a
    host callback (illegal under the SPMD mesh — the nested jit deadlocks
    against shards parked at the score-broadcast psum), this provider keeps
    the corpus token table on device, assembles ``[CLS] q [SEP] i [SEP]``
    pair rows *in-trace* and runs the transformer forward (flash-attention
    path with per-example valid-length masks) inside the engine's one
    compiled program.  Under ``make_sharded_engine`` the flattened pair
    batch is additionally split across the *item* shards, so the whole
    mesh shares the CE FLOPs and every pair is scored exactly once
    system-wide.

    The query operand the engine sees is the ``(B, query_len)`` int32 token
    batch from :meth:`tokenize_queries` (host-side, once per request batch,
    before the round loop).  The corpus table is either carried by the
    scorer (``item_tokens=``) or — the production path — by the index
    (``AnchorIndex.with_item_tokens``), position-aligned with the payload
    through every mutation.

    Accounting stays *measured*: a numpy-only counting callback (no device
    compute, mesh-legal) observes each executed scoring round, so
    ``stats.ce_calls`` equals :func:`repro.core.engine.ce_call_plan` at
    runtime and item-shard pad rows are excluded by construction.
    """

    device_resident = True
    # pairs per compiled step of bulk_score: bounds the CE activations of an
    # offline scoring sweep (~0.3M tokens at the 256-token bucket)
    BULK_PAIRS = 1024

    def __init__(
        self,
        params,
        cfg: LMConfig,
        query_token_fn: Callable[[np.ndarray], np.ndarray],
        item_tokens=None,
        pad_id: int = 0,
        cls_id: int = 1,
        sep_id: int = 2,
        len_buckets: Tuple[int, ...] = (32, 64, 128, 256, 512),
        attn_impl: Optional[str] = None,
        flash_block: Tuple[int, int] = (128, 128),
        record_pairs: bool = False,
    ):
        self.params = params
        self.cfg = cfg
        self.query_token_fn = query_token_fn
        self.item_tokens = (
            None if item_tokens is None else jnp.asarray(item_tokens, jnp.int32)
        )
        self.pad_id = pad_id
        self.cls_id = cls_id
        self.sep_id = sep_id
        self.len_buckets = tuple(sorted(len_buckets))
        self.attn_impl = resolve_attn_impl(attn_impl)
        self.flash_block = flash_block
        self.record_pairs = record_pairs
        self.stats = ScorerStats()
        self.call_log: List[np.ndarray] = []
        self._n_traces = 0
        self._bulk = jax.jit(self._bulk_impl)
        self._bulk_sharded = {}          # mesh -> compiled sharded bulk

    # -- host side: once per request batch, before the round loop ----------

    def tokenize_queries(self, query) -> jax.Array:
        """Query ids -> (B, query_len) int32 token rows (the engine operand).

        Pair lengths are validated *here* (eagerly, with an actionable
        message) whenever the scorer carries its own token table; when the
        table rides on the index instead, :meth:`build_pairs` re-validates
        at trace time — still a plain ``ValueError``, never an XLA error.
        """
        qids = np.asarray(jax.device_get(query))
        toks = np.asarray(self.query_token_fn(qids), dtype=np.int32)
        if toks.ndim != 2 or toks.shape[0] != qids.shape[0]:
            raise ValueError(
                f"query_token_fn must map (B,) ids to (B, query_len) tokens; "
                f"got {toks.shape} for B={qids.shape[0]}"
            )
        if self.item_tokens is not None:
            bucket_for(
                toks.shape[1] + int(self.item_tokens.shape[1]) + 3,
                self.len_buckets,
            )
        return jnp.asarray(toks)

    # -- device side: traced into the engine program -----------------------

    def build_pairs(self, q_tokens, item_rows) -> jax.Array:
        """(B, Lq) x (B, k, Li) -> (B, k, bucket) padded pair token rows."""
        from ..models import cross_encoder

        lq, li = int(q_tokens.shape[-1]), int(item_rows.shape[-1])
        bucket = bucket_for(lq + li + 3, self.len_buckets)
        return cross_encoder.build_pair_tokens(
            q_tokens, item_rows, pad_to=bucket,
            cls_id=self.cls_id, sep_id=self.sep_id, pad_id=self.pad_id,
        )

    def _score_flat(self, flat_tokens) -> jax.Array:
        from ..models import cross_encoder

        return cross_encoder.score_tokens(
            self.params, flat_tokens, self.cfg, pad_id=self.pad_id,
            attn_impl=self.attn_impl, flash_block=self.flash_block,
        )

    def forward(self, flat_tokens) -> jax.Array:
        """(M, bucket) pair rows -> (M,) CE scores, in the caller's trace."""
        self._n_traces += 1              # trace-time side effect
        return self._score_flat(flat_tokens)

    # -- offline bulk scoring (index build, brute-force reference) ---------

    def bulk_score(self, query_ids, item_ids, mesh=None) -> jax.Array:
        """Exact CE scores of every (query, item) pair -> (Q, N), on device.

        The offline counterpart of in-trace scoring, with the
        ``AnchorIndex.build`` bulk-scorer signature: queries are tokenized
        on the host, items read from the scorer's token table, and pairs
        scored ``BULK_PAIRS`` at a time under one compiled ``lax.map`` so
        activations stay bounded whatever N is.  With ``mesh`` the items
        split evenly over all of its devices, each scoring its share with
        the same per-step shape; the result is gathered to the default
        device either way.  Offline work is not serving cost: nothing is
        counted in ``stats``.
        """
        if self.item_tokens is None:
            raise ValueError("bulk_score reads the scorer's own token table: "
                             "construct DeviceCEScorer with item_tokens=")
        q_tok = self.tokenize_queries(query_ids)
        rows = jnp.take(self.item_tokens, jnp.asarray(item_ids), axis=0)
        if mesh is None:
            return self._bulk(q_tok, rows)
        from jax.sharding import PartitionSpec as P

        n = rows.shape[0]
        rows = jnp.pad(rows, ((0, -n % mesh.size), (0, 0)),
                       constant_values=self.pad_id)
        if mesh not in self._bulk_sharded:
            axes = tuple(mesh.axis_names)
            self._bulk_sharded[mesh] = jax.jit(jax.shard_map(
                self._bulk_impl, mesh=mesh, in_specs=(P(), P(axes)),
                out_specs=P(None, axes), check_vma=False,
            ))
        out = self._bulk_sharded[mesh](q_tok, rows)[:, :n]
        return jax.device_put(out, q_tok.sharding)

    def _bulk_impl(self, q_tok, rows):
        qn, li = q_tok.shape[0], rows.shape[1]
        n = rows.shape[0]
        chunk = max(1, self.BULK_PAIRS // qn)           # items per step
        rows = jnp.pad(rows, ((0, -n % chunk), (0, 0)), constant_values=self.pad_id)

        def step(item_rows):                            # (chunk, Li)
            pairs = self.build_pairs(
                q_tok, jnp.broadcast_to(item_rows[None], (qn, chunk, li))
            )
            return self._score_flat(pairs.reshape(qn * chunk, -1)).reshape(qn, chunk)

        out = jax.lax.map(step, rows.reshape(-1, chunk, li))  # (steps, Q, chunk)
        return jnp.moveaxis(out, 0, 1).reshape(qn, -1)[:, :n].astype(jnp.float32)

    # -- measured accounting (numpy-only callback: mesh-legal) -------------

    def _count_host(self, idx, n_pad):
        idx = np.asarray(idx)
        self.stats.requests += 1
        self.stats.pairs += int(idx.size)
        self.stats.ce_calls += int(idx.size)
        self.stats.batch_pad += int(n_pad)
        if self.record_pairs:
            self.call_log.append(idx.copy())
        return np.float32(0.0)

    def count(self, item_idx, n_pad) -> jax.Array:
        """Record one executed scoring round; returns a 0.0 the caller must
        consume (``scores + 0.0 * count(...)``) so DCE cannot drop it."""
        return jax.pure_callback(
            self._count_host,
            jax.ShapeDtypeStruct((), jnp.float32),
            item_idx, jnp.asarray(n_pad, jnp.int32),
        )

    @property
    def n_traces(self) -> int:
        """CE forwards traced so far (stable across runtime n_rounds/n_valid)."""
        return self._n_traces

    def reset_stats(self) -> None:
        self.stats = ScorerStats()
        self.call_log = []

    def __call__(self, query_tokens, item_idx) -> jax.Array:
        """Plain ScoreFn over the scorer-carried table (single device)."""
        if self.item_tokens is None:
            raise ValueError(
                "DeviceCEScorer needs a corpus token table to score directly: "
                "construct it with item_tokens=, or search through an index "
                "that carries one (AnchorIndex.with_item_tokens)"
            )
        from .engine import _device_ce_score, _local_ctx

        ctx = _local_ctx(int(self.item_tokens.shape[0]))
        return _device_ce_score(ctx, self, query_tokens, item_idx, self.item_tokens)


class CachingScorer(_HostScorer):
    """(query_id, item_id) score cache over any host-backed Scorer.

    CE scores are query-conditioned, so the unit of reuse is the *pair*:
    repeat queries (and coalesced batches sharing pairs) hit the cache and
    skip the inner model entirely.  Within one call, duplicate pairs are
    scored once.  ``stats.ce_calls`` counts only inner-model pairs —
    measured accounting for the serving layer; ``capacity`` bounds
    residency with LRU eviction.

    Cache keys are the ids the engine passes to score_fn — external corpus
    ids when searching through ``AnchorIndex.item_ids``, so entries stay
    valid across index mutation/compaction.
    """

    def __init__(self, inner: _HostScorer, capacity: int = 1_000_000,
                 record_pairs: bool = False):
        super().__init__(record_pairs)
        if not isinstance(inner, _HostScorer):
            raise TypeError(
                "CachingScorer caches host-backed scorers (TabulatedScorer / "
                "CrossEncoderScorer); pure-traced scorers fuse into the jit "
                "trace and cannot be intercepted"
            )
        self.inner = inner
        self.capacity = capacity
        self._cache: "OrderedDict[int, float]" = OrderedDict()

    @property
    def nested_device_callback(self) -> bool:
        """Mesh legality follows the wrapped scorer (cache adds no device work)."""
        return bool(getattr(self.inner, "nested_device_callback", False))

    def reset_stats(self, clear_cache: bool = False) -> None:
        super().reset_stats()
        self.inner.reset_stats()
        if clear_cache:
            self._cache.clear()

    def _host(self, qids, idx):
        b, k = idx.shape
        keys = (qids.astype(np.int64)[:, None] << 32) | idx.astype(np.int64)
        flat_keys = keys.reshape(-1)
        out = np.empty(b * k, dtype=np.float32)

        miss_keys: List[int] = []
        miss_pos: dict = {}          # key -> every flat position needing it
        for pos, key in enumerate(flat_keys.tolist()):
            hit = self._cache.get(key)
            if hit is not None:
                out[pos] = hit
                self._cache.move_to_end(key)
                self.stats.cache_hits += 1
            else:
                positions = miss_pos.get(key)
                if positions is None:
                    miss_pos[key] = [pos]
                    miss_keys.append(key)
                else:
                    positions.append(pos)

        if miss_keys:
            mk = np.asarray(miss_keys, dtype=np.int64)
            q_m = (mk >> 32).astype(qids.dtype)
            i_m = (mk & 0xFFFFFFFF).astype(idx.dtype)
            scores = np.asarray(
                self.inner._host_entry(q_m, i_m[:, None]), dtype=np.float32
            ).reshape(-1)
            self.stats.ce_calls += len(miss_keys)
            for key, s in zip(miss_keys, scores.tolist()):
                self._cache[key] = s
                if len(self._cache) > self.capacity:
                    self._cache.popitem(last=False)
                # duplicates within the call are scored once, filled everywhere
                for pos in miss_pos[key]:
                    out[pos] = s
        self.stats.cache_size = len(self._cache)
        return out.reshape(b, k)
