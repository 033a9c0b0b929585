"""The offline side of the system: the first-class :class:`AnchorIndex` artifact.

Every retriever in this codebase searches the same offline product — the
anchor-query/item score matrix ``R_anc`` plus whatever was precomputed from
it.  Following the paper's follow-up (Yadav et al., *Adaptive Retrieval and
Scalable Indexing*, arXiv 2405.03651) the index is a first-class artifact
with a full lifecycle, not a bare array:

- **build**: :meth:`AnchorIndex.build` streams (query-block x item) chunks
  through any bulk scorer — the O(k_q·|I|·C_f) offline stage is an
  embarrassingly parallel multi-day pod-scale job, so finished row blocks
  are checkpointed and a preempted build resumes where it left off;
- **save/load**: versioned persistence on the repo's
  :class:`repro.checkpoint.Checkpointer` (atomic commit, per-leaf .npy +
  manifest, elastic re-sharding on restore);
- **shard**: :meth:`AnchorIndex.shard` places the item axis over a mesh via
  ``distributed/sharding.py`` rules; :meth:`AnchorIndex.topk` then runs
  under ``shard_map`` — the engine's fused ``approx_topk`` per shard with a
  cross-shard top-k merge, so no shard ever materializes global scores;
- **mutate**: :meth:`add_items` / :meth:`remove_items` support dynamic
  corpora through *padded capacity* plus the engine's ``n_valid`` bound —
  array shapes never change, so corpus mutation never retraces the search.

Retrievers consume the artifact through ``Retriever.from_index`` (see
``core/engine.py``); the item axis of the index is addressed by *position*,
with ``item_ids`` mapping positions to external corpus ids (the engine
applies the map before every cross-encoder call).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..checkpoint.checkpointer import Checkpointer
from ..distributed import sharding
from ..kernels.approx_topk import quant
from ..kernels.approx_topk.ops import approx_topk_op
from ..kernels.approx_topk.quant import QuantizedRanc
from . import cur

# bulk_score_fn(query_ids (Q,), item_ids (N,)) -> (Q, N) exact scores
BulkScoreFn = Callable[[jax.Array, jax.Array], jax.Array]

# v2 adds the quantized payload (r_codes/r_scales leaves + payload meta).
# v3 adds the optional corpus token table (item_tokens leaf) that makes the
# index self-contained for device-resident CE scoring (DeviceCEScorer under
# the SPMD engine).  v4 adds sub-int8 payload encodings (packed int4 /
# fp8-e4m3), recorded as ``payload.code_dtype`` (+ ``payload.n_cols`` for
# packed widths) in the meta.  Saves stamp the LOWEST version whose features
# they use — a plain fp32 index keeps the v1 on-disk layout byte-for-byte,
# an int8-quantized one without tokens stamps v2, and only int4/fp8 payloads
# stamp v4 — so older readers keep loading everything they can represent;
# this build reads all four.
INDEX_FORMAT_VERSION = 4
_READABLE_FORMAT_VERSIONS = (1, 2, 3, 4)
_META_FILE = "index_meta.json"
_CKPT_STEP = 0


def build_r_anc(
    bulk_score_fn: BulkScoreFn,
    anchor_query_ids: jax.Array,
    item_ids: jax.Array,
    block_rows: int = 64,
    checkpoint_dir: Optional[str] = None,
) -> jax.Array:
    """Compute R_anc (k_q, N) in row blocks with optional resume.

    Each row block is one jit'd bulk scoring call; with a checkpoint dir the
    block results are persisted (.npy) plus a manifest, and finished blocks
    are skipped on restart.  A manifest whose ``k_q``/``n_items``/
    ``block_rows`` — or whose anchor-query/item *id content* (fingerprinted)
    — does not match the current call is stale, so it is discarded (with its
    block files) rather than silently reused.  A changed *scorer* over
    identical ids is undetectable; use a fresh checkpoint_dir per model.
    """
    k_q = int(anchor_query_ids.shape[0])
    n_items = int(item_ids.shape[0])
    n_blocks = (k_q + block_rows - 1) // block_rows
    ids_fp = hashlib.sha256(
        np.asarray(anchor_query_ids).tobytes() + b"|" + np.asarray(item_ids).tobytes()
    ).hexdigest()[:16]

    done = set()
    manifest_path = None
    if checkpoint_dir is not None:
        os.makedirs(checkpoint_dir, exist_ok=True)
        manifest_path = os.path.join(checkpoint_dir, "manifest.json")
        if os.path.exists(manifest_path):
            with open(manifest_path) as f:
                meta = json.load(f)
            if (
                meta.get("k_q") == k_q
                and meta.get("n_items") == n_items
                and meta.get("block_rows") == block_rows
                and meta.get("ids_fingerprint") == ids_fp
            ):
                done = set(meta["done_blocks"])
            else:
                # stale manifest: blocks cover different rows or different ids
                clear_build_checkpoints(checkpoint_dir)

    rows = []
    for blk in range(n_blocks):
        lo, hi = blk * block_rows, min((blk + 1) * block_rows, k_q)
        blk_path = (
            os.path.join(checkpoint_dir, f"ranc_block_{blk:05d}.npy")
            if checkpoint_dir
            else None
        )
        if blk in done and blk_path and os.path.exists(blk_path):
            rows.append(jnp.asarray(np.load(blk_path)))
            continue
        block = bulk_score_fn(anchor_query_ids[lo:hi], item_ids)
        block = jax.block_until_ready(block)
        rows.append(block)
        if checkpoint_dir:
            np.save(blk_path, np.asarray(block))
            done.add(blk)
            tmp = manifest_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(
                    {
                        "k_q": k_q,
                        "n_items": n_items,
                        "block_rows": block_rows,
                        "ids_fingerprint": ids_fp,
                        "done_blocks": sorted(done),
                    },
                    f,
                )
            os.replace(tmp, manifest_path)  # atomic commit
    return jnp.concatenate(rows, axis=0)


def clear_build_checkpoints(checkpoint_dir: str) -> None:
    """Drop :func:`build_r_anc`'s row-block checkpoints + manifest — called
    on stale-manifest invalidation and after the built index has been
    committed via :meth:`AnchorIndex.save` (the blocks are superseded)."""
    for name in os.listdir(checkpoint_dir):
        if name.startswith("ranc_block_") and name.endswith(".npy"):
            os.remove(os.path.join(checkpoint_dir, name))
    manifest = os.path.join(checkpoint_dir, "manifest.json")
    if os.path.exists(manifest):
        os.remove(manifest)


def _pad_axis(x: jax.Array, axis: int, target: int, fill) -> jax.Array:
    n = x.shape[axis]
    if n == target:
        return x
    if n > target:
        raise ValueError(f"cannot shrink axis {axis} from {n} to {target}")
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - n)
    return jnp.pad(x, widths, constant_values=fill)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=(
        "r_anc", "anchor_query_ids", "item_ids", "n_valid",
        "anchor_item_pos", "u", "item_embeddings", "item_tokens",
    ),
    meta_fields=(),
)
@dataclass
class AnchorIndex:
    """The offline artifact every retriever consumes.

    The item axis is padded to ``capacity``; positions ``[0, n_valid)`` hold
    real items (column ``j`` of ``r_anc`` scores item ``item_ids[j]``) and
    the tail holds exact-zero columns with ``item_ids == -1``.  All methods
    are functional — they return a new ``AnchorIndex`` and never resize an
    array, so a retriever holding a mutated index never retraces.
    """

    # (k_q, capacity) anchor-query scores: an fp32/bf16 array, or a coded
    # QuantizedRanc payload (int8 / packed int4 / fp8 codes + per-item-tile
    # scales) after quantize()
    r_anc: Union[jax.Array, QuantizedRanc]
    anchor_query_ids: jax.Array      # (k_q,) int32 anchor query ids
    item_ids: jax.Array              # (capacity,) int32 external ids, -1 padding
    n_valid: jax.Array               # () int32 number of real items
    # optional precomputed ANNCUR latents (arXiv 2210.12579)
    anchor_item_pos: Optional[jax.Array] = None  # (k_i,) anchor item positions
    u: Optional[jax.Array] = None                # (k_i, k_q) pinv(R_anc[:, I_anc])
    item_embeddings: Optional[jax.Array] = None  # (k_i, capacity) = U @ R_anc
    # optional corpus token table for device-resident CE scoring: row j holds
    # the (valid-first, fixed-length) item tokens of position j — kept in
    # positional lockstep with r_anc through every mutation
    item_tokens: Optional[jax.Array] = None      # (capacity, item_len) int32

    # ---- shape/metadata accessors -----------------------------------------

    @property
    def k_q(self) -> int:
        return self.r_anc.shape[0]

    @property
    def capacity(self) -> int:
        return self.r_anc.shape[1]

    @property
    def payload_dtype(self) -> str:
        """Storage dtype of the R_anc payload:
        float32 | bfloat16 | int8 | int4 | fp8."""
        return quant.payload_dtype_of(self.r_anc)

    @property
    def payload_nbytes(self) -> int:
        """Device bytes of the R_anc payload (codes + scales when int8)."""
        return int(self.r_anc.nbytes)

    def _payload_leaf(self) -> jax.Array:
        """The array whose NamedSharding carries the item-axis placement."""
        return self.r_anc.codes if self._quantized else self.r_anc

    @property
    def _quantized(self) -> bool:
        return isinstance(self.r_anc, QuantizedRanc)

    # ---- payload dtype policy ---------------------------------------------

    def quantize(
        self, dtype: str = "int8", tile: int = quant.DEFAULT_TILE
    ) -> "AnchorIndex":
        """Re-encode the R_anc payload
        (``int8`` | ``int4`` | ``fp8`` | ``bfloat16`` | ``float32``).

        The coded dtypes store per-item-tile symmetric codes + fp32 scales
        (int8 ~4x smaller, packed int4 ~8x, fp8-e4m3 ~4x with wider dynamic
        range; the fused kernel dequantizes tile-by-tile in registers).
        ANNCUR latents, if present, stay fp32 — they are (k_i, capacity)
        with k_i ≪ k_q and are not the memory bottleneck.  Quantizing an
        already-coded index with a different tile or code dtype re-quantizes
        from the dequantized codes (documented lossy; keep one encoding per
        artifact).
        """
        if dtype not in quant.PAYLOAD_DTYPES:
            raise ValueError(
                f"unknown payload dtype '{dtype}' (one of {quant.PAYLOAD_DTYPES})"
            )
        cur_payload = self.r_anc
        if dtype == self.payload_dtype and (
            not self._quantized or cur_payload.tile == tile
        ):
            return self
        dense = (
            quant.dequantize(cur_payload) if self._quantized
            else jnp.asarray(cur_payload, jnp.float32)
        )
        if dtype in quant.CODE_DTYPES:
            new = quant.quantize_ranc(dense, tile, code_dtype=dtype)
        elif dtype == "bfloat16":
            new = dense.astype(jnp.bfloat16)
        else:
            new = dense
        return dataclasses.replace(self, r_anc=new)

    @property
    def n_items(self) -> int:
        """Concrete valid-item count (host-side; do not call under a trace)."""
        return int(self.n_valid)

    @property
    def has_latents(self) -> bool:
        return self.item_embeddings is not None

    def valid_mask(self) -> jax.Array:
        """(capacity,) bool — True on real item positions."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.n_valid

    def gather_item_ids(self, pos: jax.Array) -> jax.Array:
        """Map engine positions (e.g. ``result.topk_idx``) to external ids."""
        return jnp.take(self.item_ids, pos, axis=0)

    # ---- construction ------------------------------------------------------

    @classmethod
    def from_r_anc(
        cls,
        r_anc: jax.Array,
        anchor_query_ids: Optional[jax.Array] = None,
        item_ids: Optional[jax.Array] = None,
        capacity: Optional[int] = None,
    ) -> "AnchorIndex":
        """Wrap a dense (k_q, N) score matrix, padding the item axis to
        ``capacity`` (defaults to N — no mutation headroom)."""
        k_q, n = r_anc.shape
        capacity = n if capacity is None else int(capacity)
        if capacity < n:
            raise ValueError(f"capacity={capacity} < n_items={n}")
        if anchor_query_ids is None:
            anchor_query_ids = jnp.arange(k_q, dtype=jnp.int32)
        if item_ids is None:
            item_ids = jnp.arange(n, dtype=jnp.int32)
        if item_ids.shape[0] != n:
            raise ValueError(f"item_ids {item_ids.shape} != n_items {n}")
        return cls(
            r_anc=_pad_axis(jnp.asarray(r_anc), 1, capacity, 0),
            anchor_query_ids=jnp.asarray(anchor_query_ids, jnp.int32),
            item_ids=_pad_axis(jnp.asarray(item_ids, jnp.int32), 0, capacity, -1),
            n_valid=jnp.asarray(n, jnp.int32),
        )

    @classmethod
    def build(
        cls,
        bulk_score_fn: BulkScoreFn,
        anchor_query_ids: jax.Array,
        item_ids: jax.Array,
        block_rows: int = 64,
        checkpoint_dir: Optional[str] = None,
        capacity: Optional[int] = None,
        payload_dtype: str = "float32",
        payload_tile: int = quant.DEFAULT_TILE,
    ) -> "AnchorIndex":
        """The offline indexing job: block-streamed, resumable R_anc build.

        With ``payload_dtype`` the finished artifact is emitted directly in
        the requested payload encoding (the fp32 row blocks themselves stay
        the resumable checkpoint unit — per-item-tile scales span all k_q
        rows, so quantization runs once over the assembled matrix)."""
        r_anc = build_r_anc(
            bulk_score_fn, anchor_query_ids, item_ids,
            block_rows=block_rows, checkpoint_dir=checkpoint_dir,
        )
        idx = cls.from_r_anc(
            r_anc, anchor_query_ids=anchor_query_ids, item_ids=item_ids,
            capacity=capacity,
        )
        return idx.quantize(payload_dtype, tile=payload_tile)

    def with_item_tokens(self, item_tokens) -> "AnchorIndex":
        """Attach the corpus token table (device-resident CE scoring).

        ``item_tokens`` is (n_valid, item_len) or (capacity, item_len) int32
        — row ``j`` tokenizes the item at *position* ``j`` (valid-first,
        fixed length, trailing pad).  The table is padded to capacity with
        pad rows (token 0) and from then on moves in positional lockstep
        with the payload through ``add_items``/``remove_items``/``shard``,
        so a :class:`~repro.core.scorer.DeviceCEScorer` can gather pair
        rows by engine position at any point in the index lifecycle."""
        item_tokens = jnp.asarray(item_tokens, jnp.int32)
        if item_tokens.ndim != 2:
            raise ValueError(f"item_tokens must be (n, item_len); got {item_tokens.shape}")
        n = item_tokens.shape[0]
        if n not in (self.n_items, self.capacity):
            raise ValueError(
                f"item_tokens rows ({n}) must cover the valid items "
                f"({self.n_items}) or the full capacity ({self.capacity})"
            )
        return dataclasses.replace(
            self, item_tokens=_pad_axis(item_tokens, 0, self.capacity, 0)
        )

    def with_capacity(self, capacity: int) -> "AnchorIndex":
        """Re-pad the item axis (must still hold all ``n_valid`` items).

        On a quantized payload only the padded tail changes, so every tile
        covering the valid prefix keeps bit-identical codes and scales."""
        n = self.n_items
        if capacity < n:
            raise ValueError(f"capacity={capacity} < n_valid={n}")
        if self._quantized:
            dense = _pad_axis(quant.dequantize(self.r_anc)[:, :n], 1, capacity, 0)
            r_anc = quant.requantize_preserving_prefix(self.r_anc, dense, n)
        else:
            r_anc = _pad_axis(self.r_anc[:, :n], 1, capacity, 0)
        emb = self.item_embeddings
        tok = self.item_tokens
        return dataclasses.replace(
            self,
            r_anc=r_anc,
            item_ids=_pad_axis(self.item_ids[:n], 0, capacity, -1),
            item_embeddings=(
                None if emb is None else _pad_axis(emb[:, :n], 1, capacity, 0)
            ),
            item_tokens=(
                None if tok is None else _pad_axis(tok[:n], 0, capacity, 0)
            ),
        )

    # ---- ANNCUR latents ----------------------------------------------------

    def with_anchors(
        self,
        k_anchor: Optional[int] = None,
        key: Optional[jax.Array] = None,
        anchor_pos: Optional[jax.Array] = None,
    ) -> "AnchorIndex":
        """Fix the ANNCUR anchor item *positions* (uniform over the valid
        prefix unless given) without computing latents — all the engine's
        ``ANNCURRetriever.from_index`` needs."""
        if anchor_pos is None:
            if key is None or k_anchor is None:
                raise ValueError("need (k_anchor, key) or explicit anchor_pos")
            anchor_pos = jax.random.choice(
                key, self.n_items, shape=(k_anchor,), replace=False
            )
        return dataclasses.replace(
            self, anchor_item_pos=jnp.asarray(anchor_pos, jnp.int32),
            u=None, item_embeddings=None,
        )

    def with_latents(
        self,
        k_anchor: Optional[int] = None,
        key: Optional[jax.Array] = None,
        anchor_pos: Optional[jax.Array] = None,
        rcond: float = 1e-6,
    ) -> "AnchorIndex":
        """:meth:`with_anchors` plus the precomputed ANNCUR pieces:
        ``U = pinv(R_anc[:, I_anc])`` and the latent item embeddings
        ``E_I = U @ R_anc`` (what :meth:`topk` searches over)."""
        idx = self.with_anchors(k_anchor=k_anchor, key=key, anchor_pos=anchor_pos)
        anchor_cols = quant.take_columns(idx.r_anc, idx.anchor_item_pos)
        u = cur.pinv(anchor_cols, rcond)                         # (k_i, k_q)
        return dataclasses.replace(
            idx, u=u, item_embeddings=quant.matmul(u, idx.r_anc)
        )

    def query_embedding(self, c_anchor: jax.Array) -> jax.Array:
        """(B, k_i) exact anchor scores -> (B, k_q) latent query embedding."""
        if self.u is None:
            raise ValueError("index has no latents; call with_latents() first")
        return c_anchor @ self.u

    # ---- dynamic corpus (padded capacity + n_valid, shapes never change) ---

    def add_items(
        self,
        new_item_ids: jax.Array,
        cols: Optional[jax.Array] = None,
        bulk_score_fn: Optional[BulkScoreFn] = None,
        new_tokens: Optional[jax.Array] = None,
    ) -> "AnchorIndex":
        """Append items into the padded tail.  ``cols`` is the (k_q, n_new)
        exact score block (computed via ``bulk_score_fn`` when omitted);
        latent item embeddings extend incrementally (``U`` is unchanged —
        the anchor columns are untouched).  An index carrying a token table
        requires ``new_tokens`` (n_new, item_len) so the table stays aligned
        with the payload.  Host-side offline op."""
        new_item_ids = jnp.asarray(new_item_ids, jnp.int32)
        n_new = int(new_item_ids.shape[0])
        n0 = self.n_items
        if n0 + n_new > self.capacity:
            raise ValueError(
                f"add_items overflows capacity {self.capacity} "
                f"({n0} + {n_new}); rebuild via with_capacity() first"
            )
        new_host = np.asarray(new_item_ids)
        if (new_host < 0).any():
            raise ValueError("add_items: item ids must be >= 0 (-1 is the padding sentinel)")
        if np.unique(new_host).size != n_new:
            raise ValueError("add_items: duplicate item ids in the new batch")
        if np.intersect1d(new_host, np.asarray(self.item_ids[: n0])).size:
            raise ValueError("add_items: some item ids already in the index")
        if cols is None:
            if bulk_score_fn is None:
                raise ValueError("need cols or bulk_score_fn")
            cols = bulk_score_fn(self.anchor_query_ids, new_item_ids)
        cols = jnp.asarray(cols, jnp.float32)
        if cols.shape != (self.k_q, n_new):
            raise ValueError(f"cols {cols.shape} != ({self.k_q}, {n_new})")
        tok = self.item_tokens
        if tok is not None:
            if new_tokens is None:
                raise ValueError(
                    "this index carries a token table (with_item_tokens); "
                    "add_items needs new_tokens (n_new, item_len) to keep it "
                    "position-aligned with the payload"
                )
            new_tokens = jnp.asarray(new_tokens, jnp.int32)
            if new_tokens.shape != (n_new, tok.shape[1]):
                raise ValueError(
                    f"new_tokens {new_tokens.shape} != ({n_new}, {tok.shape[1]})"
                )
            tok = jax.lax.dynamic_update_slice(tok, new_tokens, (n0, 0))
        elif new_tokens is not None:
            raise ValueError(
                "new_tokens given but the index carries no token table; "
                "attach one first (with_item_tokens)"
            )
        if self._quantized:
            # re-quantize only the tiles the new column range touches
            r_anc = quant.update_columns(self.r_anc, cols, n0)
        else:
            r_anc = jax.lax.dynamic_update_slice(
                self.r_anc, cols.astype(self.r_anc.dtype), (0, n0)
            )
        emb = self.item_embeddings
        return dataclasses.replace(
            self,
            r_anc=r_anc,
            item_ids=jax.lax.dynamic_update_slice(self.item_ids, new_item_ids, (n0,)),
            n_valid=jnp.asarray(n0 + n_new, jnp.int32),
            item_embeddings=(
                None if emb is None
                else jax.lax.dynamic_update_slice(
                    emb, (self.u @ cols).astype(emb.dtype), (0, n0)
                )
            ),
            item_tokens=tok,
        )

    def remove_items(self, remove_item_ids: jax.Array) -> "AnchorIndex":
        """Drop items by external id via *stable compaction*: surviving
        columns keep their relative order (so a removal is bit-identical to a
        from-scratch rebuild over the survivors), freed slots join the padded
        tail, and shapes never change.  On a quantized payload only the
        tiles from the first removed column onward re-quantize — the prefix
        keeps bit-identical codes and scales.  Host-side offline op."""
        cap = self.capacity
        rm = self.valid_mask() & jnp.isin(
            self.item_ids, jnp.asarray(remove_item_ids, jnp.int32)
        )
        if self.anchor_item_pos is not None and bool(rm[self.anchor_item_pos].any()):
            raise ValueError(
                "remove_items would drop an ANNCUR anchor item; rebuild the "
                "latents (with_latents) with a surviving anchor set first"
            )
        perm = jnp.argsort(rm.astype(jnp.int32), stable=True)  # survivors first, in order
        n1 = self.n_items - int(rm.sum())
        keep = jnp.arange(cap, dtype=jnp.int32) < n1
        if self._quantized:
            dense = quant.dequantize(self.r_anc)
            dense = jnp.where(keep[None, :], dense[:, perm], 0)
            # columns before the first removed position survive in place
            first_rm = int(jnp.argmax(rm)) if n1 < self.n_items else cap
            r_anc = quant.requantize_preserving_prefix(self.r_anc, dense, first_rm)
        else:
            r_anc = jnp.where(keep[None, :], self.r_anc[:, perm], 0)
        emb = self.item_embeddings
        tok = self.item_tokens
        new = dataclasses.replace(
            self,
            r_anc=r_anc,
            item_ids=jnp.where(keep, self.item_ids[perm], -1),
            n_valid=jnp.asarray(n1, jnp.int32),
            item_embeddings=(
                None if emb is None else jnp.where(keep[None, :], emb[:, perm], 0)
            ),
            item_tokens=(
                None if tok is None else jnp.where(keep[:, None], tok[perm], 0)
            ),
        )
        if self.anchor_item_pos is not None:
            inv = jnp.argsort(perm)                  # old position -> new
            new = dataclasses.replace(
                new, anchor_item_pos=inv[self.anchor_item_pos].astype(jnp.int32)
            )
        return new

    # ---- persistence (versioned, on the Checkpointer machinery) ------------

    def _tree(self) -> dict:
        t = {
            "anchor_query_ids": self.anchor_query_ids,
            "item_ids": self.item_ids,
            "n_valid": self.n_valid,
        }
        if self._quantized:
            t["r_codes"] = self.r_anc.codes
            t["r_scales"] = self.r_anc.scales
        else:
            t["r_anc"] = self.r_anc
        if self.anchor_item_pos is not None:
            t["anchor_item_pos"] = self.anchor_item_pos
        if self.has_latents:
            t.update(u=self.u, item_embeddings=self.item_embeddings)
        if self.item_tokens is not None:
            t["item_tokens"] = self.item_tokens
        return t

    def save(self, path: str) -> None:
        """Persist atomically under ``path`` (Checkpointer layout: one .npy
        per leaf + manifest with each leaf's save-time PartitionSpec, so a
        pod-scale index restores elastically onto any mesh)."""
        tree = self._tree()

        def leaf_spec(x, default: P) -> P:
            # record the ACTUAL placement of a sharded leaf; unsharded
            # leaves get the canonical default so a later load(mesh) still
            # distributes the item axis
            sh = getattr(x, "sharding", None)
            if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
                return sh.spec
            return default

        defaults = {
            "r_anc": P(None, "data"),
            "r_codes": P(None, "data"),        # co-sharded with r_scales:
            "r_scales": P("data"),             # items axis == tiles axis
            "anchor_query_ids": P(),
            "item_ids": P("data"),
            "n_valid": P(),
            "anchor_item_pos": P(),
            "u": P(),
            "item_embeddings": P(None, "data"),
            "item_tokens": P("data", None),
        }
        specs = {k: leaf_spec(v, defaults[k]) for k, v in tree.items()}
        ck = Checkpointer(path, async_save=False)
        ck.save(_CKPT_STEP, tree, specs)
        # stamp the lowest version whose on-disk features this index uses
        if self._quantized and self.r_anc.code_dtype != "int8":
            version = 4          # sub-int8 codes: packed int4 / fp8-e4m3
        elif self.item_tokens is not None:
            version = 3
        elif self._quantized:
            version = 2
        else:
            version = 1
        payload_meta = {
            "dtype": self.payload_dtype,
            "tile": self.r_anc.tile if self._quantized else None,
        }
        if self._quantized:
            payload_meta["code_dtype"] = self.r_anc.code_dtype
            payload_meta["n_cols"] = self.r_anc.n_cols
        meta = {
            "format_version": version,
            "k_q": self.k_q,
            "capacity": self.capacity,
            "n_items": self.n_items,
            "dtype": str(self.r_anc.dtype),
            "has_latents": self.has_latents,
            "payload": payload_meta,
        }
        tmp = os.path.join(path, _META_FILE + ".tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, os.path.join(path, _META_FILE))

    @classmethod
    def load(cls, path: str, mesh: Optional[Mesh] = None) -> "AnchorIndex":
        """Load a saved index; with a mesh, leaves are device_put with their
        save-time specs re-resolved on the new mesh (elastic restore)."""
        meta_path = os.path.join(path, _META_FILE)
        if not os.path.exists(meta_path):
            raise FileNotFoundError(f"no AnchorIndex at {path!r} ({_META_FILE} missing)")
        with open(meta_path) as f:
            meta = json.load(f)
        if meta.get("format_version") not in _READABLE_FORMAT_VERSIONS:
            raise ValueError(
                f"unsupported AnchorIndex format version {meta.get('format_version')} "
                f"(this build reads versions {_READABLE_FORMAT_VERSIONS})"
            )
        with open(os.path.join(path, f"step_{_CKPT_STEP}", "manifest.json")) as f:
            manifest = json.load(f)
        like = {
            k: jax.ShapeDtypeStruct(tuple(v["shape"]), np.dtype(v["dtype"]))
            for k, v in manifest["leaves"].items()
        }
        tree = Checkpointer(path, async_save=False).restore(_CKPT_STEP, like, mesh=mesh)
        if "r_codes" in tree:
            payload = meta.get("payload") or {}
            # v2/v3 meta predates sub-int8 codes: default to the int8 layout
            tree["r_anc"] = QuantizedRanc(
                codes=tree.pop("r_codes"),
                scales=tree.pop("r_scales"),
                tile=int(payload.get("tile") or quant.DEFAULT_TILE),
                code_dtype=str(payload.get("code_dtype") or "int8"),
                n_cols=int(payload.get("n_cols", -1)),
            )
        return cls(**tree)

    # ---- sharding + sharded search -----------------------------------------

    def shard(self, mesh: Mesh, rules=None) -> "AnchorIndex":
        """Place the item axis over ``mesh`` (capacity is re-padded to a
        shardable multiple if needed).  The placement lives in the arrays'
        own ``NamedSharding`` — it survives mutation (`add_items` etc.) and
        pytree ops — and :meth:`topk` / the retrievers' SPMD engine read it
        back to search under ``shard_map``.  A quantized payload co-shards
        codes and scales, and capacity aligns to
        ``n_item_shards * lcm(tile, NOISE_BLOCK)`` so every shard owns whole
        quantization tiles (with their scales) AND whole blocks of the
        engine's canonical noise field (the bit-parity requirement of
        ``sampling.blocked_gumbel``)."""
        from .sampling import NOISE_BLOCK

        idx = self
        grain = math.lcm(idx.r_anc.tile if idx._quantized else 1, NOISE_BLOCK)
        # learn which mesh axes the rules give the item axis (probe with a
        # capacity every axis divides), then align only to THOSE shards —
        # on a (data x items) mesh the data axis must not inflate the pad
        probe = sharding.spec_for(
            mesh, ("anchor_q", "items"), (idx.k_q, mesh.size * grain), rules
        )
        item_axes = probe[1] if len(probe) > 1 else None
        if item_axes is None:
            raise ValueError(
                f"item axis not shardable over mesh {dict(mesh.shape)}"
            )
        axes = (item_axes,) if isinstance(item_axes, str) else tuple(item_axes)
        n_item_shards = 1
        for a in axes:
            n_item_shards *= mesh.shape[a]
        unit = n_item_shards * grain
        if idx.capacity % unit:
            idx = idx.with_capacity(-(-idx.capacity // unit) * unit)

        def put(x, s):
            return jax.device_put(x, NamedSharding(mesh, s))

        if idx._quantized:
            r_anc = QuantizedRanc(
                codes=put(idx.r_anc.codes, P(None, axes)),
                scales=put(idx.r_anc.scales, P(axes)),
                tile=idx.r_anc.tile,
                code_dtype=idx.r_anc.code_dtype,
                n_cols=idx.r_anc.n_cols,
            )
        else:
            r_anc = put(idx.r_anc, P(None, axes))
        emb = idx.item_embeddings
        tok = idx.item_tokens
        out = dataclasses.replace(
            idx,
            r_anc=r_anc,
            anchor_query_ids=put(idx.anchor_query_ids, P()),
            item_ids=put(idx.item_ids, P(axes)),
            n_valid=put(idx.n_valid, P()),
            item_embeddings=None if emb is None else put(emb, P(None, axes)),
            item_tokens=None if tok is None else put(tok, P(axes, None)),
        )
        if idx.anchor_item_pos is not None:
            out = dataclasses.replace(
                out,
                anchor_item_pos=put(idx.anchor_item_pos, P()),
                u=put(idx.u, P()),
            )
        return out

    def _item_sharding(self) -> Tuple[Optional[Mesh], Optional[Tuple[str, ...]]]:
        """(mesh, item axes) read back from ``r_anc``'s NamedSharding, or
        (None, None) when the item axis is unsharded/replicated."""
        sh = getattr(self._payload_leaf(), "sharding", None)
        if not isinstance(sh, NamedSharding) or sh.mesh.size == 1:
            return None, None
        spec = sh.spec
        item_axes = spec[1] if len(spec) > 1 else None
        if item_axes is None:
            return None, None
        axes = (item_axes,) if isinstance(item_axes, str) else tuple(item_axes)
        return sh.mesh, axes

    def topk(
        self,
        e_q: jax.Array,
        k: int,
        *,
        mesh: Optional[Mesh] = None,
        item_axes: Optional[Tuple[str, ...]] = None,
        tile: int = 512,
    ) -> Tuple[jax.Array, jax.Array]:
        """Top-k of ``e_q @ R_anc`` over *valid* items -> (vals, positions).

        On a sharded index (``shard(mesh)``, ``load(path, mesh)``, or
        explicit mesh/item_axes) each shard runs the fused ``approx_topk``
        over its local item slab — global (B, N) scores are never
        materialized anywhere — and the per-shard candidates are merged with
        an all-gather + top-k (the cross-shard merge is over n_shards·k
        entries, ≪ N).  The placement is detected from ``r_anc``'s
        ``NamedSharding``, so mutated/replaced indices keep their path.
        """
        if mesh is None and item_axes is None:
            mesh, item_axes = self._item_sharding()
        invalid = ~self.valid_mask()
        b = e_q.shape[0]
        if mesh is None:
            mask = jnp.broadcast_to(invalid[None, :], (b, self.capacity))
            return approx_topk_op(
                e_q, self.r_anc, k, tile=tile, mask=mask
            )
        axes = item_axes
        if axes is None:
            raise ValueError("sharded topk needs item_axes alongside mesh")
        n_shards = 1
        for a in axes:
            n_shards *= mesh.shape[a]
        n_local = self.capacity // n_shards
        if k > n_local:
            raise ValueError(f"k={k} > per-shard items {n_local}")
        quantized = self._quantized
        tile_q = self.r_anc.tile if quantized else 0
        cdt = self.r_anc.code_dtype if quantized else "int8"

        def body(eq, r_local, scales_local, inv_local):
            shard_id = jnp.int32(0)
            for a in axes:
                shard_id = shard_id * jax.lax.psum(1, a) + jax.lax.axis_index(a)
            if quantized:
                # codes + scales arrive co-sharded: the local slab is a
                # self-contained payload over this shard's whole tiles
                # (shard widths are whole even tiles, so the packed width
                # sentinel n_cols=-1 resolves correctly per shard)
                r_local = QuantizedRanc(r_local, scales_local, tile_q, cdt)
            mask = jnp.broadcast_to(inv_local[None, :], (eq.shape[0], n_local))
            v, i = approx_topk_op(
                eq, r_local, k, tile=min(tile, n_local), mask=mask,
            )
            gi = i + shard_id * n_local
            vg = jax.lax.all_gather(v, axes, axis=1, tiled=True)   # (B, S*k)
            ig = jax.lax.all_gather(gi, axes, axis=1, tiled=True)
            vt, pos = jax.lax.top_k(vg, k)
            return vt, jnp.take_along_axis(ig, pos, axis=1)

        if quantized:
            payload_args = (self.r_anc.codes, self.r_anc.scales)
        else:
            payload_args = (self.r_anc, jnp.zeros((n_shards,), jnp.float32))
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(None, axes), P(axes), P(axes)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return fn(e_q, *payload_args, invalid)

    def engine_search(self, score_fn, query, cfg, key=None, **kw):
        """One-shot FULL multi-round search over this index — the engine
        twin of :meth:`topk`.  On a sharded index (``shard(mesh)`` /
        ``load(path, mesh)``) the whole round loop runs as one SPMD program
        under ``shard_map`` (``engine.make_sharded_engine``), bit-identical
        to the single-device engine; otherwise it is the plain compiled
        engine.  For repeated queries hold a
        ``Retriever.from_index``-built retriever instead — this constructs
        one per call."""
        from .engine import AdaCURRetriever

        ret = AdaCURRetriever.from_index(self, score_fn, cfg)
        return ret.search(query, key, **kw)
