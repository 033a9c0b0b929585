"""Quantized anchor-payload subsystem: sub-fp32 codes + per-item-tile scales.

At the ROADMAP's "millions of items" scale the offline artifact — the
(k_q, N) anchor score matrix ``R_anc`` — is the memory bottleneck, exactly
the gap arXiv 2405.03651 identifies over ANNCUR: fp32 R_anc at k_q=500,
N=10^6 is 2 GB, and the engine streams all of it over the item axis twice
per round.  This module stores R_anc as quantized codes plus

- ``scales`` (ceil(N / tile),) fp32 — one scale per *item tile*, shared by
  all k_q rows of that tile (``scale = amax_tile / qmax``),

in one of three code formats (``QuantizedRanc.code_dtype``):

- ``"int8"``  — (k_q, N) int8, qmax 127 (0.25x fp32 bytes);
- ``"int4"``  — (k_q, ceil(N/2)) uint8, two signed nibbles per byte
  (column 2j in the low nibble, 2j+1 in the high nibble), qmax 7
  (0.125x fp32 bytes);
- ``"fp8"``   — (k_q, N) float8_e4m3fn, qmax 448 = e4m3's max finite
  (0.25x fp32 bytes, but ~2 extra bits of dynamic range per tile vs int8).

Scores dequantize per column:  ``S_hat[:, j] = (e_q @ codes[:, j]) *
scales[j // tile]`` — algebraically the scale factors out of the
contraction, so the fused kernel applies it to the (B, T) GEMM *output* in
registers and the fp32 R_anc never exists anywhere.

Tile-local scales make mutation cheap: ``add_items``/``remove_items``
re-quantize only the tiles whose columns changed (see
:func:`update_columns` / :func:`requantize_preserving_prefix`), so
untouched tiles keep bit-identical codes *and* scales across a mutation
round-trip — including packed int4 tiles, because the quantization tile is
required to be even so tile boundaries always fall on byte boundaries.

Everything here is dtype-polymorphic over the payload policies
(``AdaCURConfig.payload_dtype``): plain fp32 arrays, bf16 arrays, and
:class:`QuantizedRanc`.  The engine and the fused ``approx_topk`` op call
the dispatchers (:func:`matmul`, :func:`gather_columns`, ...) and never
branch on the payload type themselves.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

PAYLOAD_DTYPES = ("float32", "bfloat16", "int8", "int4", "fp8")
CODE_DTYPES = ("int8", "int4", "fp8")
DEFAULT_TILE = 512

# the approximate-score contraction e_q @ R_anc in fp32 on every backend
# (a TPU's default would round both operands to bf16); every path that
# computes S_hat — dense, scan and kernel — uses it, so their scores agree
HIGHEST = jax.lax.Precision.HIGHEST

_QMAX = {"int8": 127.0, "int4": 7.0, "fp8": 448.0}
# real storage bytes per column per k_q row (scales add 4 / tile per column)
CODE_BYTES_PER_COL = {"int8": 1.0, "int4": 0.5, "fp8": 1.0}


def fp8_supported() -> bool:
    """Whether this JAX build carries float8_e4m3fn (all recent builds do)."""
    return hasattr(jnp, "float8_e4m3fn")


def pack_int4(codes: jax.Array) -> jax.Array:
    """(k_q, n) signed nibble values in [-8, 7] -> (k_q, ceil(n/2)) uint8.

    Column 2j lands in the low nibble of byte j, column 2j+1 in the high
    nibble; an odd trailing column packs against a zero phantom nibble.
    """
    c = jnp.asarray(codes, jnp.int32)
    if c.shape[1] % 2:
        c = jnp.pad(c, ((0, 0), (0, 1)))
    c = c & 0xF
    return (c[:, 0::2] | (c[:, 1::2] << 4)).astype(jnp.uint8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """(k_q, m) uint8 -> (k_q, 2m) int8 signed nibble values.

    Branch-free sign extension in int32 (``v - ((v & 8) << 1)``) and a
    repeat+parity-shift interleave, so the same helper runs unchanged inside
    the Pallas kernel body and in plain XLA — guaranteeing bit-identical
    nibble decode on every backend.
    """
    u = jnp.repeat(packed.astype(jnp.int32), 2, axis=1)          # (k_q, 2m)
    col = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    nib = (u >> jnp.where(col % 2 == 0, 0, 4)) & 0xF
    return (nib - ((nib & 0x8) << 1)).astype(jnp.int8)


def _take_nibbles(packed: jax.Array, pos: jax.Array) -> jax.Array:
    """Gather logical int4 columns ``pos`` -> (k_q, *pos.shape) int8."""
    byte = jnp.take(packed, pos // 2, axis=1).astype(jnp.int32)
    shift = jnp.where(pos % 2 == 0, 0, 4)
    nib = (byte >> shift[None]) & 0xF
    return (nib - ((nib & 0x8) << 1)).astype(jnp.int8)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("codes", "scales"),
    meta_fields=("tile", "code_dtype", "n_cols"),
)
@dataclass
class QuantizedRanc:
    """Quantized anchor payload: per-item-tile symmetric codes for R_anc.

    ``dequant(codes)[q, j] * scales[j // tile]`` reconstructs entry (q, j);
    an all-zero tile stores scale 1.0 so dequantization is always exact
    zeros there (padded capacity tails stay exact).  ``tile``/``code_dtype``
    are pytree metadata, so payloads with equal meta hash/trace identically
    under jit.  ``n_cols`` only exists for odd-width int4 payloads (the
    packed byte count over-states the logical width by one); it stays -1
    ("2x the packed width") everywhere else — in particular for every
    sharded payload, whose aligned capacity is always even, so shard_map's
    per-shard reconstruction reports the correct local width.
    """

    codes: jax.Array     # int8 (k_q, N) | uint8 (k_q, ceil(N/2)) | fp8 (k_q, N)
    scales: jax.Array    # (ceil(N / tile),) float32
    tile: int
    code_dtype: str = "int8"
    n_cols: int = -1

    @property
    def packing(self) -> int:
        """Logical columns per stored code element (2 for packed int4)."""
        return 2 if self.code_dtype == "int4" else 1

    @property
    def shape(self):
        k_q, m = self.codes.shape
        if self.code_dtype == "int4":
            return (k_q, m * 2 if self.n_cols < 0 else self.n_cols)
        return (k_q, m)

    @property
    def dtype(self):
        """The *compute* dtype — everything dequantizes into fp32."""
        return jnp.dtype(jnp.float32)

    @property
    def nbytes(self) -> int:
        """Real storage bytes (packed int4 counts 0.5 bytes per column)."""
        return self.codes.nbytes + self.scales.nbytes

    @property
    def n_tiles(self) -> int:
        return self.scales.shape[0]

    def col_scales(self) -> jax.Array:
        """(N,) per-column fp32 scales (tile scales expanded)."""
        n = self.shape[1]
        full = jnp.repeat(
            self.scales, self.tile, total_repeat_length=self.n_tiles * self.tile
        )
        return full[:n]


def payload_dtype_of(r_anc) -> str:
    """The policy name of a payload operand ("float32"/"bfloat16"/"int8"/
    "int4"/"fp8")."""
    if isinstance(r_anc, QuantizedRanc):
        return r_anc.code_dtype
    return str(jnp.asarray(r_anc).dtype)


def payload_nbytes(
    payload_dtype: str, k_q: int, n: int, tile: int = DEFAULT_TILE
) -> int:
    """Analytic REAL byte footprint of a (k_q, n) payload under a policy.

    Uses actual storage bytes — a packed int4 column is 0.5 bytes per row
    (two codes per byte), never an element count — plus the 4-byte-per-tile
    fp32 scale vector for the coded dtypes.  Matches ``.nbytes`` of the
    concrete operand (up to int4's odd-width padding byte per row).
    """
    if payload_dtype not in PAYLOAD_DTYPES:
        raise ValueError(
            f"unknown payload_dtype '{payload_dtype}' (one of {PAYLOAD_DTYPES})"
        )
    if payload_dtype == "float32":
        return k_q * n * 4
    if payload_dtype == "bfloat16":
        return k_q * n * 2
    codes = int(math.ceil(k_q * n * CODE_BYTES_PER_COL[payload_dtype]))
    return codes + 4 * (-(-n // tile))


def unpacked_codes(payload: QuantizedRanc) -> jax.Array:
    """Codes at logical width — int4 nibbles widened to int8, others as-is."""
    if payload.code_dtype == "int4":
        return unpack_int4(payload.codes)[:, : payload.shape[1]]
    return payload.codes


def quantize_ranc(
    r_anc: jax.Array, tile: int = DEFAULT_TILE, code_dtype: str = "int8"
) -> QuantizedRanc:
    """Symmetric per-item-tile quantization (round to nearest).

    Deterministic for the integer formats: re-quantizing a dequantized
    payload whose tile scale is unchanged recovers the codes bit-exactly
    (|codes| <= qmax, so the round-trip error is far below the 0.5 rounding
    radius).  fp8 makes no such fixpoint claim (its rounding grid is
    value-dependent) — mutation bit-identity for fp8 tiles comes from the
    byte-splicing in :func:`update_columns` /
    :func:`requantize_preserving_prefix`, not from re-encoding.
    """
    if code_dtype not in CODE_DTYPES:
        raise ValueError(f"unknown code_dtype '{code_dtype}' (one of {CODE_DTYPES})")
    if code_dtype == "int4" and tile % 2:
        raise ValueError(f"int4 payloads need an even tile, got {tile}")
    if code_dtype == "fp8" and not fp8_supported():
        raise ValueError("fp8 payloads need jnp.float8_e4m3fn in this JAX build")
    x = jnp.asarray(r_anc, jnp.float32)
    k_q, n = x.shape
    n_tiles = -(-n // tile)
    n_pad = n_tiles * tile
    if n_pad != n:
        x = jnp.pad(x, ((0, 0), (0, n_pad - n)))
    qmax = _QMAX[code_dtype]
    amax = jnp.max(jnp.abs(x.reshape(k_q, n_tiles, tile)), axis=(0, 2))
    scales = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    col = jnp.repeat(scales, tile, total_repeat_length=n_pad)
    y = x / col[None, :]
    if code_dtype == "fp8":
        # e4m3fn has no inf: an out-of-range cast is nan, not a saturate —
        # clip first (amax/scale can land an ulp above qmax)
        codes = jnp.clip(y, -qmax, qmax).astype(jnp.float8_e4m3fn)
        return QuantizedRanc(codes[:, :n], scales, tile, "fp8")
    q = jnp.clip(jnp.round(y), -qmax, qmax)
    if code_dtype == "int4":
        packed = pack_int4(q[:, :n].astype(jnp.int32))
        return QuantizedRanc(packed, scales, tile, "int4", n if n % 2 else -1)
    return QuantizedRanc(q.astype(jnp.int8)[:, :n], scales, tile)


def dequantize(payload: QuantizedRanc) -> jax.Array:
    """(k_q, N) fp32 reconstruction — offline/debug only, never the hot path."""
    return unpacked_codes(payload).astype(jnp.float32) * payload.col_scales()[None, :]


def as_payload(r_anc, payload_dtype: str, tile: int = DEFAULT_TILE):
    """Apply the config's payload policy to a raw operand.

    A plain array is converted *up* to the requested payload (bf16 cast or
    int8/int4/fp8 quantization — traced, so bare-r_anc retrievers pay the
    conversion per call; index-backed retrievers pre-quantize via
    ``AnchorIndex.quantize`` and skip this).  An operand that is already a
    :class:`QuantizedRanc` is authoritative and passes through unchanged.
    """
    if payload_dtype not in PAYLOAD_DTYPES:
        raise ValueError(
            f"unknown payload_dtype '{payload_dtype}' (one of {PAYLOAD_DTYPES})"
        )
    if isinstance(r_anc, QuantizedRanc) or payload_dtype == "float32":
        return r_anc
    if payload_dtype == "bfloat16":
        return jnp.asarray(r_anc).astype(jnp.bfloat16)
    return quantize_ranc(r_anc, tile, code_dtype=payload_dtype)


def matmul(e_q: jax.Array, r_anc) -> jax.Array:
    """Dense ``e_q @ R_anc`` -> (B, N) fp32 for any payload type.

    This is the *dense* engine path (and the oracle the fused kernels are
    tested against); the per-column scale is applied to the GEMM output, the
    same factoring the kernels use, so dense and fused scores agree.
    """
    if isinstance(r_anc, QuantizedRanc):
        s = jnp.matmul(
            e_q.astype(jnp.float32),
            unpacked_codes(r_anc).astype(jnp.float32),
            precision=HIGHEST,
        )
        return s * r_anc.col_scales()[None, :]
    return jnp.matmul(
        e_q.astype(jnp.float32), jnp.asarray(r_anc).astype(jnp.float32),
        precision=HIGHEST,
    )


def take_columns(r_anc, pos: jax.Array) -> jax.Array:
    """R_anc[:, pos] -> (k_q, k) fp32 for an unbatched position vector."""
    if isinstance(r_anc, QuantizedRanc):
        if r_anc.code_dtype == "int4":
            cols = _take_nibbles(r_anc.codes, pos).astype(jnp.float32)
        else:
            cols = jnp.take(r_anc.codes, pos, axis=1).astype(jnp.float32)
        return cols * r_anc.scales[pos // r_anc.tile][None, :]
    return jnp.take(jnp.asarray(r_anc), pos, axis=1).astype(jnp.float32)


def gather_columns(r_anc, anchor_idx: jax.Array, via_onehot: bool = False):
    """R_anc[:, I_anc] for a batch of per-query anchor sets -> (B, k_q, k) fp32.

    The payload-aware twin of ``cur.gather_anchor_columns`` — dequantizes
    exactly the gathered columns (k columns, not N; for packed int4 that is
    k *nibble* reads, never a full unpack).  ``via_onehot`` expresses the
    gather as a one-hot matmul for column-sharded payloads (see cur.py for
    why).
    """
    if not isinstance(r_anc, QuantizedRanc):
        r = jnp.asarray(r_anc)
        if via_onehot:
            n = r.shape[1]
            onehot = (
                anchor_idx[:, None, :] == jnp.arange(n)[None, :, None]
            ).astype(jnp.float32)
            return jnp.einsum("qn,bnk->bqk", r.astype(jnp.float32), onehot)
        return jnp.swapaxes(r.T[anchor_idx], 1, 2).astype(jnp.float32)
    scale = r_anc.scales[anchor_idx // r_anc.tile]            # (B, k)
    if via_onehot:
        n = r_anc.shape[1]
        onehot = (
            anchor_idx[:, None, :] == jnp.arange(n)[None, :, None]
        ).astype(jnp.float32)
        cols = jnp.einsum(
            "qn,bnk->bqk", unpacked_codes(r_anc).astype(jnp.float32), onehot
        )
    elif r_anc.code_dtype == "int4":
        # (k_q, B, k) nibble gather -> (B, k_q, k)
        cols = jnp.swapaxes(
            _take_nibbles(r_anc.codes, anchor_idx), 0, 1
        ).astype(jnp.float32)
    else:
        cols = jnp.swapaxes(r_anc.codes.T[anchor_idx], 1, 2).astype(jnp.float32)
    return cols * scale[:, None, :]


def subset_columns(r_anc, pos: jax.Array, valid: jax.Array):
    """Gather columns ``pos`` into a compact sub-payload of the same policy.

    The workhorse of candidate-subset search: ``pos`` (C,) are corpus column
    positions (padded entries may repeat position 0 — ``valid`` (C,) bool
    marks the real ones) and the result is a (k_q, C) payload whose column j
    *dequantizes bit-identically* to column ``pos[j]`` of the full payload.
    For int8/fp8 the gathered codes keep their original bytes and each
    column carries its source tile's scale (``tile=1`` — per-column scales),
    so no re-quantization happens and whole-tile alignment of the subset is
    not required.  Packed int4 nibbles widen to int8 codes (the nibble
    *values* are preserved exactly, so dequantization stays bit-identical;
    only the shortlist-sized subset pays the 2x byte widening — subsets can
    be odd-width and scattered, which packed storage cannot represent).
    Invalid columns are exact zeros (codes 0 / scale 1.0 / fp32 0), matching
    the engine's padded-capacity invariant.
    """
    if isinstance(r_anc, QuantizedRanc):
        scales = jnp.where(
            valid, r_anc.scales[pos // r_anc.tile], jnp.float32(1.0)
        )
        if r_anc.code_dtype == "int4":
            codes = _take_nibbles(r_anc.codes, pos)
            codes = jnp.where(valid[None, :], codes, jnp.int8(0))
            return QuantizedRanc(codes, scales, tile=1, code_dtype="int8")
        codes = jnp.take(r_anc.codes, pos, axis=1)
        codes = jnp.where(valid[None, :], codes, jnp.zeros((), codes.dtype))
        return QuantizedRanc(codes, scales, tile=1, code_dtype=r_anc.code_dtype)
    r = jnp.asarray(r_anc)
    cols = jnp.take(r, pos, axis=1)
    return jnp.where(valid[None, :], cols, jnp.zeros((), r.dtype))


# ---------------------------------------------------------------------------
# Tile-local mutation: re-quantize ONLY the touched tiles.
# ---------------------------------------------------------------------------


def dequantize_slice(payload: QuantizedRanc, lo: int, hi: int) -> jax.Array:
    """fp32 reconstruction of columns [lo, hi) — lo/hi concrete host ints.

    For packed int4, ``lo`` must be even (callers slice at tile boundaries,
    and int4 tiles are even); ``hi`` may be odd (a phantom high nibble is
    decoded and discarded).
    """
    if payload.code_dtype == "int4":
        assert lo % 2 == 0, "int4 slices must start on a byte boundary"
        packed = jax.lax.slice_in_dim(payload.codes, lo // 2, -(-hi // 2), axis=1)
        codes = unpack_int4(packed)[:, : hi - lo]
    else:
        codes = jax.lax.slice_in_dim(payload.codes, lo, hi, axis=1)
    return codes.astype(jnp.float32) * payload.col_scales()[lo:hi][None, :]


def update_columns(
    payload: QuantizedRanc, cols: jax.Array, start: int
) -> QuantizedRanc:
    """Overwrite columns [start, start + m) with fp32 ``cols``, re-quantizing
    only the tiles that range touches (``add_items``' hot path).  Codes in a
    touched tile whose scale is unchanged by the new columns re-quantize
    bit-identically (int8/int4); tiles outside the range are returned
    byte-for-byte — for packed int4 the touched region is spliced at byte
    granularity, which tile-evenness makes exact.
    """
    k_q, m = cols.shape
    tile = payload.tile
    n = payload.shape[1]
    t0 = start // tile
    t1 = -(-(start + m) // tile)                   # exclusive touched-tile end
    lo, hi = t0 * tile, min(t1 * tile, n)
    region = dequantize_slice(payload, lo, hi)
    region = jax.lax.dynamic_update_slice(
        region, jnp.asarray(cols, jnp.float32), (0, start - lo)
    )
    sub = quantize_ranc(region, tile, code_dtype=payload.code_dtype)
    if payload.code_dtype == "int4":
        codes = jax.lax.dynamic_update_slice(payload.codes, sub.codes, (0, lo // 2))
    else:
        codes = jax.lax.dynamic_update_slice(payload.codes, sub.codes, (0, lo))
    scales = jax.lax.dynamic_update_slice(payload.scales, sub.scales, (t0,))
    return QuantizedRanc(
        codes, scales, tile, payload.code_dtype, payload.n_cols
    )


def requantize_preserving_prefix(
    old: QuantizedRanc, new_f32: jax.Array, first_touched_col: int
) -> QuantizedRanc:
    """Quantize ``new_f32``, then restore the bytes of every tile strictly
    before the first touched column from ``old`` (they are guaranteed
    value-identical, and this guarantees them *bit*-identical — fp scale
    recomputation could otherwise drift an ulp; for fp8 the re-encoding
    grid itself can drift, so byte restoration is the only correctness
    story).

    Used by ``remove_items`` (stable compaction leaves the prefix before the
    first removed column in place) and ``with_capacity`` (only the padded
    tail changes).  ``new_f32`` may have a different width than ``old``.
    """
    newp = quantize_ranc(new_f32, old.tile, code_dtype=old.code_dtype)
    t0 = min(first_touched_col // old.tile, old.n_tiles, newp.n_tiles)
    keep = t0 * old.tile
    if keep == 0:
        return newp
    kc = keep // old.packing            # tile evenness: byte-aligned for int4
    codes = newp.codes.at[:, :kc].set(old.codes[:, :kc])
    scales = newp.scales.at[:t0].set(old.scales[:t0])
    return QuantizedRanc(codes, scales, old.tile, old.code_dtype, newp.n_cols)
