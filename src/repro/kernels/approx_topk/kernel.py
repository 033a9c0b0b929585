"""Fused approximate-score + top-k Pallas kernel (the paper's hot op).

ADACUR's per-round inner loop (Alg. 2 line 7 + retrieval) is

    S_hat = e_q @ R_anc ;  top-k(S_hat  masked on selected anchors)

with e_q = C_test @ U precomputed (B, k_q) and R_anc (k_q, N).  Naively this
writes the (B, N) score matrix to HBM and reads it back for top-k — 2·B·N·4
bytes of traffic that dominates at N ~ 10^6.  This kernel fuses the GEMM
with a per-tile top-k so scores never leave VMEM:

  grid = (n_item_tiles,); each step:
    scores = e_q @ R_anc[:, tile]                 (MXU, (B, T))
    scores += noise[:, tile]                      (optional Gumbel input —
                                                   SoftMax sampling w/o
                                                   replacement, Kool 2019)
    drop   = mask[:, tile] (selected anchors, Alg. 3 line 8)
             ∨ tile_ids >= n_valid
    per-tile top-k via k iterations of (max, argmax, suppress)
  outputs: (B, n_tiles, k) values + global indices.

The tiny (B, n_tiles·k) cross-tile merge happens in ops.py with one
jax.lax.top_k — n_tiles·k ≪ N, so the HBM round-trip shrinks by ~T/k
(e.g. 512/64 = 8x) and the GEMM output never hits HBM at all.

Suppression is a dense (B, N) bool mask streamed tile by tile (O(B·T) per
tile): the engine already maintains its ``selected`` mask, so no id list is
compared in-kernel.  ``n_valid`` suppresses padded item ids >= n_valid when
R_anc's item axis is padded to a shardable multiple (pod meshes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..backend import resolve_interpret
from .quant import HIGHEST, unpack_int4

NEG_INF = -1e30


def pad_to_tile(tile: int, r_anc, noise=None, mask=None, scales=None,
                pack: int = 1, n: int | None = None):
    """Zero-pad the item axis to a tile multiple (shared by both backends).

    ``scales`` is the optional (N,) per-column dequantization scale vector of
    a quantized payload; padded columns carry scale 1.0 (their codes pad to
    0, so the padded scores are exact zeros and the n_items bound masks
    them).  ``pack`` > 1 means ``r_anc`` holds packed codes (int4: 2 logical
    columns per stored byte) — it is padded in *packed* coordinates, which
    tile-evenness keeps exact; ``n`` is then the logical column count.
    """
    if n is None:
        n = r_anc.shape[1] * pack
    n_pad = pl.cdiv(n, tile) * tile
    m_pad = n_pad // pack
    if r_anc.shape[1] != m_pad:
        r_anc = jnp.pad(r_anc, ((0, 0), (0, m_pad - r_anc.shape[1])))
    if n_pad != n:
        pad = ((0, 0), (0, n_pad - n))
        noise = jnp.pad(noise, pad) if noise is not None else None
        mask = jnp.pad(mask, pad) if mask is not None else None
        if scales is not None:
            scales = jnp.pad(scales, (0, n_pad - n), constant_values=1.0)
    return r_anc, noise, mask, scales, n_pad


def check_compiled_payload(kernel: str, pack: int, interpret: bool) -> None:
    """Refuse a payload the compiled TPU kernel cannot take, naming it.

    Packed int4 lowers, but widening its nibbles in-kernel
    (``quant.unpack_int4``'s repeat-interleave) needs several times the
    scoped VMEM of a v5e core at every tile width; the Pallas interpreter
    and the scan backend still run it on CPU."""
    if pack == 2 and not interpret:
        raise NotImplementedError(
            f"{kernel}: int4 payloads do not compile for TPU (the in-kernel "
            f"nibble unpack exceeds scoped VMEM); use int8 or fp8"
        )


def as_kernel_mask(mask: jax.Array) -> jax.Array:
    """A (B, N) bool suppression mask as the int8 operand the kernels
    stream (a TPU kernel takes no bool memory operand)."""
    return mask.astype(jnp.int8)


def _set_col(buf, i, col):
    """``buf[:, i] = col`` as an iota-mask select (a scatter does not lower
    in a TPU kernel)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 1)
    return jnp.where(lanes == i, col[:, None], buf)


def _approx_topk_kernel(
    e_q_ref,        # (B, k_q)
    r_anc_ref,      # (k_q, T) scores / int8 / fp8 — or (k_q, T/2) packed int4
    *rest,          # [scales_ref (1,T)] [noise_ref (B,T)] [mask_ref (B,T)]
                    # vals_ref, idx_ref
    tile: int,
    k: int,
    n_items: int,
    pack: int,
    has_scales: bool,
    has_noise: bool,
    has_mask: bool,
):
    it = iter(rest)
    scales_ref = next(it) if has_scales else None
    noise_ref = next(it) if has_noise else None
    mask_ref = next(it) if has_mask else None
    vals_ref, idx_ref = next(it), next(it)
    ti = pl.program_id(0)
    e_q = e_q_ref[...].astype(jnp.float32)                 # (B, k_q)
    # fused dequant front end: an int8/fp8 tile widens in registers (a
    # packed int4 tile additionally sign-extends its nibbles first); the
    # per-column scale factors out of the contraction and multiplies the
    # (B, T) GEMM output, so the fp32 R_anc tile never exists in memory.
    r = r_anc_ref[...]
    if pack == 2:
        r = unpack_int4(r)                                 # (k_q, T) int8
    r = r.astype(jnp.float32)                              # (k_q, T)
    scores = jax.lax.dot_general(
        e_q, r, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                       # (B, T)
    if scales_ref is not None:
        scores = scores * scales_ref[...]                  # (1, T) broadcast
    if noise_ref is not None:
        scores = scores + noise_ref[...].astype(jnp.float32)
    b = scores.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    gids = ti * tile + cols
    # suppress padded ids and the streamed mask (already-selected anchors,
    # Alg. 3 line 8)
    drop = gids >= n_items
    if mask_ref is not None:
        drop = drop | (mask_ref[...].astype(jnp.int32) != 0)
    scores = jnp.where(drop, NEG_INF, scores)

    def take_max(i, carry):
        s, vals, idx = carry
        m = jnp.max(s, axis=1)                              # (B,)
        # first column holding the max (argmax's tie rule)
        am = jnp.min(jnp.where(s == m[:, None], cols, tile), axis=1)
        vals = _set_col(vals, i, m)
        idx = _set_col(idx, i, ti * tile + am)
        # suppress the winner
        s = jnp.where(cols == am[:, None], NEG_INF, s)
        return s, vals, idx

    vals0 = jnp.full((b, k), NEG_INF, jnp.float32)
    idx0 = jnp.zeros((b, k), jnp.int32)
    _, vals, idx = jax.lax.fori_loop(0, k, take_max, (scores, vals0, idx0))
    vals_ref[...] = vals
    idx_ref[...] = idx


def approx_topk_tiles(
    e_q: jax.Array,        # (B, k_q) f32
    r_anc: jax.Array,      # (k_q, N) scores — or quantized codes (pass scales)
    k: int,
    *,
    tile: int = 512,
    interpret: bool | None = None,
    noise: jax.Array | None = None,   # (B, N) additive noise (Gumbel sampling)
    mask: jax.Array | None = None,    # (B, N) bool — True = suppress
    n_valid: int | None = None,       # real item count when N is padded
    scales: jax.Array | None = None,  # (N,) per-column dequant scales
    pack: int = 1,                    # 2 = r_anc is packed int4 (k_q, N/2)
    n_cols: int | None = None,        # logical N when r_anc is packed
):
    """Returns per-tile (vals (B, n_tiles, k), idx (B, n_tiles, k))."""
    interpret = resolve_interpret(interpret)
    check_compiled_payload("approx_topk_sweep", pack, interpret)
    b, k_q = e_q.shape
    n = r_anc.shape[1] * pack if n_cols is None else n_cols
    if pack > 1 and tile % pack:
        tile += pack - tile % pack
    r_anc, noise, mask, scales, n_pad = pad_to_tile(
        tile, r_anc, noise, mask, scales, pack=pack, n=n
    )
    n_tiles = n_pad // tile
    kernel = functools.partial(
        _approx_topk_kernel, tile=tile, k=k,
        n_items=n if n_valid is None else min(n_valid, n),
        pack=pack,
        has_scales=scales is not None,
        has_noise=noise is not None, has_mask=mask is not None,
    )
    in_specs = [
        pl.BlockSpec((b, k_q), lambda ti: (0, 0)),
        pl.BlockSpec((k_q, tile // pack), lambda ti: (0, ti)),
    ]
    inputs = [e_q, r_anc]
    if scales is not None:
        in_specs.append(pl.BlockSpec((1, tile), lambda ti: (0, ti)))
        inputs.append(scales[None, :])
    for extra in (noise, None if mask is None else as_kernel_mask(mask)):
        if extra is not None:
            in_specs.append(pl.BlockSpec((b, tile), lambda ti: (0, ti)))
            inputs.append(extra)
    # per-tile lists are (n_tiles, B, k) with the tile axis squeezed out of
    # the block, so every block's minor two dims are the array's own
    out_block = pl.BlockSpec((pl.Squeezed(), b, k), lambda ti: (ti, 0, 0))
    vals, idx = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[out_block, out_block],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, b, k), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles, b, k), jnp.int32),
        ],
        interpret=interpret,
        name="approx_topk_sweep",
    )(*inputs)
    return jnp.swapaxes(vals, 0, 1), jnp.swapaxes(idx, 0, 1)
