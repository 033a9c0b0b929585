"""CUR decomposition primitives for ANNCUR/ADACUR.

The paper (Alg. 2) approximates all-item scores for a test query as

    S_hat = C_test @ pinv(R_anc[:, I_anc]) @ R_anc

with ``R_anc ∈ R^{k_q x N}`` the offline anchor-query/all-item score matrix,
``I_anc`` the anchor-item column subset and ``C_test ∈ R^{k_i}`` the exact CE
scores of the test query against the anchor items.

This module provides:

- ``approx_scores``       — the faithful Alg. 2 (batched over queries);
- ``query_embedding``     — the beyond-paper ``e_q = C_test @ U`` factoring
  (one rank-k_q GEMM against R_anc instead of two large GEMMs per round);
- ``pinv`` / ``block_pinv_extend`` — full and *incremental* Moore-Penrose
  pseudo-inverse.  The paper recomputes the pinv from scratch every round,
  O(k_q·k_i²); the incremental bordering update is O(k_q·k_i·k_s) per round
  and is validated against the full pinv in tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# The CUR products and solves are fp32 and small; on a TPU the default
# matmul precision would round their operands to bf16 (one MXU pass) and
# drift the anchor selection.  Each call site asks for full fp32 instead —
# never a process-wide flag, which would also slow the bf16 CE forward.
HIGHEST = jax.lax.Precision.HIGHEST


def pinv(a: jax.Array, rcond: float = 1e-6) -> jax.Array:
    """Moore-Penrose pseudo-inverse (SVD-based, batched over leading dims)."""
    return jnp.linalg.pinv(a, rtol=rcond)


def gather_anchor_columns(
    r_anc: jax.Array, anchor_idx: jax.Array, via_onehot: bool = False
) -> jax.Array:
    """R_anc[:, I_anc] for a batch of per-query anchor sets.

    Args:
      r_anc: (k_q, N) anchor-query/item scores.
      anchor_idx: (B, k) int32 anchor item ids.
      via_onehot: express the gather as a one-hot matmul.  Under SPMD with
        R_anc column-sharded, a plain gather makes XLA REPLICATE the 2 GB
        table per device; the matmul contracts the sharded axis shard-local
        and psums the (B, k_q, k) result instead.

    Returns:
      (B, k_q, k) per-query anchor column subsets.
    """
    if via_onehot:
        n = r_anc.shape[1]
        onehot = (
            anchor_idx[:, None, :] == jnp.arange(n)[None, :, None]
        ).astype(r_anc.dtype)                                # (B, N, k)
        return jnp.einsum("qn,bnk->bqk", r_anc, onehot)
    # take along the item axis; result (B, k_q, k)
    return jnp.swapaxes(r_anc.T[anchor_idx], 1, 2)


def query_embedding(
    r_anc_cols: jax.Array, c_test: jax.Array, rcond: float = 1e-6
) -> jax.Array:
    """e_q = C_test @ pinv(R_anc[:, I_anc])  — (B, k_q).

    ``S_hat = e_q @ R_anc`` then reconstructs Alg. 2 line 7 with a single
    (B,k_q)x(k_q,N) GEMM.
    """
    u = pinv(r_anc_cols, rcond)  # (B, k, k_q)
    return jnp.einsum("bk,bkq->bq", c_test, u, precision=HIGHEST)


def approx_scores(
    r_anc: jax.Array,
    c_test: jax.Array,
    anchor_idx: jax.Array,
    rcond: float = 1e-6,
) -> jax.Array:
    """Faithful Algorithm 2: approximate scores of ALL items for each query.

    Args:
      r_anc: (k_q, N).
      c_test: (B, k) exact CE scores of each query against its anchors.
      anchor_idx: (B, k) anchor item ids.

    Returns:
      (B, N) approximate scores.
    """
    cols = gather_anchor_columns(r_anc, anchor_idx)      # (B, k_q, k)
    e_q = query_embedding(cols, c_test, rcond)           # (B, k_q)
    return e_q @ r_anc                                   # (B, N)


# ---------------------------------------------------------------------------
# Incremental (bordered) pseudo-inverse  — beyond-paper optimization #1
# ---------------------------------------------------------------------------


def _bordered_blocks(
    a: jax.Array, p: jax.Array, b: jax.Array, ridge: float
) -> tuple:
    """Shared core of the bordering update: (D, K) for M = [A | B].

    D = P @ B, C = B - A @ D, and K is pinv(C) blended with the Greville
    fallback ``K = (I + DᵀD)⁻¹ Dᵀ P`` per-column when the residual C is
    (numerically) rank-deficient — new columns inside span(A)."""
    d = jnp.matmul(p, b, precision=HIGHEST)        # (n, s)
    c = b - jnp.matmul(a, d, precision=HIGHEST)    # (m, s)
    # full-column-rank branch: K1 = (CᵀC + ridge I)⁻¹ Cᵀ
    gram = jnp.matmul(c.T, c, precision=HIGHEST)
    s = gram.shape[-1]
    eye = jnp.eye(s, dtype=gram.dtype)
    scale = jnp.trace(gram) / s + 1.0
    k1 = jnp.linalg.solve(gram + ridge * scale * eye, c.T)
    # rank-deficient branch: K2 = (I + DᵀD)⁻¹ Dᵀ P
    k2 = jnp.linalg.solve(
        eye + jnp.matmul(d.T, d, precision=HIGHEST),
        jnp.matmul(d.T, p, precision=HIGHEST),
    )
    # DUPLICATE new columns (coarse payload grids — int4 especially — make
    # exact column collisions likely) leave gram singular with a large
    # trace, and the ridge underflows against the fp32 rounding of the
    # diagonal add, so LU turns the whole solve non-finite.  Each c column
    # has a healthy norm there, so the w-blend below would keep the NaNs;
    # fall back to the (always finite) Greville branch instead.  Finite
    # solves pass through untouched, so healthy updates keep their exact
    # bits.
    k1 = jnp.where(jnp.isfinite(k1), k1, k2)
    # per-column blend: column j uses branch 1 iff ‖c_j‖² is non-negligible
    # relative to ‖b_j‖².
    c_norm = jnp.sum(c * c, axis=0)
    b_norm = jnp.sum(b * b, axis=0) + 1e-30
    w = (c_norm > 1e-10 * b_norm).astype(k1.dtype)[:, None]
    return d, w * k1 + (1.0 - w) * k2


def block_pinv_extend(
    a: jax.Array,
    p: jax.Array,
    b: jax.Array,
    ridge: float = 1e-8,
) -> jax.Array:
    """Extend ``P = pinv(A)`` to ``pinv([A | B])`` via the bordering identity.

    For M = [A B] with A (m,n), P = A⁺ (n,m), B (m,s):

        D = P @ B                     (n,s)
        C = B - A @ D                 (m,s)  residual of B off col-space(A)
        K = pinv(C)                   (s,m)  [full-col-rank fast path below]
        M⁺ = [ P - D @ K ]
             [     K     ]

    When C is (numerically) rank-deficient — the new columns lie in the span
    of the old — the Greville fallback ``K = (I + DᵀD)⁻¹ Dᵀ P`` applies; we
    blend the two branches per-column on a residual-magnitude test so the
    update stays jit-friendly (no data-dependent control flow).

    Anchor matrices here are tall (k_q anchor queries ≫ k_i anchor items), so
    the full-column-rank branch is the hot path; the ridge keeps the small
    (s,s) solves well-posed.
    """
    d, k = _bordered_blocks(a, p, b, ridge)
    top = p - jnp.matmul(d, k, precision=HIGHEST)
    return jnp.concatenate([top, k], axis=0)


def block_pinv_extend_static(
    a_full: jax.Array,
    p_full: jax.Array,
    b: jax.Array,
    start,
    ridge: float = 1e-8,
) -> jax.Array:
    """Shape-invariant bordering update over *preallocated* buffers.

    ``a_full`` (m, K) holds the anchor columns filled so far in columns
    [0, start) with exact zeros beyond; ``p_full`` (K, m) holds their pinv in
    rows [0, start) with exact zeros beyond.  The new block ``b`` (m, s) is
    incorporated by writing its K-rows into [start, start+s) — the same math
    as :func:`block_pinv_extend` (the zero padding contributes exact zeros to
    every contraction), but with static shapes so the multi-round engine's
    loop body is trace-invariant and ``start`` may be a traced index.
    """
    d, k = _bordered_blocks(a_full, p_full, b, ridge)
    # rows >= start stay exactly zero (p, d zero)
    top = p_full - jnp.matmul(d, k, precision=HIGHEST)
    return jax.lax.dynamic_update_slice(top, k, (start, 0))


def incremental_pinv_init(a0: jax.Array, rcond: float = 1e-6) -> jax.Array:
    """pinv of the first anchor block (computed once, full SVD)."""
    return pinv(a0, rcond)


def cur_reconstruction(
    r_anc: jax.Array, anchor_idx: jax.Array, rows: jax.Array, rcond: float = 1e-6
) -> jax.Array:
    """Full CUR reconstruction M̃ = C U R of arbitrary score rows.

    Used by the ANNCUR offline index and by approximation-error benchmarks:
    ``rows`` is (B, k) exact scores of B queries on the anchor columns, the
    return is the (B, N) approximation of their full score rows.
    """
    return approx_scores(r_anc, rows, anchor_idx, rcond)
