"""Plain ADACUR replay, teacher-forced on the anchors the chip chose.

ADACUR (split budget, TopK strategy) picks ``k_s`` fresh anchors a round:
the items with the highest approximate scores S = e_q @ R_anc among those
not yet picked, where e_q = c @ pinv(R_anc[:, anchors]) from the exact CE
scores c of the anchors so far.  After the last round it CE-scores the
``k_r`` best remaining items and returns the top ``k`` of everything
scored.

The replay takes the chip's anchors and their CE scores as the history of
each round, solves e_q in float64 on the host, and scores every item in
float32 at HIGHEST precision on the device.  It reports, per request, how
far the chip's picks fall below the reference's ``k_s``-th best remaining
score (the widest gap, in units of the row's standard deviation of S), and
the same for the served items that came from the rerank.

``control=True`` makes the picks itself in the precision below the
program's float32-at-HIGHEST CUR: e_q, its pinv and S at ``high`` (three
bfloat16 passes), and reads the gap of its own picks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 16


def _estimates(cols, c, k_s, rounds, rcond, control):
    """(n, rounds, k_q) e_q after rounds 1..rounds, float64 (or the
    control's float32 at ``high``)."""
    n, k_q, _ = cols.shape
    out = np.zeros((n, rounds, k_q))
    for r in range(1, rounds + 1):
        m = r * k_s
        if control:
            with jax.default_matmul_precision("high"):
                a = jnp.asarray(cols[:, :, :m], jnp.float32)
                p = jnp.linalg.pinv(a, rtol=rcond)
                e = jnp.einsum("nk,nkq->nq", jnp.asarray(c[:, :m], jnp.float32), p)
            out[:, r - 1] = np.asarray(e, np.float64)
        else:
            for j in range(n):
                p = np.linalg.pinv(cols[j, :, :m], rcond=rcond)
                out[j, r - 1] = c[j, :m] @ p
    return out


@functools.partial(jax.jit, static_argnums=4)
def _round_stats(e, r_anc, excluded, picks, kth):
    """S rows = e @ R_anc with ``excluded`` items suppressed; returns the
    k-th best remaining score, S at ``picks``, and the row's std."""
    s = jnp.matmul(e, r_anc, precision=jax.lax.Precision.HIGHEST)
    std = jnp.std(s, axis=1)
    rows = jnp.arange(s.shape[0])[:, None]
    at = s[rows, jnp.clip(picks, 0, s.shape[1] - 1)]
    s = s.at[rows, excluded].set(-jnp.inf)
    top_v, top_i = jax.lax.top_k(s, kth)
    return top_v[:, -1], at, std, top_i


def replay(r_anc, anchors, anchor_scores, rerank_picks, k_s, rounds, k_r,
           rcond, control=False):
    """Per-request widest selection gap over rounds 1..rounds-1 and the
    rerank.  ``anchors``/``anchor_scores`` (n, rounds*k_s) are the chip's;
    ``rerank_picks`` (n, m) are the served items that are not anchors
    (padded with -1).  Returns (gaps (n,), repeats (n,)): ``repeats``
    counts picks that were already anchors, which ADACUR never makes."""
    anchors = np.asarray(anchors, np.int64)
    c = np.asarray(anchor_scores, np.float64)
    n = anchors.shape[0]
    cols = np.asarray(jnp.take(r_anc, jnp.asarray(anchors), axis=1), np.float64)
    cols = np.moveaxis(cols, 0, 1)                        # (n, k_q, k_i)
    e = _estimates(cols, c, k_s, rounds, rcond, control=False)
    e_ctl = _estimates(cols, c, k_s, rounds, rcond, control=True) if control else None
    gaps = np.zeros(n)
    repeats = np.zeros(n, np.int64)
    for r in range(1, rounds + 1):
        final = r == rounds
        m = r * k_s
        kth = k_r if final else k_s
        picks = (np.asarray(rerank_picks, np.int64) if final
                 else anchors[:, m:m + k_s])
        for lo in range(0, n, ROW_BLOCK):
            sl = slice(lo, lo + ROW_BLOCK)
            excluded = jnp.asarray(anchors[sl, :m])
            if control:
                with jax.default_matmul_precision("high"):
                    s_ctl = jnp.matmul(jnp.asarray(e_ctl[sl, r - 1], jnp.float32), r_anc)
                rows = jnp.arange(s_ctl.shape[0])[:, None]
                s_ctl = s_ctl.at[rows, excluded].set(-jnp.inf)
                picks_sl = np.asarray(jax.lax.top_k(s_ctl, kth)[1], np.int64)
                del s_ctl
            else:
                picks_sl = picks[sl]
            kv, at, std, _ = _round_stats(
                jnp.asarray(e[sl, r - 1], jnp.float32), r_anc, excluded,
                jnp.asarray(picks_sl), kth,
            )
            kv, at, std = (np.asarray(x, np.float64) for x in (kv, at, std))
            valid = picks_sl >= 0
            gap = np.where(valid, np.maximum(kv[:, None] - at, 0.0), 0.0)
            gaps[sl] = np.maximum(gaps[sl], gap.max(axis=1) / std)
            prev = anchors[sl, :m]
            repeats[sl] += np.array([
                np.isin(p[v], a).sum() for p, v, a in zip(picks_sl, valid, prev)
            ])
    return gaps, repeats


def topk_violations(anchors, anchor_scores, served_ids, served_scores) -> np.ndarray:
    """Per-request count of faults in the final merge, which ADACUR does
    exactly: the served list must be sorted, hold distinct items, give each
    anchor its own CE score, and leave out no anchor that scored higher
    than the last served item."""
    out = []
    for a, c, ids, s in zip(anchors, anchor_scores, served_ids, served_scores):
        bad = int(np.sum(np.diff(s) > 0)) + (len(ids) - len(set(ids.tolist())))
        pos = {int(x): float(y) for x, y in zip(a, c)}
        bad += sum(1 for i, v in zip(ids, s) if int(i) in pos and pos[int(i)] != float(v))
        served = set(ids.tolist())
        bad += sum(1 for i, v in pos.items() if i not in served and v > float(s[-1]))
        out.append(bad)
    return np.asarray(out)
