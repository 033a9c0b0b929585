"""Decides ``correct``: what the timed path served, compared with the plain
references after the window has closed.

Serving cells, on a sample of the window's requests drawn from the seed:

- ``ce_gap``: widest |served - reference| CE score over every pair the
  chip scored for the request (its 100 anchors and its served top-k), the
  reference a float32 forward of the same weights and tokens;
- ``cur_gap``: widest shortfall of the chip's anchor and rerank picks
  below the reference's k-th best approximate score, in standard
  deviations of the row, from a float64 replay of the CUR solve on the
  chip's own anchors (``references/adacur.py``);
- ``topk_faults``: faults of the final merge and repeated picks (exact, 0);

and over the whole window ``ce_calls_off_plan`` (measured CE pairs minus
the plan's budget times the batch rows searched, exact), ``failed``
(requests due in the window that got no answer or an error) and
``window_compiles``.  The index-build cell compares a sample of its bulk
scores (``ce_gap``).
"""

from __future__ import annotations

import importlib

import numpy as np


def engine_shape(cfg: dict, c) -> dict:
    from cell import family

    eng, dep = cfg["engine"], cfg["deployment"]
    att = family(cfg).attention(cfg)
    return dict(
        k_q=dep["k_q"], n_items=dep["n_items"], rounds=eng["rounds"],
        budget=eng["budget"], k_anchor=eng["k_anchor"],
        k_s=eng["k_anchor"] // eng["rounds"],
        k_r=eng["budget"] - eng["k_anchor"], k_retrieve=eng["k_retrieve"],
        tile=getattr(c.engine_cfg, "fused_tile", None),
        layers=att["layers"], seq_len=att["seq_len"], heads=att["heads"],
        head_dim=att["qk_head_dim"], v_head_dim=att["v_head_dim"],
        causal=att["causal"], pinv_rcond=eng["pinv_rcond"],
    )


def reference(cfg: dict):
    """The configuration's plain reference module, found by name."""
    return importlib.import_module(f"references.{cfg['reference']}")


def ce_reference(c, qids, items, control=False) -> np.ndarray:
    """Reference scores of the (qid, item) pairs."""
    import jax.numpy as jnp

    ref = reference(c.cfg)
    q = c.query_tokens[np.asarray(qids)]
    it = np.asarray(jnp.take(c.item_tokens, jnp.asarray(items), axis=0))
    pairs = ref.pair_tokens(q, it, c.cfg, c.cfg["deployment"]["pair_len"])
    return ref.scores(c.params, pairs, c.cfg, control=control)


def serving_sample(c, win, rng, n: int):
    """The sampled requests' (qid, anchors, anchor scores, served ids,
    served scores), fetched to the host."""
    import jax

    done = [r for r in win.in_window() if r.ok]
    pick = rng.choice(len(done), size=min(n, len(done)), replace=False)
    rows = [done[i] for i in pick]
    out = []
    for r in rows:
        res = win.batches[r.batch].result
        a, s = jax.device_get((res.anchor_idx[r.row], res.anchor_scores[r.row]))
        out.append((r.qid, np.asarray(a), np.asarray(s), np.asarray(r.ids),
                    np.asarray(r.scores)))
    return out


def serving_numbers(c, sample, eng, n_ce: int, control=False) -> dict:
    """ce_gap (over the first ``n_ce`` requests of the sample), cur_gap and
    topk_faults of a sample, or of the control put in the program's place
    (``control=True``)."""
    from references import adacur

    ce_sample = sample[:n_ce]
    qids = np.concatenate([[q] * (len(a) + len(ids)) for q, a, _, ids, _ in ce_sample])
    items = np.concatenate([np.concatenate([a, ids]) for _, a, _, ids, _ in ce_sample])
    chip = np.concatenate([np.concatenate([s, sc]) for _, _, s, _, sc in ce_sample])
    ref = ce_reference(c, qids, items)
    if control:
        chip = ce_reference(c, qids, items, control=True)
    anchors = np.stack([a for _, a, _, _, _ in sample])
    a_scores = np.stack([s for _, _, s, _, _ in sample])
    rerank = np.full((len(sample), eng["k_retrieve"]), -1, np.int64)
    for j, (_, a, _, ids, _) in enumerate(sample):
        extra = ids[~np.isin(ids, a)]
        rerank[j, :len(extra)] = extra
    gaps, repeats = adacur.replay(
        c.r_anc, anchors, a_scores, rerank, eng["k_s"], eng["rounds"],
        eng["k_r"], eng["pinv_rcond"], control=control,
    )
    faults = adacur.topk_violations(
        anchors, a_scores, [s[3] for s in sample], [s[4] for s in sample])
    return {"ce_gap": float(np.max(np.abs(chip - ref))),
            "cur_gap": float(np.max(gaps)),
            "topk_faults": int(np.sum(faults) + np.sum(repeats))}


def build_sample(c, win, rng, n_calls: int, per_call: int):
    """(qids, items, chip scores) of sampled pairs of sampled bulk calls."""
    import jax

    calls = win.calls
    pick = sorted(rng.choice(len(calls), size=min(n_calls, len(calls)), replace=False))
    qs, its, chip = [], [], []
    for i in pick:
        _, _, _, qids, ids, out = calls[i]
        out = np.asarray(jax.device_get(out))
        rq = rng.integers(len(qids), size=per_call)
        ri = rng.integers(len(ids), size=per_call)
        qs.append(qids[rq])
        its.append(ids[ri])
        chip.append(out[rq, ri])
    return np.concatenate(qs), np.concatenate(its), np.concatenate(chip)


def verdict(checks: dict) -> bool:
    """``correct``: every number compared is within its limit."""
    return all(v["value"] <= v["limit"] for v in checks.values())


def run_checks(c, win, eng: dict, compiles: int, rng, control: bool = False):
    """(checks, attempted, failed, control checks) of a finished window.
    The control's checks, only with ``control``, are the same comparison
    with the control in the program's place: the reference in the precision
    below the configuration's, on the same sample."""
    import jax

    limits = c.cfg["limits"]
    checks = {}
    if c.service is None:
        attempted = len(win.calls)
        failed = sum(1 for call in win.calls
                     if not bool(jax.device_get(jax.numpy.isfinite(call[5]).all())))
        qids, items, chip = build_sample(
            c, win, rng, c.traffic["check_calls"], c.traffic["check_pairs_per_call"])
        c.free_program()
        ref = ce_reference(c, qids, items)
        nums = {"ce_gap": float(np.max(np.abs(chip - ref)))}
        ctl = ({"ce_gap": float(np.max(np.abs(ce_reference(c, qids, items, control=True) - ref)))}
               if control else None)
    else:
        reqs = win.in_window()
        attempted = len(reqs)
        failed = sum(1 for r in reqs if not r.ok)
        searched = sum(b.bucket for b in win.batches if b.t1 <= win.t_end)
        off_plan = abs(win.ce_pairs - searched * eng["budget"])
        sample = serving_sample(c, win, rng, c.traffic["check_cur_requests"])
        c.free_program()
        nums = serving_numbers(c, sample, eng, c.traffic["check_requests"])
        ctl = (serving_numbers(c, sample, eng, c.traffic["check_requests"], control=True)
               if control else None)
        checks["ce_calls_off_plan"] = {"value": int(off_plan), "limit": 0}
    checks["failed"] = {"value": int(failed), "limit": 0}
    checks["window_compiles"] = {"value": int(compiles), "limit": 0}
    limit = dict(limits, topk_faults=0)

    def with_numbers(numbers):
        return dict({k: {"value": v, "limit": limit[k]} for k, v in numbers.items()},
                    **checks)

    return (with_numbers(nums), attempted, failed,
            with_numbers(ctl) if ctl is not None else None)
