"""Quickstart: ADACUR vs ANNCUR on a synthetic cross-encoder domain.

    PYTHONPATH=src python examples/quickstart.py [--payload-dtype int8] \
        [--first-stage {de,bm25}]

Builds a 10K-item domain, wraps the offline scores in the one
:class:`AnchorIndex` artifact (build/save/load/shard/mutate lives there),
then runs budget-matched retrieval with the paper's method and the
fixed-anchor baseline — both as configurations of the unified Retriever
engine — and prints Top-k-Recall.  ``--payload-dtype int8`` (or ``int4`` /
``fp8``) demonstrates the quantized payload end to end: the index stores
per-tile codes + fp32 scales (int8/fp8 ~4x smaller, packed int4 ~8x) and
the fused kernel dequantizes tile-by-tile in registers.
``--round-kernel persistent`` fuses each round's estimate, Gumbel top-k
and early-exit monitor into one payload sweep."""

import argparse

import jax
import jax.numpy as jnp

from repro.configs.base import AdaCURConfig
from repro.core import retrieval
from repro.core.engine import AdaCURRetriever, ANNCURRetriever
from repro.core.index import AnchorIndex
from repro.core.scorer import SyntheticScorer
from repro.data.synthetic import make_synthetic_ce
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--payload-dtype",
                    choices=("float32", "bfloat16", "int8", "int4", "fp8"),
                    default="float32",
                    help="storage/streaming dtype of the R_anc payload "
                         "(int8/fp8 ~4x smaller, packed int4 ~8x)")
    ap.add_argument("--round-kernel", choices=("staged", "persistent"),
                    default="staged",
                    help="persistent: one fused payload sweep per round "
                         "(bit-identical rankings to staged)")
    ap.add_argument("--first-stage", choices=("none", "de", "bm25"),
                    default="none",
                    help="add a multi-stage hybrid row: first-stage "
                         "shortlist -> candidate-restricted ADACUR")
    args = ap.parse_args()

    print("building synthetic CE domain: 10,000 items, 500 anchor queries...")
    ce = make_synthetic_ce(jax.random.PRNGKey(0), n_queries=600, n_items=10000)
    m = ce.full_matrix(jnp.arange(600))
    test_q, exact = jnp.arange(500, 600), m[500:]
    # every provider (synthetic / tabulated / real CE) is a Scorer; see
    # examples/real_ce_search.py for the transformer-CE stack
    score_fn = SyntheticScorer(ce)

    # the offline artifact: anchor-query scores + ids; at scale this is
    # AnchorIndex.build(...) (resumable) + .save()/.load() + .shard(mesh)
    index = AnchorIndex.from_r_anc(m[:500], anchor_query_ids=jnp.arange(500))
    fp32_bytes = index.payload_nbytes
    if args.payload_dtype != "float32":
        index = index.quantize(args.payload_dtype)
        print(f"payload {args.payload_dtype}: {index.payload_nbytes / 1e6:.1f} MB "
              f"(fp32: {fp32_bytes / 1e6:.1f} MB, "
              f"{index.payload_nbytes / fp32_bytes:.2f}x)")

    budget = 200  # exact CE calls per query at test time
    print(f"\nCE-call budget per query: {budget}  (brute force would need 10,000)\n")

    cfg = AdaCURConfig(k_anchor=100, n_rounds=5, budget_ce=budget,
                       strategy="topk", k_retrieve=100, loop_mode="fori",
                       use_fused_topk=True, payload_dtype=args.payload_dtype,
                       round_kernel=args.round_kernel)
    ret = AdaCURRetriever.from_index(index, score_fn, cfg)
    res = ret.search(test_q, jax.random.PRNGKey(1))
    rep = retrieval.evaluate_result("ADACUR(TopK,5 rounds)", res, exact)

    idx = index.with_anchors(k_anchor=100, key=jax.random.PRNGKey(2))
    ret2 = ANNCURRetriever.from_index(idx, score_fn, budget, 100)
    res2 = ret2.search(test_q)
    rep2 = retrieval.evaluate_result("ANNCUR(random anchors)", res2, exact)

    reports = [rep, rep2]
    if args.first_stage != "none":
        from repro.core.candidates import (
            BM25Candidates, DualEncoderCandidates, HybridRetriever,
        )

        if args.first_stage == "de":
            gen = DualEncoderCandidates(ce.q_emb, ce.i_emb)
        else:
            from repro.data.synthetic import lexical_signatures

            gen = BM25Candidates(lexical_signatures(ce.i_emb, seed=3),
                                 lexical_signatures(ce.q_emb, seed=3))
        hyb = HybridRetriever(score_fn=score_fn, generator=gen, cfg=cfg,
                              index=index, shortlist_k=4 * budget,
                              mode="mask")
        res3 = hyb.search(test_q, jax.random.PRNGKey(3))
        reports.append(retrieval.evaluate_result(
            f"HYBRID({args.first_stage}->ADACUR)", res3, exact))

    print(f"{'method':<28} {'R@1':>6} {'R@10':>6} {'R@100':>6}")
    for rep_i in reports:
        print(f"{rep_i.method:<28} {rep_i.recall[1]:>6.3f} "
              f"{rep_i.recall[10]:>6.3f} {rep_i.recall[100]:>6.3f}")
    assert rep.recall[100] > rep2.recall[100], "ADACUR should beat ANNCUR@100"
    print("\nADACUR > ANNCUR at equal budget — the paper's headline result.")


if __name__ == "__main__":
    main()
