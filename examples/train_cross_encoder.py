"""End-to-end driver: TRAIN a transformer cross-encoder on ZESHEL-like
entity-linking data for a few hundred steps, index the corpus, and serve
k-NN retrieval with ADACUR — the paper's full pipeline with a real
(non-synthetic) scorer.

    PYTHONPATH=src python examples/train_cross_encoder.py [--steps 300]

Stages:
  1. generate a ZESHEL-like domain (entity descriptions + noisy mentions);
  2. train the ce-tiny backbone with in-batch ranking loss (checkpointed,
     watchdog-monitored — the production train loop in miniature);
  3. offline-index R_anc with the TRAINED CE (resumable block builder);
  4. budget-matched retrieval: ADACUR vs ANNCUR vs TF-IDF rerank;
  5. report Top-k-Recall of the CE's own top-k (the paper's metric) and
     gold-entity accuracy.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import registry
from repro.configs.base import AdaCURConfig, replace
from repro.core import retrieval
from repro.core.engine import AdaCURRetriever, ANNCURRetriever
from repro.core.index import AnchorIndex
from repro.data.synthetic import make_zeshel_like
from repro.distributed.fault_tolerance import StragglerWatchdog
from repro.launch.compile_cache import enable_compile_cache
from repro.models import cross_encoder
from repro.training import optimizer


def tfidf_retriever(ds):
    """TF-IDF baseline (paper Appendix B): cosine over tf-idf vectors."""
    n_items, vocab = ds.item_tokens.shape[0], ds.vocab_size
    tf = np.zeros((n_items, vocab), np.float32)
    for i, row in enumerate(ds.item_tokens):
        np.add.at(tf[i], row, 1.0)
    df = (tf > 0).sum(0) + 1
    idf = np.log(n_items / df)
    item_vec = tf * idf
    item_vec /= np.linalg.norm(item_vec, axis=1, keepdims=True) + 1e-9

    def retrieve(query_tokens):
        q = np.zeros((query_tokens.shape[0], vocab), np.float32)
        for i, row in enumerate(query_tokens):
            np.add.at(q[i], row, 1.0)
        q *= idf
        q /= np.linalg.norm(q, axis=1, keepdims=True) + 1e-9
        return np.argsort(-(q @ item_vec.T), axis=1)

    return retrieve


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--n-items", type=int, default=800)
    ap.add_argument("--n-queries", type=int, default=220)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--negatives", type=int, default=3)
    ap.add_argument("--budget", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ce_ckpt")
    args = ap.parse_args()

    # -- 1. data --------------------------------------------------------------
    ds = make_zeshel_like(0, n_items=args.n_items, n_queries=args.n_queries)
    n_train_q = args.n_queries - 60
    print(f"domain: {args.n_items} entities, {args.n_queries} mentions "
          f"({n_train_q} train / 60 test)")

    # -- 2. train the CE -------------------------------------------------------
    cfg = replace(registry.CE_TINY, n_layers=2, d_model=128, n_heads=4,
                  n_kv_heads=2, head_dim=32, d_ff=256, vocab_size=ds.vocab_size,
                  dtype="float32", remat=False)
    params, _ = cross_encoder.init_cross_encoder(jax.random.PRNGKey(0), cfg)
    opt_cfg = optimizer.AdamWConfig(lr=1e-3, total_steps=args.steps, warmup_steps=20)
    opt_state = optimizer.init_adamw(params)

    @jax.jit
    def train_step(params, opt_state, pair_tokens):
        loss, grads = jax.value_and_grad(cross_encoder.ranking_loss)(
            params, pair_tokens, cfg
        )
        params, opt_state, metrics = optimizer.adamw_update(
            opt_cfg, params, grads, opt_state
        )
        return params, opt_state, loss, metrics

    rng = np.random.default_rng(0)
    mgr = CheckpointManager(args.ckpt_dir, save_every=100, keep=2)
    watchdog = StragglerWatchdog()
    t0 = time.time()
    for step in range(args.steps):
        qs = rng.integers(0, n_train_q, size=args.batch)
        golds = ds.gold[qs][:, None]
        negs = rng.integers(0, args.n_items, size=(args.batch, args.negatives))
        items = np.concatenate([golds, negs], axis=1)     # item 0 = gold
        pairs = jnp.asarray(ds.pair_tokens(qs, items))
        t_step = time.monotonic()
        params, opt_state, loss, _ = train_step(params, opt_state, pairs)
        watchdog.observe(step, time.monotonic() - t_step)
        mgr.maybe_save(step + 1, {"params": params})
        if step % 50 == 0 or step == args.steps - 1:
            print(f"  step {step:4d}  rank-loss {float(loss):.4f}")
    print(f"trained {args.steps} steps in {time.time() - t0:.0f}s")

    # -- 3. offline R_anc index with the trained CE ---------------------------
    item_ids_all = np.arange(args.n_items)

    @jax.jit
    def bulk_rows(q_ids, pair_toks):
        return cross_encoder.score_pairs(params, pair_toks, cfg)

    def bulk_score(q_ids, item_ids):
        q_ids = np.asarray(q_ids)
        out = []
        chunk = 128
        for lo in range(0, len(item_ids), chunk):
            it = np.asarray(item_ids[lo : lo + chunk])
            toks = jnp.asarray(ds.pair_tokens(q_ids, np.tile(it, (len(q_ids), 1))))
            out.append(bulk_rows(q_ids, toks))
        return jnp.concatenate(out, axis=1)

    print("building the AnchorIndex with the trained CE (resumable block builder)...")
    t0 = time.time()
    index = AnchorIndex.build(
        bulk_score, jnp.arange(n_train_q), jnp.arange(args.n_items),
        block_rows=32,
    )
    print(f"AnchorIndex (k_q={index.k_q}, |I|={index.n_items}) "
          f"in {time.time() - t0:.0f}s")

    test_q = np.arange(n_train_q, args.n_queries)
    exact = np.asarray(bulk_score(test_q, item_ids_all))
    exact = jnp.asarray(exact)

    def score_fn(q_ids, item_idx):
        toks = jnp.asarray(ds.pair_tokens(np.asarray(q_ids), np.asarray(item_idx)))
        return cross_encoder.score_pairs(params, toks, cfg)

    # -- 4. budget-matched retrieval -------------------------------------------
    budget = args.budget
    acfg = AdaCURConfig(k_anchor=budget // 2, n_rounds=4, budget_ce=budget,
                        strategy="topk", k_retrieve=64)
    # jit=False: the tokenizing score_fn is numpy-backed (non-traceable)
    res_a = AdaCURRetriever.from_index(index, score_fn, acfg, jit=False).search(
        test_q, jax.random.PRNGKey(1)
    )
    rep_a = retrieval.evaluate_result("ADACUR", res_a, exact, ks=(1, 10, 64))

    idx = index.with_anchors(k_anchor=budget // 2, key=jax.random.PRNGKey(2))
    res_n = ANNCURRetriever.from_index(idx, score_fn, budget, 64,
                                       jit=False).search(test_q)
    rep_n = retrieval.evaluate_result("ANNCUR", res_n, exact, ks=(1, 10, 64))

    tfidf = tfidf_retriever(ds)
    cand = jnp.asarray(tfidf(ds.query_tokens[test_q]))
    res_t = retrieval.rerank_baseline(score_fn, cand, test_q, budget, 64)
    rep_t = retrieval.evaluate_result("TF-IDF+rerank", res_t, exact, ks=(1, 10, 64))

    # -- 5. report --------------------------------------------------------------
    print(f"\nCE-call budget {budget}/query (brute force = {args.n_items}):")
    print(f"{'method':<16} {'R@1':>6} {'R@10':>6} {'R@64':>6}  gold-acc@1")
    gold = ds.gold[test_q]
    for rep, res in ((rep_a, res_a), (rep_n, res_n), (rep_t, res_t)):
        acc = float((np.asarray(res.topk_idx[:, 0]) == gold).mean())
        print(f"{rep.method:<16} {rep.recall[1]:>6.3f} {rep.recall[10]:>6.3f} "
              f"{rep.recall[64]:>6.3f}  {acc:.3f}")


if __name__ == "__main__":
    main()
