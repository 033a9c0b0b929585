"""End-to-end k-NN search with a REAL transformer cross-encoder.

    PYTHONPATH=src python examples/real_ce_search.py

The quickstart drives the engine with a closed-form synthetic scorer; this
example runs the full production stack instead:

1. a ZESHEL-like token corpus + a tiny transformer CE (the paper's
   f_theta) — scoring means tokenize, micro-batch, flash-attention;
2. the offline AnchorIndex built by bulk-scoring anchor queries with that
   same CE;
3. an online engine search through :class:`CrossEncoderScorer` (length
   buckets + static micro-batches: request shapes never retrace) wrapped
   in :class:`CachingScorer` — repeat queries re-issue zero CE calls;
4. measured accounting: CE calls observed at runtime, not assumed.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AdaCURConfig, replace
from repro.configs.registry import CE_TINY
from repro.core import engine
from repro.core.index import AnchorIndex
from repro.core.scorer import CachingScorer, CrossEncoderScorer
from repro.data.synthetic import make_zeshel_like
from repro.launch.compile_cache import enable_compile_cache
from repro.models import cross_encoder


def main():
    enable_compile_cache()
    n_items, n_anchor_q, n_test_q = 300, 60, 16
    print(f"corpus: {n_items} entity descriptions, {n_anchor_q} anchor queries")
    ds = make_zeshel_like(0, n_items=n_items, n_queries=n_anchor_q + n_test_q,
                          item_len=16, query_len=12)
    lm_cfg = replace(
        CE_TINY, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=ds.vocab_size, dtype="float32", remat=False,
    )
    params, _ = cross_encoder.init_cross_encoder(jax.random.PRNGKey(0), lm_cfg)
    scorer = CachingScorer(CrossEncoderScorer(
        params, lm_cfg, ds.pair_tokens, micro_batch=64, flash_block=(32, 32),
    ))

    print("building AnchorIndex by bulk-scoring anchor queries with the CE...")
    t0 = time.perf_counter()

    def bulk(q_ids, item_ids):
        q = np.asarray(q_ids)
        return jnp.asarray(
            scorer.inner._host(q, np.tile(np.asarray(item_ids), (len(q), 1)))
        )

    index = AnchorIndex.build(
        bulk, jnp.arange(n_anchor_q), jnp.arange(n_items), block_rows=16
    )
    print(f"  {n_anchor_q}x{n_items} CE scores in {time.perf_counter()-t0:.1f}s "
          f"({scorer.inner.n_traces} compiled shapes)")
    scorer.reset_stats()

    cfg = AdaCURConfig(k_anchor=16, n_rounds=4, budget_ce=32, k_retrieve=10,
                       loop_mode="fori")
    retriever = engine.AdaCURRetriever.from_index(index, scorer, cfg)
    test_q = jnp.arange(n_anchor_q, n_anchor_q + n_test_q)

    t0 = time.perf_counter()
    res = jax.block_until_ready(retriever.search(test_q, jax.random.PRNGKey(1)))
    print(f"\ncold search of {n_test_q} queries: {time.perf_counter()-t0:.1f}s, "
          f"{scorer.stats.ce_calls} measured CE calls "
          f"(= plan {engine.ce_call_plan(cfg) * n_test_q})")

    cold_calls = scorer.stats.ce_calls
    t0 = time.perf_counter()
    res2 = jax.block_until_ready(retriever.search(test_q, jax.random.PRNGKey(1)))
    print(f"repeat search: {time.perf_counter()-t0:.1f}s, "
          f"{scorer.stats.ce_calls - cold_calls} new CE calls "
          f"({scorer.stats.cache_hits} cache hits)")
    assert np.array_equal(np.asarray(res.topk_idx), np.asarray(res2.topk_idx))

    # the untrained CE defines its own ground truth: how often does the
    # budgeted search retrieve the CE's exact argmax?
    exact = np.asarray(bulk(test_q, jnp.arange(n_items)))
    ce_top1 = exact.argmax(axis=1)
    hit = (np.asarray(res.topk_idx) == ce_top1[:, None]).any(1).mean()
    print(f"\nCE-argmax recall@{cfg.k_retrieve}: {hit:.2f} "
          f"at {cfg.budget_ce}/{n_items} CE calls per query")


if __name__ == "__main__":
    main()
