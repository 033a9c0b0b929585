"""Integration: ADACUR end-to-end with REAL scorers (the trained-CE path
and the recsys joint scorers), plus the fused kernel consistency with the
engine's own computation and the serving loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import AdaCURConfig, replace
from repro.core import adacur, cur, index as index_lib, retrieval
from repro.data.synthetic import make_zeshel_like
from repro.kernels.approx_topk.ops import approx_topk_op
from repro.launch.serve import AdaCURService, RetrievalRequest
from repro.models import cross_encoder
from repro.models.recsys import bst


@pytest.fixture(scope="module")
def ce_domain():
    """Tiny untrained transformer CE over a ZESHEL-like corpus."""
    ds = make_zeshel_like(0, n_items=200, n_queries=50, item_len=12, query_len=8)
    cfg = replace(
        registry.CE_TINY, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab_size=ds.vocab_size, dtype="float32",
        remat=False,
    )
    params, _ = cross_encoder.init_cross_encoder(jax.random.PRNGKey(0), cfg)

    def score_fn(q_ids, item_idx):
        toks = jnp.asarray(ds.pair_tokens(np.asarray(q_ids), np.asarray(item_idx)))
        return cross_encoder.score_pairs(params, toks, cfg)

    def bulk(q_ids, item_ids):
        toks = jnp.asarray(
            ds.pair_tokens(np.asarray(q_ids), np.tile(np.asarray(item_ids), (len(q_ids), 1)))
        )
        return cross_encoder.score_pairs(params, toks, cfg)

    return ds, score_fn, bulk


class TestTransformerCEPipeline:
    def test_index_then_search(self, ce_domain, tmp_path):
        ds, score_fn, bulk = ce_domain
        r_anc = index_lib.build_r_anc(
            bulk, jnp.arange(30), jnp.arange(200), block_rows=16,
            checkpoint_dir=str(tmp_path),
        )
        assert r_anc.shape == (30, 200)
        # resume path: second call loads from the manifest (no rescoring)
        r_anc2 = index_lib.build_r_anc(
            bulk, jnp.arange(30), jnp.arange(200), block_rows=16,
            checkpoint_dir=str(tmp_path),
        )
        np.testing.assert_allclose(np.asarray(r_anc), np.asarray(r_anc2), rtol=1e-6)

        test_q = np.arange(30, 40)
        exact = bulk(test_q, np.arange(200))
        cfg = AdaCURConfig(k_anchor=20, n_rounds=4, budget_ce=40, k_retrieve=20)
        res = adacur.adacur_search(score_fn, r_anc, test_q, cfg, jax.random.PRNGKey(1))
        rep = retrieval.evaluate_result("adacur-ce", res, exact, ks=(1, 10))
        assert rep.recall[1] > 0.5  # finds the CE's own argmax most of the time

    def test_anchor_scores_match_direct_ce(self, ce_domain):
        ds, score_fn, bulk = ce_domain
        r_anc = bulk(np.arange(20), np.arange(200))
        test_q = np.arange(20, 26)
        cfg = AdaCURConfig(k_anchor=12, n_rounds=3, budget_ce=24, k_retrieve=10)
        res = adacur.adacur_search(score_fn, r_anc, test_q, cfg, jax.random.PRNGKey(0))
        direct = score_fn(test_q, res.anchor_idx)
        np.testing.assert_allclose(
            np.asarray(res.anchor_scores), np.asarray(direct), rtol=1e-4, atol=1e-4
        )


class TestFusedKernelConsistency:
    def test_kernel_matches_engine_round(self, small_domain):
        """The fused approx_topk kernel reproduces the engine's round-2
        candidate selection exactly (same e_q, same masking)."""
        r_anc = small_domain["r_anc"]
        exact = small_domain["exact"]
        anchor = jnp.tile(jnp.arange(0, 2000, 50)[None, :], (4, 1))  # 40 anchors
        c_test = jnp.take_along_axis(exact[:4], anchor, axis=1)
        cols = cur.gather_anchor_columns(r_anc, anchor)
        e_q = cur.query_embedding(cols, c_test, rcond=1e-4)

        # engine path: full scores -> mask -> top-k
        s_hat = e_q @ r_anc
        rows = jnp.arange(4)[:, None]
        sel = jnp.zeros((4, 2000), bool).at[rows, anchor].set(True)
        ref_v, ref_i = jax.lax.top_k(jnp.where(sel, -1e30, s_hat), 16)

        v, i = approx_topk_op(e_q, r_anc, 16, tile=256, interpret=True,
                              mask=sel)
        np.testing.assert_allclose(np.asarray(v), np.asarray(ref_v), rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))


class TestRecsysADACUR:
    def test_bst_joint_scorer_with_adacur(self):
        """The paper's technique over the BST cross-encoder-class scorer."""
        cfg = registry.smoke_config("bst")
        params, _ = bst.init_bst(jax.random.PRNGKey(0), cfg)
        n_items = 500
        hist = jax.random.randint(jax.random.PRNGKey(1), (6, cfg.seq_len), 0, n_items)

        def score_fn(h, idx):
            return bst.score_candidates(params, h, idx, cfg)

        # offline: 40 anchor "queries" (user histories) x all items
        anchor_hists = jax.random.randint(
            jax.random.PRNGKey(2), (40, cfg.seq_len), 0, n_items
        )
        all_items = jnp.tile(jnp.arange(n_items)[None], (40, 1))
        r_anc = bst.score_candidates(params, anchor_hists, all_items, cfg)
        exact = bst.score_candidates(
            params, hist, jnp.tile(jnp.arange(n_items)[None], (6, 1)), cfg
        )
        acfg = AdaCURConfig(k_anchor=24, n_rounds=4, budget_ce=60, k_retrieve=50)
        res = adacur.adacur_search(score_fn, r_anc, hist, acfg, jax.random.PRNGKey(3))
        rep = retrieval.evaluate_result("bst-adacur", res, exact, ks=(1, 10))
        # with an untrained scorer, structure is weak; sanity: valid results
        assert res.topk_idx.shape == (6, 50)
        assert rep.recall[10] >= 0.0
        ref = bst.score_candidates(params, hist, res.topk_idx, cfg)
        np.testing.assert_allclose(
            np.asarray(res.topk_scores), np.asarray(ref), rtol=1e-4, atol=1e-4
        )


class TestServing:
    def test_straggler_flushed_by_poll_after_deadline(self, small_domain):
        """Regression: a lone queued request past max_wait_s used to sit
        until ANOTHER request arrived; poll() must serve it."""
        import time

        cfg = AdaCURConfig(k_anchor=20, n_rounds=4, budget_ce=40, k_retrieve=20)
        svc = AdaCURService(
            small_domain["ce"].score_fn(), small_domain["r_anc"], cfg,
            max_batch=4, max_wait_s=0.02,
        )
        assert svc.submit(RetrievalRequest(query_id=205)) is None
        assert svc.poll() == []          # deadline not reached yet
        time.sleep(0.03)
        out = svc.poll()                 # no second request ever arrives
        assert len(out) == 1 and out[0].query_id == 205
        assert out[0].item_ids.shape == (20,)
        assert svc.poll() == []          # queue drained

    def test_service_accepts_custom_retriever(self, small_domain):
        from repro.core.engine import AdaCURRetriever

        cfg = AdaCURConfig(
            k_anchor=20, n_rounds=4, budget_ce=40, k_retrieve=20,
            loop_mode="fori", use_fused_topk=True, fused_tile=256,
        )
        ret = AdaCURRetriever(small_domain["ce"].score_fn(), small_domain["r_anc"], cfg)
        svc = AdaCURService(retriever=ret, max_batch=2, max_wait_s=10.0)
        out = []
        for qid in (201, 202):
            got = svc.submit(RetrievalRequest(query_id=qid))
            out += got or []
        assert len(out) == 2
        assert all(r.item_ids.shape == (20,) for r in out)

    def test_service_batches_and_answers(self, small_domain):
        cfg = AdaCURConfig(k_anchor=20, n_rounds=4, budget_ce=40, k_retrieve=20)
        svc = AdaCURService(
            small_domain["ce"].score_fn(), small_domain["r_anc"], cfg,
            max_batch=4, max_wait_s=10.0,
        )
        responses = []
        for qid in range(200, 208):
            out = svc.submit(RetrievalRequest(query_id=qid))
            if out:
                responses += out
        responses += svc.flush()
        assert len(responses) == 8
        for r in responses:
            assert r.item_ids.shape == (20,)
            assert r.ce_calls == 40
