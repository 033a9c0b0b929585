"""Scorer subsystem: provider parity, measured accounting, (query, item)
score caching, length-bucketed micro-batching with zero retraces, and the
real-CE end-to-end search parity vs the exact tabulated matrix."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import AdaCURConfig, replace
from repro.core import engine
from repro.core.scorer import (
    CachingScorer,
    CrossEncoderScorer,
    DeviceCEScorer,
    Scorer,
    SyntheticScorer,
    TabulatedScorer,
    scorer_stats,
)
from repro.data.synthetic import make_synthetic_ce, make_zeshel_like
from repro.models import cross_encoder


@pytest.fixture(scope="module")
def ce_setup():
    """Tiny transformer CE + its exact score matrix (the parity oracle)."""
    ds = make_zeshel_like(0, n_items=80, n_queries=24, item_len=12, query_len=8)
    cfg_lm = replace(
        registry.CE_TINY, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab_size=ds.vocab_size, dtype="float32",
        remat=False,
    )
    params, _ = cross_encoder.init_cross_encoder(jax.random.PRNGKey(0), cfg_lm)
    scorer = CrossEncoderScorer(
        params, cfg_lm, ds.pair_tokens, micro_batch=16, flash_block=(16, 16),
        len_buckets=(32, 64),
    )
    matrix = np.asarray(
        scorer._host(np.arange(24), np.tile(np.arange(80), (24, 1)))
    )
    scorer.reset_stats()
    return {"ds": ds, "lm": (params, cfg_lm), "scorer": scorer, "m": matrix}


class TestProviders:
    def test_protocol(self, ce_setup):
        ce = make_synthetic_ce(jax.random.PRNGKey(1), n_queries=8, n_items=50)
        assert isinstance(SyntheticScorer(ce), Scorer)
        assert isinstance(TabulatedScorer(np.zeros((4, 5))), Scorer)
        assert isinstance(ce_setup["scorer"], Scorer)
        assert scorer_stats(lambda q, i: i) is None

    def test_synthetic_matches_ce(self):
        ce = make_synthetic_ce(jax.random.PRNGKey(1), n_queries=8, n_items=50)
        s = SyntheticScorer(ce)
        q = jnp.arange(4)
        idx = jnp.arange(12).reshape(4, 3)
        np.testing.assert_array_equal(
            np.asarray(s(q, idx)), np.asarray(ce.score_pairs(q, idx))
        )

    def test_tabulated_counts_inside_jit(self):
        m = np.arange(20, dtype=np.float32).reshape(4, 5)
        tab = TabulatedScorer(m, record_pairs=True)

        @jax.jit
        def f(q, idx):
            return tab(q, idx)

        q = jnp.array([0, 2])
        idx = jnp.array([[1, 3], [0, 4]])
        out = np.asarray(f(q, idx))
        np.testing.assert_array_equal(out, m[np.array([0, 2])[:, None], np.asarray(idx)])
        assert tab.stats.ce_calls == 4 and tab.stats.requests == 1
        out2 = np.asarray(f(q, idx))       # compiled path still counts
        np.testing.assert_array_equal(out, out2)
        assert tab.stats.ce_calls == 8 and len(tab.call_log) == 2


class TestCachingScorer:
    def test_hits_and_accounting(self):
        m = np.random.default_rng(0).normal(size=(6, 30)).astype(np.float32)
        cache = CachingScorer(TabulatedScorer(m))
        q = jnp.array([1, 2])
        idx = jnp.array([[0, 1, 2], [3, 4, 5]])
        a = np.asarray(cache(q, idx))
        np.testing.assert_array_equal(a, m[np.array([1, 2])[:, None], np.asarray(idx)])
        assert cache.stats.ce_calls == 6 and cache.stats.cache_hits == 0
        b = np.asarray(cache(q, idx))
        np.testing.assert_array_equal(a, b)
        assert cache.stats.ce_calls == 6 and cache.stats.cache_hits == 6
        # partial overlap: only fresh pairs reach the inner scorer
        idx2 = jnp.array([[0, 1, 7], [3, 8, 9]])
        np.asarray(cache(q, idx2))
        assert cache.stats.ce_calls == 9 and cache.stats.cache_hits == 9

    def test_within_call_dedup(self):
        m = np.random.default_rng(1).normal(size=(4, 10)).astype(np.float32)
        inner = TabulatedScorer(m)
        cache = CachingScorer(inner)
        q = jnp.array([0, 0])                 # two rows, same query
        idx = jnp.array([[1, 2, 1], [2, 3, 3]])   # duplicates inside the call
        out = np.asarray(cache(q, idx))
        np.testing.assert_array_equal(out, m[0][np.asarray(idx)])
        assert cache.stats.ce_calls == 3       # {1, 2, 3} scored once
        assert inner.stats.ce_calls == 3

    def test_lru_capacity(self):
        m = np.zeros((1, 100), dtype=np.float32)
        cache = CachingScorer(TabulatedScorer(m), capacity=4)
        cache(jnp.array([0]), jnp.arange(6)[None, :])
        assert cache.stats.cache_size == 4
        # the two oldest pairs were evicted and must be re-scored
        cache(jnp.array([0]), jnp.arange(2)[None, :])
        assert cache.stats.ce_calls == 8

    def test_rejects_pure_traced_inner(self):
        ce = make_synthetic_ce(jax.random.PRNGKey(1), n_queries=8, n_items=50)
        with pytest.raises(TypeError):
            CachingScorer(SyntheticScorer(ce))


class TestCrossEncoderScorer:
    def test_matches_direct_score_pairs(self, ce_setup):
        ds, (params, cfg_lm) = ce_setup["ds"], ce_setup["lm"]
        sc = ce_setup["scorer"]
        q = np.arange(5)
        idx = (np.arange(20).reshape(5, 4) * 3) % 80
        toks = jnp.asarray(ds.pair_tokens(q, idx))
        ref = np.asarray(cross_encoder.score_pairs(params, toks, cfg_lm))
        out = np.asarray(sc(jnp.asarray(q), jnp.asarray(idx)))
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)

    def test_bucketing_never_retraces(self, ce_setup):
        sc = ce_setup["scorer"]
        sc._host(np.arange(3), np.arange(6).reshape(3, 2))
        n0 = sc.n_traces
        # new (batch, k) shapes, same token bucket -> zero retraces
        for b, k in [(1, 1), (7, 5), (2, 16), (5, 3)]:
            sc._host(np.arange(b), (np.arange(b * k).reshape(b, k) * 7) % 80)
        assert sc.n_traces == n0
        assert sc.stats.batch_pad > 0          # partial chunks were padded

    def test_bucket_overflow_raises_at_construction(self, ce_setup):
        """Satellite: the pair-length probe fails eagerly, with an actionable
        message — not an opaque XLA error from inside jax.pure_callback."""
        ds, (params, cfg_lm) = ce_setup["ds"], ce_setup["lm"]
        with pytest.raises(ValueError, match="len_buckets"):
            CrossEncoderScorer(params, cfg_lm, ds.pair_tokens, len_buckets=(8,))

    def test_bucket_overflow_raises_per_call(self, ce_setup):
        """With the probe disabled (pair_fn that rejects dummy ids), the host
        enqueue path still raises the same actionable ValueError."""
        ds, (params, cfg_lm) = ce_setup["ds"], ce_setup["lm"]
        sc = CrossEncoderScorer(
            params, cfg_lm, ds.pair_tokens, len_buckets=(8,),
            probe_pair_len=False,
        )
        with pytest.raises(ValueError, match="len_buckets"):
            sc._host(np.arange(2), np.arange(4).reshape(2, 2))

    def test_flash_varlen_matches_ref_attention(self, ce_setup):
        """One padded bucket, mixed true lengths: the flash path's SMEM
        valid-length masking equals the (B, L) kv_mask reference."""
        ds, (params, cfg_lm) = ce_setup["ds"], ce_setup["lm"]
        toks = ds.pair_tokens(np.arange(4), np.arange(12).reshape(4, 3))
        b, k, length = toks.shape
        padded = np.zeros((b, k, 48), np.int32)
        padded[:, :, :length] = toks
        ref = cross_encoder.score_pairs(params, jnp.asarray(padded), cfg_lm)
        flash = cross_encoder.score_pairs(
            params, jnp.asarray(padded), cfg_lm, attn_impl="flash",
            flash_block=(16, 16),
        )
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(ref), atol=1e-5, rtol=1e-5
        )


class TestEndToEndParity:
    @pytest.mark.parametrize("loop_mode", ["unrolled", "fori"])
    def test_real_ce_search_matches_tabulated(self, ce_setup, loop_mode):
        """The acceptance bar: an engine search scored by the REAL
        cross-encoder retrieves exactly what the tabulated exact matrix
        retrieves — tokenization, bucketing, micro-batching and the flash
        path introduce no drift."""
        m = ce_setup["m"]
        cfg = AdaCURConfig(
            k_anchor=12, n_rounds=3, budget_ce=24, k_retrieve=10,
            loop_mode=loop_mode,
        )
        r_anc = jnp.asarray(m[:16])
        q = jnp.arange(16, 24)
        res_ce = jax.block_until_ready(
            engine.make_engine(ce_setup["scorer"], cfg)(
                r_anc, q, jax.random.PRNGKey(5)
            )
        )
        res_tab = jax.block_until_ready(
            engine.make_engine(TabulatedScorer(m), cfg)(
                r_anc, q, jax.random.PRNGKey(5)
            )
        )
        np.testing.assert_array_equal(
            np.asarray(res_ce.topk_idx), np.asarray(res_tab.topk_idx)
        )
        np.testing.assert_allclose(
            np.asarray(res_ce.topk_scores), np.asarray(res_tab.topk_scores),
            atol=1e-4, rtol=1e-4,
        )

    def test_cached_ce_search(self, ce_setup):
        """CachingScorer over the real CE: a repeated search re-scores
        nothing and returns identical results."""
        m = ce_setup["m"]
        cache = CachingScorer(ce_setup["scorer"])
        cfg = AdaCURConfig(
            k_anchor=12, n_rounds=3, budget_ce=24, k_retrieve=10,
            loop_mode="fori",
        )
        run = engine.make_engine(cache, cfg)
        r_anc = jnp.asarray(m[:16])
        q = jnp.arange(16, 24)
        r1 = jax.block_until_ready(run(r_anc, q, jax.random.PRNGKey(5)))
        cold = cache.stats.ce_calls
        assert cold > 0
        r2 = jax.block_until_ready(run(r_anc, q, jax.random.PRNGKey(5)))
        assert cache.stats.ce_calls == cold          # zero new CE calls
        np.testing.assert_array_equal(
            np.asarray(r1.topk_idx), np.asarray(r2.topk_idx)
        )

    def test_microbatch_pad_rows_never_leak(self, ce_setup):
        """Satellite audit: a batch size that forces micro-batch padding
        (B=5, k_s=4 -> 20 pairs padded to 32; rerank 60 -> 64) keeps
        measured == planned — pad rows are scored for shape stability but
        never reach ``stats.ce_calls`` or the cache."""
        m = ce_setup["m"]
        ds, (params, cfg_lm) = ce_setup["ds"], ce_setup["lm"]
        sc = CrossEncoderScorer(
            params, cfg_lm, ds.pair_tokens, micro_batch=16,
            flash_block=(16, 16), len_buckets=(32, 64),
        )
        cfg = AdaCURConfig(
            k_anchor=12, n_rounds=3, budget_ce=24, k_retrieve=10,
            loop_mode="fori",
        )
        r_anc = jnp.asarray(m[:16])
        q = jnp.arange(16, 21)                       # B=5: every chunk pads
        jax.block_until_ready(
            engine.make_engine(sc, cfg)(r_anc, q, jax.random.PRNGKey(5))
        )
        assert sc.stats.ce_calls == engine.ce_call_plan(cfg) * 5
        assert sc.stats.batch_pad > 0                # padding really happened
        # through the cache: every miss keys exactly one real pair, so no
        # pad-derived phantom entries can appear
        inner = CrossEncoderScorer(
            params, cfg_lm, ds.pair_tokens, micro_batch=16,
            flash_block=(16, 16), len_buckets=(32, 64),
        )
        cache = CachingScorer(inner)
        jax.block_until_ready(
            engine.make_engine(cache, cfg)(r_anc, q, jax.random.PRNGKey(5))
        )
        assert cache.stats.cache_size == cache.stats.ce_calls
        assert inner.stats.ce_calls == cache.stats.ce_calls
        assert inner.stats.batch_pad > 0


class TestDeviceCEScorer:
    """The device-resident CE provider: in-trace pair assembly + forward,
    exact parity with the host-callback scorer, and measured == planned
    accounting fired from inside the compiled program."""

    @pytest.fixture()
    def device_scorer(self, ce_setup):
        ds, (params, cfg_lm) = ce_setup["ds"], ce_setup["lm"]
        return DeviceCEScorer(
            params, cfg_lm,
            query_token_fn=lambda q: np.asarray(ds.query_tokens)[q],
            item_tokens=ds.item_tokens,
            len_buckets=(32, 64), flash_block=(16, 16),
        )

    def test_matches_host_scorer(self, ce_setup, device_scorer):
        q = jnp.arange(5)
        idx = jnp.asarray((np.arange(20).reshape(5, 4) * 3) % 80)
        q_tok = device_scorer.tokenize_queries(q)
        out = np.asarray(device_scorer(q_tok, idx))
        ref = np.asarray(ce_setup["scorer"](q, idx))
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)
        assert device_scorer.stats.ce_calls == 20
        assert device_scorer.stats.requests == 1

    def test_engine_search_matches_tabulated(self, ce_setup, device_scorer):
        """Device-resident CE retrieves exactly what the tabulated exact
        matrix retrieves, with zero retraces across run-shape variations."""
        m = ce_setup["m"]
        cfg = AdaCURConfig(
            k_anchor=12, n_rounds=3, budget_ce=24, k_retrieve=10,
            loop_mode="fori",
        )
        r_anc = jnp.asarray(m[:16])
        q = jnp.arange(16, 24)
        q_tok = device_scorer.tokenize_queries(q)
        run = engine.make_engine(device_scorer, cfg)
        res = jax.block_until_ready(run(r_anc, q_tok, jax.random.PRNGKey(5)))
        res_tab = jax.block_until_ready(
            engine.make_engine(TabulatedScorer(m), cfg)(
                r_anc, q, jax.random.PRNGKey(5)
            )
        )
        np.testing.assert_array_equal(
            np.asarray(res.topk_idx), np.asarray(res_tab.topk_idx)
        )
        np.testing.assert_allclose(
            np.asarray(res.topk_scores), np.asarray(res_tab.topk_scores),
            atol=1e-4, rtol=1e-4,
        )
        assert device_scorer.stats.ce_calls == engine.ce_call_plan(cfg) * 8
        n0 = device_scorer.n_traces
        for n_rounds in (1, 3, 2):
            jax.block_until_ready(
                run(r_anc, q_tok, jax.random.PRNGKey(5), n_rounds=n_rounds)
            )
        assert device_scorer.n_traces == n0          # bucketed: no retraces

    def test_bulk_score_matches_exact_matrix(self, ce_setup, device_scorer):
        """The on-device bulk path (index build, brute force) scores every
        pair like the host CE — padded item chunks included — and counts
        nothing as serving cost."""
        q = jnp.arange(3, 8)
        out = np.asarray(device_scorer.bulk_score(q, jnp.arange(80)))
        np.testing.assert_allclose(out, ce_setup["m"][3:8], atol=1e-4, rtol=1e-4)
        assert device_scorer.stats.ce_calls == 0

    def test_bucket_overflow_raises_eagerly(self, ce_setup):
        ds, (params, cfg_lm) = ce_setup["ds"], ce_setup["lm"]
        sc = DeviceCEScorer(
            params, cfg_lm,
            query_token_fn=lambda q: np.asarray(ds.query_tokens)[q],
            item_tokens=ds.item_tokens, len_buckets=(8,),
        )
        with pytest.raises(ValueError, match="len_buckets"):
            sc.tokenize_queries(jnp.arange(2))

    def test_requires_token_table(self, ce_setup):
        ds, (params, cfg_lm) = ce_setup["ds"], ce_setup["lm"]
        sc = DeviceCEScorer(
            params, cfg_lm,
            query_token_fn=lambda q: np.asarray(ds.query_tokens)[q],
        )
        with pytest.raises(ValueError, match="token table"):
            sc(jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 2), jnp.int32))
