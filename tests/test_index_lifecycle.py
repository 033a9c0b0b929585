"""AnchorIndex lifecycle: build -> interrupt -> resume bit-parity, stale
manifest invalidation (the block_rows regression), save -> load -> search
round-trip parity, add_items/remove_items parity vs a from-scratch rebuild
(and no-retrace), external item ids, and the index-first service."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AdaCURConfig
from repro.core.engine import AdaCURRetriever, ANNCURRetriever, RerankRetriever
from repro.core.index import AnchorIndex, build_r_anc
from repro.data.synthetic import make_synthetic_ce
from repro.launch.mesh import make_mesh

CFG = AdaCURConfig(
    k_anchor=20, n_rounds=4, budget_ce=40, k_retrieve=10, loop_mode="fori"
)


@pytest.fixture(scope="module")
def dom():
    ce = make_synthetic_ce(jax.random.PRNGKey(0), n_queries=60, n_items=300)
    m = ce.full_matrix(jnp.arange(60))
    return {
        "ce": ce,
        "m": m,                      # (60, 300) full score matrix
        "q_ids": jnp.arange(40),     # anchor queries
        "test_q": jnp.arange(40, 60),
    }


class TestBuildResume:
    def test_interrupt_then_resume_bit_parity(self, dom, tmp_path):
        """A preempted build resumes from its block checkpoints and produces
        the exact bytes an uninterrupted build produces, rescoring only the
        missing blocks."""
        ce, q_ids = dom["ce"], dom["q_ids"]
        item_ids = jnp.arange(300)
        d = str(tmp_path / "ck")

        calls = {"n": 0}

        def flaky(q, i):
            if calls["n"] >= 2:
                raise RuntimeError("preempted")
            calls["n"] += 1
            return ce.score_block(q, i)

        with pytest.raises(RuntimeError):
            build_r_anc(flaky, q_ids, item_ids, block_rows=8, checkpoint_dir=d)

        count = {"n": 0}

        def counting(q, i):
            count["n"] += 1
            return ce.score_block(q, i)

        resumed = build_r_anc(counting, q_ids, item_ids, block_rows=8,
                              checkpoint_dir=d)
        assert count["n"] == 3          # 5 blocks total, 2 were checkpointed
        fresh = build_r_anc(ce.score_block, q_ids, item_ids, block_rows=8)
        np.testing.assert_array_equal(np.asarray(resumed), np.asarray(fresh))

    def test_stale_block_rows_invalidates_manifest(self, dom, tmp_path):
        """Regression: the manifest used to validate only k_q/n_items, so
        resuming with a different block_rows silently reused blocks whose
        row ranges no longer matched.  It must be invalidated instead."""
        ce, q_ids = dom["ce"], dom["q_ids"]
        item_ids = jnp.arange(300)
        d = str(tmp_path / "ck")
        first = build_r_anc(ce.score_block, q_ids, item_ids, block_rows=16,
                            checkpoint_dir=d)
        # same dir, different block geometry: all blocks must be rescored
        second = build_r_anc(ce.score_block, q_ids, item_ids, block_rows=8,
                             checkpoint_dir=d)
        assert second.shape == (40, 300)
        np.testing.assert_array_equal(np.asarray(second), np.asarray(first))
        with open(os.path.join(d, "manifest.json")) as f:
            meta = json.load(f)
        assert meta["block_rows"] == 8
        assert len(meta["done_blocks"]) == 5

    def test_stale_id_content_invalidates_manifest(self, dom, tmp_path):
        """Same shapes/block geometry but DIFFERENT anchor-query ids must
        not reuse blocks: the manifest fingerprints the id content."""
        ce = dom["ce"]
        item_ids = jnp.arange(300)
        d = str(tmp_path / "ck")
        build_r_anc(ce.score_block, jnp.arange(40), item_ids, block_rows=16,
                    checkpoint_dir=d)
        other_q = jnp.arange(10, 50)       # same k_q, different queries
        got = build_r_anc(ce.score_block, other_q, item_ids, block_rows=16,
                          checkpoint_dir=d)
        fresh = build_r_anc(ce.score_block, other_q, item_ids, block_rows=16)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(fresh))

    def test_resume_skips_all_blocks(self, dom, tmp_path):
        ce, q_ids = dom["ce"], dom["q_ids"]
        d = str(tmp_path / "ck")
        idx = AnchorIndex.build(ce.score_block, q_ids, jnp.arange(300),
                                block_rows=16, checkpoint_dir=d)

        def exploding(q, i):
            raise AssertionError("resume must not rescore finished blocks")

        idx2 = AnchorIndex.build(exploding, q_ids, jnp.arange(300),
                                 block_rows=16, checkpoint_dir=d)
        np.testing.assert_array_equal(np.asarray(idx.r_anc), np.asarray(idx2.r_anc))


class TestSaveLoad:
    def test_save_load_search_round_trip(self, dom, tmp_path):
        ce, m = dom["ce"], dom["m"]
        sf = ce.score_fn()
        index = AnchorIndex.from_r_anc(m[:40], capacity=320).with_latents(
            k_anchor=10, key=jax.random.PRNGKey(5)
        )
        path = str(tmp_path / "index")
        index.save(path)
        loaded = AnchorIndex.load(path)
        for name in ("r_anc", "item_ids", "n_valid", "anchor_item_pos",
                     "u", "item_embeddings"):
            np.testing.assert_array_equal(
                np.asarray(getattr(index, name)), np.asarray(getattr(loaded, name))
            )
        key = jax.random.PRNGKey(1)
        res_m = AdaCURRetriever.from_index(index, sf, CFG).search(dom["test_q"], key)
        res_l = AdaCURRetriever.from_index(loaded, sf, CFG).search(dom["test_q"], key)
        np.testing.assert_array_equal(
            np.asarray(res_m.topk_idx), np.asarray(res_l.topk_idx)
        )
        np.testing.assert_array_equal(
            np.asarray(res_m.topk_scores), np.asarray(res_l.topk_scores)
        )

    def test_version_check(self, dom, tmp_path):
        index = AnchorIndex.from_r_anc(dom["m"][:40])
        path = str(tmp_path / "index")
        index.save(path)
        meta_path = os.path.join(path, "index_meta.json")
        with open(meta_path) as f:
            meta = json.load(f)
        meta["format_version"] = 999
        with open(meta_path, "w") as f:
            json.dump(meta, f)
        with pytest.raises(ValueError, match="format version"):
            AnchorIndex.load(path)

    def test_load_missing_dir_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            AnchorIndex.load(str(tmp_path / "nope"))


class TestMutation:
    def test_add_items_parity_vs_rebuild(self, dom):
        ce, m = dom["ce"], dom["m"]
        sf = ce.score_fn()
        key = jax.random.PRNGKey(1)
        index = AnchorIndex.from_r_anc(
            m[:40, :250], item_ids=jnp.arange(250), capacity=300
        )
        grown = index.add_items(jnp.arange(250, 300), cols=m[:40, 250:300])
        rebuild = AnchorIndex.from_r_anc(m[:40], capacity=300)
        np.testing.assert_array_equal(
            np.asarray(grown.r_anc), np.asarray(rebuild.r_anc)
        )
        res_g = AdaCURRetriever.from_index(grown, sf, CFG).search(dom["test_q"], key)
        res_r = AdaCURRetriever.from_index(rebuild, sf, CFG).search(dom["test_q"], key)
        np.testing.assert_array_equal(
            np.asarray(res_g.topk_idx), np.asarray(res_r.topk_idx)
        )
        np.testing.assert_array_equal(
            np.asarray(res_g.topk_scores), np.asarray(res_r.topk_scores)
        )

    def test_remove_items_parity_vs_rebuild(self, dom):
        ce, m = dom["ce"], dom["m"]
        sf = ce.score_fn()
        key = jax.random.PRNGKey(2)
        full = AnchorIndex.from_r_anc(m[:40], capacity=300)
        rm = jnp.arange(100, 150)
        shrunk = full.remove_items(rm)
        surv = np.setdiff1d(np.arange(300), np.asarray(rm))
        rebuild = AnchorIndex.from_r_anc(
            m[:40][:, surv], item_ids=jnp.asarray(surv), capacity=300
        )
        np.testing.assert_array_equal(
            np.asarray(shrunk.r_anc), np.asarray(rebuild.r_anc)
        )
        np.testing.assert_array_equal(
            np.asarray(shrunk.item_ids), np.asarray(rebuild.item_ids)
        )
        res_s = AdaCURRetriever.from_index(shrunk, sf, CFG).search(dom["test_q"], key)
        res_r = AdaCURRetriever.from_index(rebuild, sf, CFG).search(dom["test_q"], key)
        np.testing.assert_array_equal(
            np.asarray(res_s.topk_idx), np.asarray(res_r.topk_idx)
        )
        # removed external ids never appear in the results
        got_ids = np.asarray(shrunk.gather_item_ids(res_s.topk_idx))
        assert not np.isin(got_ids, np.asarray(rm)).any()

    def test_mutation_never_retraces(self, dom):
        ce, m = dom["ce"], dom["m"]
        sf = ce.score_fn()
        traces = []

        def counting_sf(q, i):
            traces.append(1)
            return sf(q, i)

        index = AnchorIndex.from_r_anc(
            m[:40, :250], item_ids=jnp.arange(250), capacity=300
        )
        ret = AdaCURRetriever.from_index(index, counting_sf, CFG)
        ret.search(dom["test_q"], jax.random.PRNGKey(1))
        n_traces = len(traces)
        assert n_traces > 0
        ret.index = index.add_items(jnp.arange(250, 300), cols=m[:40, 250:300])
        ret.search(dom["test_q"], jax.random.PRNGKey(1))
        ret.index = ret.index.remove_items(jnp.arange(10, 40))
        ret.search(dom["test_q"], jax.random.PRNGKey(2))
        assert len(traces) == n_traces, "index mutation retraced the engine"

    def test_mutation_guards(self, dom):
        m = dom["m"]
        index = AnchorIndex.from_r_anc(m[:40, :250], capacity=260)
        with pytest.raises(ValueError, match="overflows capacity"):
            index.add_items(jnp.arange(250, 300), cols=m[:40, 250:300])
        with pytest.raises(ValueError, match="already in the index"):
            index.add_items(jnp.arange(5), cols=m[:40, :5])
        with pytest.raises(ValueError, match="duplicate item ids"):
            index.add_items(jnp.asarray([250, 250]), cols=m[:40, :2])
        with pytest.raises(ValueError, match="padding sentinel"):
            index.add_items(jnp.asarray([-1]), cols=m[:40, :1])
        latent = index.with_latents(k_anchor=8, key=jax.random.PRNGKey(0))
        anchor_id = int(latent.gather_item_ids(latent.anchor_item_pos)[0])
        with pytest.raises(ValueError, match="anchor item"):
            latent.remove_items(jnp.asarray([anchor_id]))

    def test_remove_items_remaps_anchor_positions(self, dom):
        """Compaction shifts anchor positions; the latents must track them."""
        m = dom["m"]
        index = AnchorIndex.from_r_anc(m[:40]).with_latents(
            anchor_pos=jnp.asarray([200, 250, 299])
        )
        shrunk = index.remove_items(jnp.arange(0, 50))   # non-anchor prefix
        np.testing.assert_array_equal(
            np.asarray(shrunk.anchor_item_pos), np.asarray([150, 200, 249])
        )
        np.testing.assert_array_equal(
            np.asarray(shrunk.gather_item_ids(shrunk.anchor_item_pos)),
            np.asarray([200, 250, 299]),
        )


class TestStaticVsDynamicValidPath:
    def test_unpadded_index_keeps_static_engine_path(self, dom):
        """An unpadded index must not force the runtime n_valid bound (which
        routes the fused TPU kernel through a (B, N) mask) and must match
        the classic bare-r_anc retriever exactly."""
        ce, m = dom["ce"], dom["m"]
        sf = ce.score_fn()
        ret = AdaCURRetriever.from_index(AnchorIndex.from_r_anc(m[:40]), sf, CFG)
        _, kw = ret._search_operands()
        assert "n_valid" not in kw
        res = ret.search(dom["test_q"], jax.random.PRNGKey(1))
        ref = AdaCURRetriever(sf, m[:40], CFG).search(dom["test_q"], jax.random.PRNGKey(1))
        np.testing.assert_array_equal(
            np.asarray(res.topk_idx), np.asarray(ref.topk_idx)
        )

    def test_padded_index_uses_dynamic_bound(self, dom):
        ret = AdaCURRetriever.from_index(
            AnchorIndex.from_r_anc(dom["m"][:40], capacity=350),
            dom["ce"].score_fn(), CFG,
        )
        _, kw = ret._search_operands()
        assert "n_valid" in kw

    def test_remove_on_unpadded_index_stays_correct(self, dom):
        """Removing from an initially-unpadded index flips it to the dynamic
        path (one retrace) — freed slots must still never be retrieved."""
        ce, m = dom["ce"], dom["m"]
        sf = ce.score_fn()
        ret = AdaCURRetriever.from_index(AnchorIndex.from_r_anc(m[:40]), sf, CFG)
        ret.search(dom["test_q"], jax.random.PRNGKey(1))
        rm = jnp.arange(0, 50)
        ret.index = ret.index.remove_items(rm)
        res = ret.search(dom["test_q"], jax.random.PRNGKey(2))
        got = np.asarray(ret.index.gather_item_ids(res.topk_idx))
        assert not np.isin(got, np.asarray(rm)).any()
        assert (got >= 0).all()


class TestExternalItemIds:
    def test_engine_maps_positions_to_ids(self, dom):
        """With non-identity item_ids, score_fn sees external ids and the
        returned exact scores match a direct CE call on those ids."""
        ce, m = dom["ce"], dom["m"]
        sf = ce.score_fn()
        ids = jnp.arange(100, 300)        # items 100..299 only, positions 0..199
        index = AnchorIndex.from_r_anc(m[:40, 100:300], item_ids=ids, capacity=220)
        res = AdaCURRetriever.from_index(index, sf, CFG).search(
            dom["test_q"], jax.random.PRNGKey(3)
        )
        ext = index.gather_item_ids(res.topk_idx)
        assert (np.asarray(ext) >= 100).all()
        direct = sf(dom["test_q"], ext)
        np.testing.assert_allclose(
            np.asarray(res.topk_scores), np.asarray(direct), rtol=1e-5, atol=1e-5
        )


class TestShardedTopk:
    def test_single_device_shard_parity(self, dom):
        index = AnchorIndex.from_r_anc(dom["m"][:40], capacity=320)
        mesh = make_mesh((1,), ("data",))
        sharded = index.shard(mesh)
        e_q = jax.random.normal(jax.random.PRNGKey(3), (5, 40))
        v0, i0 = index.topk(e_q, 8, tile=64)
        v1, i1 = sharded.topk(e_q, 8, tile=64)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), rtol=1e-5)


class TestANNCURLivesInTheIndex:
    """The deprecated ``core.anncur`` shim module is gone: its offline
    product is ``with_latents`` and its search is ``ANNCURRetriever``."""

    def test_shim_module_removed(self):
        with pytest.raises(ImportError):
            from repro.core import anncur  # noqa: F401

    def test_latents_index_drives_the_engine(self, dom):
        sf = dom["ce"].score_fn()
        index = AnchorIndex.from_r_anc(dom["m"][:40]).with_latents(
            k_anchor=10, key=jax.random.PRNGKey(7)
        )
        assert index.has_latents
        res = ANNCURRetriever.from_index(
            index, sf, budget_ce=20, k_retrieve=10
        ).search(dom["test_q"])
        # retrieved scores are the exact CE scores of the retrieved ids
        ref = jnp.take_along_axis(dom["m"][40:], res.topk_idx, axis=1)
        np.testing.assert_allclose(
            np.asarray(res.topk_scores), np.asarray(ref), rtol=1e-5, atol=1e-5
        )


class TestServiceOverIndex:
    def test_service_from_index_path_and_swap(self, dom, tmp_path):
        from repro.launch.serve import AdaCURService, RetrievalRequest

        ce, m = dom["ce"], dom["m"]
        index = AnchorIndex.from_r_anc(m[:40, :250], capacity=300)
        path = str(tmp_path / "index")
        index.save(path)
        svc = AdaCURService(
            score_fn=ce.score_fn(), cfg=CFG, index=path,
            max_batch=2, max_wait_s=10.0,
        )
        out = []
        for qid in (41, 42):
            out += svc.submit(RetrievalRequest(query_id=qid)) or []
        assert len(out) == 2
        assert all((r.item_ids < 250).all() for r in out)
        # grow the corpus in place: no rebuild, served from the next batch
        svc.swap_index(svc.index.add_items(jnp.arange(250, 300),
                                           cols=m[:40, 250:300]))
        out2 = []
        for qid in (43, 44):
            out2 += svc.submit(RetrievalRequest(query_id=qid)) or []
        assert len(out2) == 2

    def test_default_retriever_ignores_candidate_fn(self, dom):
        """A service built with the default AdaCUR retriever plus a
        candidate_fn must not crash at flush (regression: search() rejected
        the candidate_idx kwarg)."""
        from repro.launch.serve import AdaCURService, RetrievalRequest

        svc = AdaCURService(
            score_fn=dom["ce"].score_fn(), cfg=CFG,
            index=AnchorIndex.from_r_anc(dom["m"][:40]),
            max_batch=1, candidate_fn=lambda qids: jnp.zeros(
                (qids.shape[0], CFG.budget_ce), jnp.int32
            ),
        )
        out = svc.submit(RetrievalRequest(query_id=45))
        assert out and out[0].item_ids.shape == (10,)

    def test_swap_index_requires_index_backed_retriever(self, dom):
        from repro.launch.serve import AdaCURService

        sf = dom["ce"].score_fn()
        svc = AdaCURService(score_fn=sf, r_anc=dom["m"][:40], cfg=CFG,
                            retriever=AdaCURRetriever(sf, dom["m"][:40], CFG))
        with pytest.raises(ValueError, match="index-backed"):
            svc.swap_index(AnchorIndex.from_r_anc(dom["m"][:40]))

    def test_make_retriever_kinds(self, dom):
        from repro.launch.serve import make_retriever

        sf = dom["ce"].score_fn()
        index = AnchorIndex.from_r_anc(dom["m"][:40])
        for kind, cls in (("adacur", AdaCURRetriever),
                          ("anncur", ANNCURRetriever),
                          ("rerank", RerankRetriever)):
            ret = make_retriever(kind, index, sf, CFG)
            assert isinstance(ret, cls)
        with pytest.raises(ValueError, match="unknown retriever"):
            make_retriever("bm25", index, sf, CFG)


class TestTokenTable:
    """The corpus token table (device-resident CE): positional lockstep with
    the payload through every mutation, v3 save/load round-trip, and the
    lowest-version stamping that keeps older readers working."""

    def _tokens(self, n, item_len=6):
        return (np.arange(n * item_len, dtype=np.int32).reshape(n, item_len)
                % 97) + 3

    def test_attach_pads_to_capacity(self, dom):
        index = AnchorIndex.from_r_anc(dom["m"][:40, :250],
                                       item_ids=jnp.arange(250), capacity=300)
        tok = self._tokens(250)
        with_tok = index.with_item_tokens(tok)
        assert with_tok.item_tokens.shape == (300, 6)
        np.testing.assert_array_equal(np.asarray(with_tok.item_tokens[:250]), tok)
        np.testing.assert_array_equal(
            np.asarray(with_tok.item_tokens[250:]), np.zeros((50, 6), np.int32)
        )
        # full-capacity tables attach as-is; other row counts are rejected
        assert index.with_item_tokens(
            self._tokens(300)
        ).item_tokens.shape == (300, 6)
        with pytest.raises(ValueError, match="rows"):
            index.with_item_tokens(self._tokens(123))

    def test_mutation_keeps_positional_lockstep(self, dom):
        m = dom["m"]
        tok = self._tokens(250)
        index = AnchorIndex.from_r_anc(
            m[:40, :250], item_ids=jnp.arange(250), capacity=300
        ).with_item_tokens(tok)

        # add_items without tokens must fail loudly, with them it appends
        with pytest.raises(ValueError, match="new_tokens"):
            index.add_items(jnp.arange(250, 260), cols=m[:40, 250:260])
        new_tok = self._tokens(10) + 1
        grown = index.add_items(jnp.arange(250, 260), cols=m[:40, 250:260],
                                new_tokens=new_tok)
        np.testing.assert_array_equal(np.asarray(grown.item_tokens[:250]), tok)
        np.testing.assert_array_equal(np.asarray(grown.item_tokens[250:260]),
                                      new_tok)

        # remove_items compacts the table with the same stable permutation
        # as the payload: position j still tokenizes the item at position j
        shrunk = grown.remove_items(jnp.arange(100, 150))
        ids = np.asarray(shrunk.item_ids)
        table = np.asarray(shrunk.item_tokens)
        full = np.concatenate([tok, new_tok], axis=0)
        for pos in range(int(shrunk.n_items)):
            np.testing.assert_array_equal(table[pos], full[ids[pos]])

        # tokenless index rejects stray new_tokens
        bare = AnchorIndex.from_r_anc(m[:40, :250], capacity=300)
        with pytest.raises(ValueError, match="no token table"):
            bare.add_items(jnp.arange(250, 260), cols=m[:40, 250:260],
                           new_tokens=new_tok)

    def test_save_load_round_trip_and_version(self, dom, tmp_path):
        m = dom["m"]
        base = AnchorIndex.from_r_anc(m[:40, :250], capacity=300)

        # a token-carrying index stamps v3 and round-trips the table
        path3 = str(tmp_path / "v3")
        base.with_item_tokens(self._tokens(250)).save(path3)
        with open(os.path.join(path3, "index_meta.json")) as f:
            assert json.load(f)["format_version"] == 3
        loaded = AnchorIndex.load(path3)
        np.testing.assert_array_equal(
            np.asarray(loaded.item_tokens),
            np.asarray(base.with_item_tokens(self._tokens(250)).item_tokens),
        )

        # feature-gated stamping: plain fp32 stays v1, quantized-only v2
        path1 = str(tmp_path / "v1")
        base.save(path1)
        with open(os.path.join(path1, "index_meta.json")) as f:
            assert json.load(f)["format_version"] == 1
        assert AnchorIndex.load(path1).item_tokens is None
        path2 = str(tmp_path / "v2")
        base.quantize("int8", tile=50).save(path2)
        with open(os.path.join(path2, "index_meta.json")) as f:
            assert json.load(f)["format_version"] == 2

    def test_capacity_repad_preserves_table(self, dom):
        index = AnchorIndex.from_r_anc(
            dom["m"][:40, :250], capacity=300
        ).with_item_tokens(self._tokens(250))
        wide = index.with_capacity(384)
        assert wide.item_tokens.shape == (384, 6)
        np.testing.assert_array_equal(
            np.asarray(wide.item_tokens[:250]), self._tokens(250)
        )
        np.testing.assert_array_equal(
            np.asarray(wide.item_tokens[250:]), 0
        )
