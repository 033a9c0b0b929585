"""Quantized anchor-payload lifecycle: quantize/dequantize invariants, the
payload-policy wiring (config -> from_index -> engine), save -> load ->
search parity, shard(mesh) codes+scales co-sharding parity, and the
mutation round-trip guarantee (remove_items -> add_items keeps untouched
tiles bit-identical)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AdaCURConfig, replace
from repro.core.engine import AdaCURRetriever, ANNCURRetriever, RerankRetriever
from repro.core.index import AnchorIndex
from repro.data.synthetic import make_synthetic_ce
from repro.kernels.approx_topk import quant
from repro.kernels.approx_topk.quant import QuantizedRanc
from repro.launch.mesh import make_mesh

TILE = 64
CFG = AdaCURConfig(
    k_anchor=20, n_rounds=4, budget_ce=40, k_retrieve=10, loop_mode="fori",
    payload_dtype="int8", payload_tile=TILE,
)


@pytest.fixture(scope="module")
def dom():
    ce = make_synthetic_ce(jax.random.PRNGKey(0), n_queries=60, n_items=300)
    m = ce.full_matrix(jnp.arange(60))
    return {
        "ce": ce,
        "m": m,                      # (60, 300) full score matrix
        "q_ids": jnp.arange(40),
        "test_q": jnp.arange(40, 60),
    }


def _codes_scales(idx):
    assert isinstance(idx.r_anc, QuantizedRanc)
    return np.asarray(idx.r_anc.codes), np.asarray(idx.r_anc.scales)


class TestQuantizePrimitives:
    def test_round_trip_error_bound_and_zero_tiles(self):
        r = jax.random.normal(jax.random.PRNGKey(1), (24, 500))
        r = r.at[:, 448:].set(0.0)               # an exactly-zero tail tile
        p = quant.quantize_ranc(r, tile=64)
        deq = quant.dequantize(p)
        # half-lsb error bound, exact zeros stay exact
        assert float(jnp.abs(deq - r).max()) <= float(p.scales.max()) * 0.5 + 1e-7
        np.testing.assert_array_equal(np.asarray(deq[:, 448:]), 0.0)
        assert float(p.scales[-1]) == 1.0        # zero tile stores scale 1.0
        # deterministic: re-quantizing the dequantized payload is a fixpoint
        p2 = quant.quantize_ranc(deq, tile=64)
        np.testing.assert_array_equal(np.asarray(p.codes), np.asarray(p2.codes))

    def test_payload_is_quarter_size(self):
        r = jnp.ones((128, 4096))
        p = quant.quantize_ranc(r, tile=512)
        assert p.nbytes / r.nbytes <= 0.3

    def test_int4_pack_unpack_round_trip(self):
        """Packed-nibble codes: two columns per byte, exact code recovery,
        and the dequantized payload reconstructs within half an int4 lsb."""
        r = jax.random.normal(jax.random.PRNGKey(2), (16, 384))
        p = quant.quantize_ranc(r, tile=64, code_dtype="int4")
        assert p.codes.dtype == jnp.uint8
        assert p.codes.shape == (16, 192)            # two codes per byte
        assert p.shape == (16, 384)
        assert p.nbytes / r.nbytes <= 0.15
        deq = quant.dequantize(p)
        assert float(jnp.abs(deq - r).max()) <= float(p.scales.max()) * 0.5 + 1e-6
        # re-quantizing the reconstruction is a fixpoint of the code grid
        p2 = quant.quantize_ranc(deq, tile=64, code_dtype="int4")
        np.testing.assert_array_equal(np.asarray(p.codes), np.asarray(p2.codes))

    def test_int4_requires_even_tile(self):
        r = jnp.ones((4, 128))
        with pytest.raises(ValueError, match="even tile"):
            quant.quantize_ranc(r, tile=63, code_dtype="int4")

    @pytest.mark.skipif(not quant.fp8_supported(), reason="no float8 in build")
    def test_fp8_round_trip_and_bytes(self):
        r = 3.0 * jax.random.normal(jax.random.PRNGKey(3), (16, 384))
        p = quant.quantize_ranc(r, tile=64, code_dtype="fp8")
        assert p.codes.dtype == jnp.float8_e4m3fn
        assert p.nbytes / r.nbytes <= 0.3
        deq = quant.dequantize(p)
        # fp8-e4m3 carries a 3-bit mantissa: error <= |x| * 2^-4 everywhere
        # the code is normal, plus one subnormal ulp (scale * 2^-9) near 0
        bound = jnp.abs(r) * 2.0 ** -4 + float(p.scales.max()) * 2.0 ** -9 + 1e-7
        assert bool((jnp.abs(deq - r) <= bound).all())

    def test_index_quantize_policy(self, dom):
        idx = AnchorIndex.from_r_anc(dom["m"][:40])
        q = idx.quantize("int8", tile=TILE)
        assert q.payload_dtype == "int8"
        assert q.payload_nbytes < 0.3 * idx.payload_nbytes
        assert q.quantize("int8", tile=TILE) is q        # idempotent
        b = idx.quantize("bfloat16")
        assert b.payload_dtype == "bfloat16"
        back = q.quantize("float32")
        assert back.payload_dtype == "float32"
        np.testing.assert_allclose(
            np.asarray(back.r_anc), np.asarray(quant.dequantize(q.r_anc))
        )


class TestPayloadPolicyWiring:
    def test_from_index_quantizes_once(self, dom):
        sf = dom["ce"].score_fn()
        idx = AnchorIndex.from_r_anc(dom["m"][:40])
        ret = AdaCURRetriever.from_index(idx, sf, CFG)
        assert ret.index.payload_dtype == "int8"
        # an already-quantized index is authoritative (no re-encode)
        ret2 = AdaCURRetriever.from_index(ret.index, sf, CFG)
        assert ret2.index is ret.index

    def test_quantized_index_is_authoritative(self, dom):
        """A policy mismatch never dequantizes an int8 artifact — the
        payload converts UP only, mirroring quant.as_payload."""
        sf = dom["ce"].score_fn()
        idx8 = AnchorIndex.from_r_anc(dom["m"][:40]).quantize("int8", tile=TILE)
        ret = AdaCURRetriever.from_index(
            idx8, sf, replace(CFG, payload_dtype="bfloat16")
        )
        assert ret.index is idx8

    def test_bare_r_anc_matches_prequantized_index(self, dom):
        """In-trace as_payload conversion == offline index quantization."""
        sf = dom["ce"].score_fn()
        key = jax.random.PRNGKey(3)
        res_bare = AdaCURRetriever(sf, dom["m"][:40], CFG).search(dom["test_q"], key)
        res_idx = AdaCURRetriever.from_index(
            AnchorIndex.from_r_anc(dom["m"][:40]), sf, CFG
        ).search(dom["test_q"], key)
        np.testing.assert_array_equal(
            np.asarray(res_bare.topk_idx), np.asarray(res_idx.topk_idx)
        )

    @pytest.mark.parametrize("fused", [False, True])
    def test_fused_vs_dense_under_int8(self, dom, fused):
        """Same payload -> same scores: fused and dense engines agree."""
        sf = dom["ce"].score_fn()
        cfg = replace(CFG, use_fused_topk=fused, fused_tile=128)
        res = AdaCURRetriever.from_index(
            AnchorIndex.from_r_anc(dom["m"][:40]), sf, cfg
        ).search(dom["test_q"], jax.random.PRNGKey(5))
        ref = AdaCURRetriever.from_index(
            AnchorIndex.from_r_anc(dom["m"][:40]), sf,
            replace(cfg, use_fused_topk=not fused),
        ).search(dom["test_q"], jax.random.PRNGKey(5))
        hits = (
            np.asarray(res.topk_idx)[:, :, None]
            == np.asarray(ref.topk_idx)[:, None, :]
        ).any(-1)
        assert hits.mean() >= 0.99

    def test_anncur_and_rerank_over_quantized_index(self, dom):
        sf = dom["ce"].score_fn()
        base = replace(CFG, use_fused_topk=True, fused_tile=128)
        idx = AnchorIndex.from_r_anc(dom["m"][:40]).with_anchors(
            k_anchor=10, key=jax.random.PRNGKey(7)
        )
        res = ANNCURRetriever.from_index(
            idx, sf, budget_ce=20, k_retrieve=10, base_cfg=base
        ).search(dom["test_q"])
        assert (np.asarray(res.topk_idx) >= 0).all()
        order = jnp.tile(jnp.arange(300)[None, :], (20, 1))
        res2 = RerankRetriever.from_index(
            idx, sf, budget_ce=20, k_retrieve=10, base_cfg=base
        ).search(dom["test_q"], candidate_idx=order)
        assert (np.asarray(res2.topk_idx) >= 0).all()

    def test_recall_parity_with_fp32(self, dom):
        """The headline acceptance property at test scale: int8 retrieval
        recall@10 tracks fp32 on the same seeds.  This 20-query domain has
        ~0.05 seed-to-seed recall noise, so the assertion averages three
        seeds with a matching tolerance; the bench asserts the tight 0.005
        bound at N=100k where the query sample is large."""
        from repro.core import retrieval

        sf = dom["ce"].score_fn()
        exact = dom["m"][40:]
        _, gt = retrieval.exact_topk(exact, 10)
        recalls = {"float32": [], "int8": []}
        for dtype, acc in recalls.items():
            cfg = replace(CFG, payload_dtype=dtype)
            ret = AdaCURRetriever.from_index(
                AnchorIndex.from_r_anc(dom["m"][:40]), sf, cfg
            )
            for seed in (11, 12, 13):
                res = ret.search(dom["test_q"], jax.random.PRNGKey(seed))
                acc.append(float(retrieval.topk_recall(res.topk_idx, gt, 10)))
        gap = abs(np.mean(recalls["int8"]) - np.mean(recalls["float32"]))
        assert gap <= 0.05, recalls


class TestQuantizedPersistence:
    def test_save_load_search_parity(self, dom, tmp_path):
        sf = dom["ce"].score_fn()
        index = AnchorIndex.from_r_anc(dom["m"][:40], capacity=320).quantize(
            "int8", tile=TILE
        )
        path = str(tmp_path / "qindex")
        index.save(path)
        meta = json.load(open(os.path.join(path, "index_meta.json")))
        assert meta["format_version"] == 2       # int8 keeps the v2 layout
        assert meta["payload"] == {
            "dtype": "int8", "tile": TILE, "code_dtype": "int8", "n_cols": -1,
        }
        loaded = AnchorIndex.load(path)
        assert loaded.payload_dtype == "int8"
        c0, s0 = _codes_scales(index)
        c1, s1 = _codes_scales(loaded)
        np.testing.assert_array_equal(c0, c1)
        np.testing.assert_array_equal(s0, s1)
        key = jax.random.PRNGKey(1)
        res_m = AdaCURRetriever.from_index(index, sf, CFG).search(dom["test_q"], key)
        res_l = AdaCURRetriever.from_index(loaded, sf, CFG).search(dom["test_q"], key)
        np.testing.assert_array_equal(
            np.asarray(res_m.topk_idx), np.asarray(res_l.topk_idx)
        )
        np.testing.assert_array_equal(
            np.asarray(res_m.topk_scores), np.asarray(res_l.topk_scores)
        )

    @pytest.mark.parametrize("dtype", ["int4", "fp8"])
    def test_v4_sub_int8_save_load_round_trip(self, dom, tmp_path, dtype):
        """Sub-int8 payloads stamp format v4, record code_dtype/n_cols in
        the meta, and round-trip codes+scales and search results exactly."""
        if dtype == "fp8" and not quant.fp8_supported():
            pytest.skip("no float8 in build")
        sf = dom["ce"].score_fn()
        index = AnchorIndex.from_r_anc(dom["m"][:40], capacity=320).quantize(
            dtype, tile=TILE
        )
        path = str(tmp_path / f"{dtype}index")
        index.save(path)
        meta = json.load(open(os.path.join(path, "index_meta.json")))
        assert meta["format_version"] == 4
        assert meta["payload"]["code_dtype"] == dtype
        assert meta["payload"]["tile"] == TILE
        loaded = AnchorIndex.load(path)
        assert loaded.payload_dtype == dtype
        c0, s0 = _codes_scales(index)
        c1, s1 = _codes_scales(loaded)
        # all code dtypes here are 1-byte; compare raw bits (fp8 included)
        np.testing.assert_array_equal(c0.view(np.uint8), c1.view(np.uint8))
        np.testing.assert_array_equal(s0, s1)
        key = jax.random.PRNGKey(1)
        cfg = replace(CFG, payload_dtype=dtype)
        res_m = AdaCURRetriever.from_index(index, sf, cfg).search(dom["test_q"], key)
        res_l = AdaCURRetriever.from_index(loaded, sf, cfg).search(dom["test_q"], key)
        np.testing.assert_array_equal(
            np.asarray(res_m.topk_idx), np.asarray(res_l.topk_idx)
        )
        np.testing.assert_array_equal(
            np.asarray(res_m.topk_scores), np.asarray(res_l.topk_scores)
        )

    def test_v1_artifacts_still_load(self, dom, tmp_path):
        index = AnchorIndex.from_r_anc(dom["m"][:40])
        path = str(tmp_path / "v1index")
        index.save(path)
        meta_path = os.path.join(path, "index_meta.json")
        meta = json.load(open(meta_path))
        meta["format_version"] = 1
        del meta["payload"]
        with open(meta_path, "w") as f:
            json.dump(meta, f)
        loaded = AnchorIndex.load(path)
        np.testing.assert_array_equal(
            np.asarray(loaded.r_anc), np.asarray(index.r_anc)
        )

    def test_quantized_latents_save_load(self, dom, tmp_path):
        index = (
            AnchorIndex.from_r_anc(dom["m"][:40])
            .quantize("int8", tile=TILE)
            .with_latents(k_anchor=8, key=jax.random.PRNGKey(2))
        )
        path = str(tmp_path / "qlat")
        index.save(path)
        loaded = AnchorIndex.load(path)
        np.testing.assert_array_equal(np.asarray(index.u), np.asarray(loaded.u))
        np.testing.assert_array_equal(
            np.asarray(index.item_embeddings), np.asarray(loaded.item_embeddings)
        )


class TestQuantizedSharding:
    def test_codes_scales_cosharded_topk_parity(self, dom):
        index = AnchorIndex.from_r_anc(dom["m"][:40], capacity=320).quantize(
            "int8", tile=TILE
        )
        mesh = make_mesh((1,), ("data",))
        sharded = index.shard(mesh)
        assert isinstance(sharded.r_anc, QuantizedRanc)
        # codes and scales carry matching item-axis placements (a 1-device
        # mesh reads back as unsharded; the real multi-shard co-sharding
        # parity runs in tests/multidevice_check.py with 8 host devices)
        codes_spec = sharded.r_anc.codes.sharding.spec
        scales_spec = sharded.r_anc.scales.sharding.spec
        # a one-axis spec entry reads back as the bare axis name
        assert codes_spec[0] is None and codes_spec[1] == "data"
        assert scales_spec[0] == "data"
        e_q = jax.random.normal(jax.random.PRNGKey(3), (5, 40))
        v0, i0 = index.topk(e_q, 8, tile=TILE)
        v1, i1 = sharded.topk(e_q, 8, tile=TILE)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), rtol=1e-5)

    def test_shard_aligns_capacity_to_whole_tiles(self, dom):
        index = AnchorIndex.from_r_anc(dom["m"][:40]).quantize("int8", tile=TILE)
        mesh = make_mesh((1,), ("data",))
        sharded = index.shard(mesh)                 # 300 -> 320 (5 tiles)
        assert sharded.capacity % TILE == 0
        assert sharded.r_anc.scales.shape[0] == sharded.capacity // TILE
        assert sharded.n_items == 300


class TestQuantizedMutation:
    def test_remove_add_round_trip_untouched_tiles_bit_identical(self, dom):
        m = dom["m"]
        index = AnchorIndex.from_r_anc(m[:40], capacity=320).quantize(
            "int8", tile=TILE
        )
        c0, s0 = _codes_scales(index)
        # remove the last 40 valid items (touched tiles start at col 260)
        shrunk = index.remove_items(jnp.arange(260, 300))
        c1, s1 = _codes_scales(shrunk)
        t0 = 260 // TILE                       # first touched tile
        np.testing.assert_array_equal(c1[:, : t0 * TILE], c0[:, : t0 * TILE])
        np.testing.assert_array_equal(s1[:t0], s0[:t0])
        # add them back: prefix tiles stay bit-identical through the cycle
        grown = shrunk.add_items(jnp.arange(260, 300), cols=m[:40, 260:300])
        c2, s2 = _codes_scales(grown)
        np.testing.assert_array_equal(c2[:, : t0 * TILE], c0[:, : t0 * TILE])
        np.testing.assert_array_equal(s2[:t0], s0[:t0])
        assert grown.n_items == 300
        np.testing.assert_array_equal(
            np.asarray(grown.item_ids), np.asarray(index.item_ids)
        )

    @pytest.mark.parametrize("dtype", ["int4", "fp8"])
    def test_sub_int8_remove_add_round_trip_bit_identical(self, dom, dtype):
        """The tile-local requantization contract holds below int8: a
        remove -> add cycle leaves every untouched tile's packed codes and
        scales bit-identical, and restores the touched region within the
        code grid's error bound."""
        if dtype == "fp8" and not quant.fp8_supported():
            pytest.skip("no float8 in build")
        m = dom["m"]
        index = AnchorIndex.from_r_anc(m[:40], capacity=320).quantize(
            dtype, tile=TILE
        )
        c0, s0 = _codes_scales(index)
        shrunk = index.remove_items(jnp.arange(260, 300))
        grown = shrunk.add_items(jnp.arange(260, 300), cols=m[:40, 260:300])
        c2, s2 = _codes_scales(grown)
        t0 = 260 // TILE                         # first touched tile
        kc = t0 * TILE // index.r_anc.packing    # prefix width in code cols
        np.testing.assert_array_equal(
            c2.view(np.uint8)[:, :kc], c0.view(np.uint8)[:, :kc]
        )
        np.testing.assert_array_equal(s2[:t0], s0[:t0])
        assert grown.n_items == 300
        np.testing.assert_array_equal(
            np.asarray(grown.item_ids), np.asarray(index.item_ids)
        )
        # restored columns reconstruct within half an ulp of their tile's
        # grid (int4: uniform lsb; fp8: ulp(448) = 32 code units at amax)
        deq = np.asarray(quant.dequantize(grown.r_anc))[:, 260:300]
        err = np.abs(deq - np.asarray(m[:40, 260:300]))
        s_max = float(np.asarray(s2[t0:]).max())
        assert err.max() <= s_max * (0.5 if dtype == "int4" else 16.0) + 1e-5

    def test_add_items_requantizes_only_touched_tiles(self, dom):
        m = dom["m"]
        index = AnchorIndex.from_r_anc(
            m[:40, :256], item_ids=jnp.arange(256), capacity=320
        ).quantize("int8", tile=TILE)
        c0, s0 = _codes_scales(index)
        grown = index.add_items(jnp.arange(256, 300), cols=m[:40, 256:300])
        c1, s1 = _codes_scales(grown)
        # valid prefix occupies tiles 0..3 exactly; only tile 4 changes
        np.testing.assert_array_equal(c1[:, :256], c0[:, :256])
        np.testing.assert_array_equal(s1[:4], s0[:4])
        # new columns reconstruct within the quantization error bound
        deq = np.asarray(quant.dequantize(grown.r_anc))[:, 256:300]
        err = np.abs(deq - np.asarray(m[:40, 256:300]))
        assert err.max() <= float(grown.r_anc.scales[4]) * 0.5 + 1e-6

    def test_mutation_never_retraces_quantized(self, dom):
        m = dom["m"]
        sf = dom["ce"].score_fn()
        traces = []

        def counting_sf(q, i):
            traces.append(1)
            return sf(q, i)

        index = AnchorIndex.from_r_anc(
            m[:40, :250], item_ids=jnp.arange(250), capacity=320
        ).quantize("int8", tile=TILE)
        ret = AdaCURRetriever.from_index(index, counting_sf, CFG)
        ret.search(dom["test_q"], jax.random.PRNGKey(1))
        n_traces = len(traces)
        assert n_traces > 0
        ret.index = index.add_items(jnp.arange(250, 300), cols=m[:40, 250:300])
        ret.search(dom["test_q"], jax.random.PRNGKey(1))
        ret.index = ret.index.remove_items(jnp.arange(10, 40))
        ret.search(dom["test_q"], jax.random.PRNGKey(2))
        assert len(traces) == n_traces, "quantized mutation retraced the engine"

    def test_removed_items_never_retrieved(self, dom):
        m = dom["m"]
        sf = dom["ce"].score_fn()
        index = AnchorIndex.from_r_anc(m[:40], capacity=320).quantize(
            "int8", tile=TILE
        )
        rm = jnp.arange(0, 50)
        shrunk = index.remove_items(rm)
        res = AdaCURRetriever.from_index(shrunk, sf, CFG).search(
            dom["test_q"], jax.random.PRNGKey(2)
        )
        got = np.asarray(shrunk.gather_item_ids(res.topk_idx))
        assert not np.isin(got, np.asarray(rm)).any()
        assert (got >= 0).all()


class TestQuantizedBuildAndService:
    def test_build_emits_quantized_payload(self, dom, tmp_path):
        ce = dom["ce"]
        idx = AnchorIndex.build(
            ce.score_block, dom["q_ids"], jnp.arange(300), block_rows=16,
            checkpoint_dir=str(tmp_path / "ck"),
            payload_dtype="int8", payload_tile=TILE,
        )
        assert idx.payload_dtype == "int8"
        ref = AnchorIndex.build(
            ce.score_block, dom["q_ids"], jnp.arange(300), block_rows=16
        ).quantize("int8", tile=TILE)
        c0, s0 = _codes_scales(idx)
        c1, s1 = _codes_scales(ref)
        np.testing.assert_array_equal(c0, c1)
        np.testing.assert_array_equal(s0, s1)

    def test_service_over_quantized_index_with_swap(self, dom):
        from repro.launch.serve import AdaCURService, RetrievalRequest

        m = dom["m"]
        index = AnchorIndex.from_r_anc(m[:40, :250], capacity=320).quantize(
            "int8", tile=TILE
        )
        svc = AdaCURService(
            score_fn=dom["ce"].score_fn(), cfg=CFG, index=index,
            max_batch=2, max_wait_s=10.0,
        )
        out = []
        for qid in (41, 42):
            out += svc.submit(RetrievalRequest(query_id=qid)) or []
        assert len(out) == 2
        assert all((r.item_ids < 250).all() for r in out)
        svc.swap_index(svc.index.add_items(jnp.arange(250, 300),
                                           cols=m[:40, 250:300]))
        out2 = []
        for qid in (43, 44):
            out2 += svc.submit(RetrievalRequest(query_id=qid)) or []
        assert len(out2) == 2
