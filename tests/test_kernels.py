"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all against the pure-jnp ref.py oracles, in interpret mode (CPU executes
the kernel bodies; Mosaic lowering is the TPU target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # hermetic container: deterministic fallback
    from _hypothesis_fallback import given, settings, st
from numpy.testing import assert_allclose

from repro.kernels.approx_topk import quant
from repro.kernels.approx_topk.ops import approx_topk_op
from repro.kernels.approx_topk.persistent import persistent_round_op
from repro.kernels.approx_topk.ref import approx_topk_reference
from repro.core.sampling import blocked_gumbel
from repro.kernels.embedding_bag.ops import embedding_bag_op
from repro.kernels.embedding_bag.ref import embedding_bag_reference
from repro.core import telemetry
from repro.kernels.flash_attention.kernel import flash_attention, pairs_per_step
from repro.kernels.flash_attention.ref import attention_reference


class TestFlashAttention:
    @pytest.mark.parametrize(
        "b,lq,lk,h,kv,hd,causal",
        [
            (2, 128, 128, 4, 2, 32, True),     # GQA 2:1
            (1, 256, 256, 4, 4, 64, True),     # MHA
            (2, 100, 100, 2, 1, 16, False),    # MQA, bidir, ragged tail
            (1, 64, 192, 2, 2, 32, True),      # decode-chunk (Lk > Lq)
            (1, 128, 128, 8, 2, 128, True),    # GQA 4:1, MXU-width head
        ],
    )
    def test_matches_reference(self, b, lq, lk, h, kv, hd, causal):
        ks = jax.random.split(jax.random.PRNGKey(b * lq + lk), 3)
        q = jax.random.normal(ks[0], (b, lq, h, hd), jnp.float32)
        k = jax.random.normal(ks[1], (b, lk, kv, hd), jnp.float32)
        v = jax.random.normal(ks[2], (b, lk, kv, hd), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
        ref = attention_reference(q, k, v, causal=causal)
        assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, 128, 2, 32)).astype(dtype)
        k = jax.random.normal(ks[1], (1, 128, 2, 32)).astype(dtype)
        v = jax.random.normal(ks[2], (1, 128, 2, 32)).astype(dtype)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        ref = attention_reference(q, k, v, causal=True)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
        )

    @settings(max_examples=8, deadline=None)
    @given(
        lq=st.integers(16, 130),
        h=st.sampled_from([2, 4]),
        kv=st.sampled_from([1, 2]),
        hd=st.sampled_from([16, 32]),
        causal=st.booleans(),
        seed=st.integers(0, 1000),
    )
    def test_property_random_shapes(self, lq, h, kv, hd, causal, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (1, lq, h, hd), jnp.float32)
        k = jax.random.normal(ks[1], (1, lq, kv, hd), jnp.float32)
        v = jax.random.normal(ks[2], (1, lq, kv, hd), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32, interpret=True)
        ref = attention_reference(q, k, v, causal=causal)
        assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)

    @settings(max_examples=8, deadline=None)
    @given(
        l=st.integers(17, 96),
        h=st.sampled_from([2, 4]),
        causal=st.booleans(),
        seed=st.integers(0, 1000),
    )
    def test_varlen_kv_lens_matches_masked_reference(self, l, h, causal, seed):
        """Per-example SMEM valid lengths (the CE bucket-padding path): the
        kernel must equal dense attention over each row's valid prefix, at
        every valid query position."""
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        b, hd = 3, 16
        q = jax.random.normal(ks[0], (b, l, h, hd), jnp.float32)
        k = jax.random.normal(ks[1], (b, l, h, hd), jnp.float32)
        v = jax.random.normal(ks[2], (b, l, h, hd), jnp.float32)
        lens = jax.random.randint(ks[3], (b,), 1, l + 1)
        out = flash_attention(
            q, k, v, causal=causal, block_q=32, block_k=32, interpret=True,
            kv_lens=lens,
        )
        for i in range(b):
            n = int(lens[i])
            ref = attention_reference(
                q[i : i + 1, :n], k[i : i + 1, :n], v[i : i + 1, :n],
                causal=causal,
            )
            assert_allclose(
                np.asarray(out[i, :n]), np.asarray(ref[0]), atol=3e-5, rtol=3e-5
            )

    # the whole-sequence schedule: bidirectional pairs at the served head
    # layouts (BERT-base 12 x 64 in 256 tokens, MiniLM-L6 12 x 32 in 128)
    @pytest.mark.parametrize(
        "b,l,h,kv,hd,dtype,ragged",
        [
            (4, 256, 12, 12, 64, jnp.float32, False),
            (4, 256, 12, 12, 64, jnp.bfloat16, False),
            (8, 128, 12, 12, 32, jnp.float32, False),
            (8, 128, 12, 12, 32, jnp.bfloat16, False),
            (4, 256, 12, 12, 64, jnp.bfloat16, True),
            (8, 128, 12, 12, 32, jnp.float32, True),
            (4, 128, 8, 2, 32, jnp.float32, True),     # GQA 4:1
            (5, 128, 12, 12, 32, jnp.float32, True),   # 5 pairs in blocks of 4
        ],
    )
    def test_whole_sequence_matches_reference(self, b, l, h, kv, hd, dtype, ragged):
        ks = jax.random.split(jax.random.PRNGKey(b * l + hd), 4)
        q = jax.random.normal(ks[0], (b, l, h, hd)).astype(dtype)
        k = jax.random.normal(ks[1], (b, l, kv, hd)).astype(dtype)
        v = jax.random.normal(ks[2], (b, l, kv, hd)).astype(dtype)
        lens = (jax.random.randint(ks[3], (b,), 1, l + 1) if ragged
                else jnp.full((b,), l, jnp.int32))
        assert pairs_per_step(b, l, l, h, kv, hd, jnp.dtype(dtype).itemsize) > 0
        out = flash_attention(q, k, v, causal=False, kv_lens=lens, interpret=True)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        for i in range(b):
            n = int(lens[i])
            ref = attention_reference(
                q[i : i + 1, :n], k[i : i + 1, :n], v[i : i + 1, :n], causal=False
            )
            assert_allclose(
                np.asarray(out[i, :n], np.float32), np.asarray(ref[0], np.float32),
                atol=tol, rtol=tol,
            )

    @pytest.mark.parametrize(
        "b,l,h,hd,causal,schedule",
        [
            (1600, 256, 12, 64, False, "whole_sequence"),   # BERT-base CE rerank
            (320, 128, 12, 32, False, "whole_sequence"),    # MiniLM-L6 CE round
            (1600, 256, 12, 64, True, "online_softmax"),
            (2, 2048, 12, 64, False, "online_softmax"),     # KV past VMEM
        ],
    )
    def test_schedule_choice(self, b, l, h, hd, causal, schedule):
        """The shape picks the schedule, and each traced call says which in
        one ``flash.schedule`` mark; the online-softmax grid is the
        (B*H, Lq/128, Lk/128) one."""
        qkv = jax.ShapeDtypeStruct((b, l, h, hd), jnp.bfloat16)
        lens = jax.ShapeDtypeStruct((b,), jnp.int32)
        telemetry.reset()
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, n: flash_attention(
                q, k, v, causal=causal, kv_lens=n, interpret=True)
        )(qkv, qkv, qkv, lens)
        marks = [m for m in telemetry.snapshot().marks if m.name == "flash.schedule"]
        assert [m.attrs for m in marks] == [
            {"schedule": schedule, "pairs": b, "seq": l, "heads": h}]
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        bp = pairs_per_step(b, l, l, h, h, hd, 2)
        if schedule == "whole_sequence":
            assert bp > 0 and b % bp == 0
            assert call.params["grid_mapping"].grid == (b // bp,)
        else:
            assert causal or bp == 0
            assert call.params["grid_mapping"].grid == (b * h, l // 128, l // 128)


def _anchor_mask(anchors, n):
    """(B, N) bool selected-mask of a (B, A) anchor-id list."""
    rows = jnp.arange(anchors.shape[0])[:, None]
    return jnp.zeros((anchors.shape[0], n), bool).at[rows, anchors].set(True)


class TestApproxTopK:
    @pytest.mark.parametrize("impl", ["pallas", "scan"])
    @pytest.mark.parametrize(
        "b,kq,n,a,k,tile",
        [(4, 64, 2048, 16, 32, 256), (2, 100, 999, 8, 10, 128), (1, 32, 5000, 4, 64, 512)],
    )
    def test_matches_reference(self, b, kq, n, a, k, tile, impl):
        ks = jax.random.split(jax.random.PRNGKey(n + k), 3)
        e_q = jax.random.normal(ks[0], (b, kq))
        r = jax.random.normal(ks[1], (kq, n))
        anchors = jax.random.randint(ks[2], (b, a), 0, n)
        mask = _anchor_mask(anchors, n)
        v1, i1 = approx_topk_op(e_q, r, k, tile=tile, interpret=True, impl=impl,
                                mask=mask)
        v2, i2 = approx_topk_reference(e_q, r, k, mask=mask)
        assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-4, rtol=1e-4)
        # fused and dense rankings are BIT-equal, not merely set-equal:
        # per-column dots agree bitwise and ties break by ascending index
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        # anchor masking property: no returned id may be a masked anchor
        hits = (np.asarray(i1)[:, :, None] == np.asarray(anchors)[:, None, :]).any()
        assert not hits

    @pytest.mark.parametrize("impl", ["pallas", "scan"])
    def test_gumbel_noise_input(self, impl):
        """SoftMax sampling path: scores + Gumbel noise, S_hat never formed."""
        ks = jax.random.split(jax.random.PRNGKey(5), 4)
        e_q = jax.random.normal(ks[0], (3, 40))
        r = jax.random.normal(ks[1], (40, 1200))
        anchors = jax.random.randint(ks[2], (3, 8), 0, 1200)
        g = jax.random.gumbel(ks[3], (3, 1200), dtype=jnp.float32)
        mask = _anchor_mask(anchors, 1200)
        v1, i1 = approx_topk_op(e_q, r, 16, tile=256, interpret=True,
                                noise=g, mask=mask, impl=impl)
        v2, i2 = approx_topk_reference(e_q, r, 16, noise=g, mask=mask)
        assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-4, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    @pytest.mark.parametrize("impl", ["pallas", "scan"])
    def test_dense_mask_and_n_valid(self, impl):
        ks = jax.random.split(jax.random.PRNGKey(6), 4)
        e_q = jax.random.normal(ks[0], (2, 32))
        r = jax.random.normal(ks[1], (32, 900))
        mask = jax.random.bernoulli(ks[2], 0.2, (2, 900))
        v1, i1 = approx_topk_op(e_q, r, 12, tile=128, interpret=True,
                                mask=mask, n_valid=800, impl=impl)
        v2, i2 = approx_topk_reference(e_q, r, 12, mask=mask, n_valid=800)
        assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-4, rtol=1e-4)
        assert (np.asarray(i1) < 800).all()
        assert not np.asarray(jnp.take_along_axis(mask, i1, axis=1)).any()

    @pytest.mark.parametrize("impl", ["pallas", "scan"])
    def test_exact_tie_break_by_ascending_index(self, impl):
        """Exact score ties (integer-valued inputs: the GEMM is exact) must
        resolve deterministically by ascending item index, bit-equal to the
        dense reference — within a tile, across tiles, and in the merge."""
        kq, n, k = 8, 777, 40
        e_q = jnp.ones((2, kq), jnp.float32)
        # scores cycle through 4 exact levels -> ~194 exact ties per level
        levels = jnp.arange(n, dtype=jnp.float32) % 4
        r = jnp.broadcast_to(levels[None, :], (kq, n))
        v1, i1 = approx_topk_op(e_q, r, k, tile=128, interpret=True,
                                impl=impl)
        v2, i2 = approx_topk_reference(e_q, r, k)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        # the contract itself: equal-valued winners appear in id order
        i1, v1 = np.asarray(i1), np.asarray(v1)
        for row_v, row_i in zip(v1, i1):
            for lvl in np.unique(row_v):
                ids = row_i[row_v == lvl]
                assert (np.diff(ids) > 0).all(), (lvl, ids)

    @pytest.mark.parametrize("impl", ["pallas", "scan"])
    def test_quantized_payload_matches_reference(self, impl):
        """int8 payload: fused dequant-matmul == dequantized dense oracle,
        bit-equal rankings, and the payload really is ~4x smaller."""
        ks = jax.random.split(jax.random.PRNGKey(11), 3)
        e_q = jax.random.normal(ks[0], (3, 48))
        r = jax.random.normal(ks[1], (48, 1333))
        p = quant.quantize_ranc(r, tile=96)
        assert p.nbytes < 0.3 * r.nbytes
        # quantization error bound: half an lsb per entry
        err = jnp.abs(quant.dequantize(p) - r)
        assert float(err.max()) <= float(p.col_scales().max()) * 0.5 + 1e-6
        mask = _anchor_mask(jax.random.randint(ks[2], (3, 6), 0, 1333), 1333)
        v1, i1 = approx_topk_op(e_q, p, 16, tile=256, interpret=True,
                                mask=mask, impl=impl)
        v2, i2 = approx_topk_reference(e_q, p, 16, mask=mask)
        assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    @pytest.mark.parametrize("impl", ["pallas", "scan"])
    def test_quantized_payload_with_noise_mask_n_valid(self, impl):
        """The full input surface (Gumbel noise + dense mask + n_valid)
        composes with the quantized payload identically to the oracle."""
        ks = jax.random.split(jax.random.PRNGKey(12), 4)
        e_q = jax.random.normal(ks[0], (2, 32))
        p = quant.quantize_ranc(jax.random.normal(ks[1], (32, 900)), tile=128)
        mask = jax.random.bernoulli(ks[2], 0.2, (2, 900))
        g = jax.random.gumbel(ks[3], (2, 900), dtype=jnp.float32)
        v1, i1 = approx_topk_op(e_q, p, 12, tile=128, interpret=True,
                                noise=g, mask=mask, n_valid=800, impl=impl)
        v2, i2 = approx_topk_reference(
            e_q, p, 12, noise=g, mask=mask, n_valid=800,
        )
        assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        assert (np.asarray(i1) < 800).all()

    def test_descending_and_unique(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        e_q = jax.random.normal(ks[0], (3, 48))
        r = jax.random.normal(ks[1], (48, 1500))
        v, i = approx_topk_op(e_q, r, 20, tile=256, interpret=True)
        v = np.asarray(v)
        assert (np.diff(v, axis=1) <= 1e-6).all()
        for row in np.asarray(i):
            assert len(np.unique(row)) == len(row)

    @settings(max_examples=8, deadline=None)
    @given(
        n=st.integers(100, 3000),
        k=st.sampled_from([8, 16, 33]),
        seed=st.integers(0, 1000),
    )
    def test_property_matches_reference(self, n, k, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        e_q = jax.random.normal(ks[0], (2, 32))
        r = jax.random.normal(ks[1], (32, n))
        mask = _anchor_mask(jax.random.randint(ks[2], (2, 6), 0, n), n)
        v1, _ = approx_topk_op(e_q, r, k, tile=256, interpret=True, mask=mask)
        v2, _ = approx_topk_reference(e_q, r, k, mask=mask)
        assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-4, rtol=1e-4)


def _persistent_dtypes():
    return ["float32", "int8", "int4"] + (["fp8"] if quant.fp8_supported() else [])


class TestPersistentRound:
    """The persistent round kernel streams each payload tile ONCE and
    produces both per-round top-ks (Gumbel sample + provisional monitor).
    Its contract is bitwise: both outputs equal the staged two-pass
    approx_topk_op calls exactly, for every payload dtype and backend."""

    B, KQ, N = 5, 12, 900

    @pytest.fixture(scope="class")
    def dom(self):
        key = jax.random.PRNGKey(0)
        nkey = jax.random.fold_in(key, 6)
        return {
            "e_q": jax.random.normal(jax.random.fold_in(key, 1), (self.B, self.KQ)),
            "r": jax.random.normal(jax.random.fold_in(key, 2), (self.KQ, self.N)),
            "mask": jax.random.bernoulli(
                jax.random.fold_in(key, 4), 0.1, (self.B, self.N)
            ),
            "prov_mask": jax.random.bernoulli(
                jax.random.fold_in(key, 5), 0.2, (self.B, self.N)
            ),
            "nkey": nkey,
            "noise": blocked_gumbel(nkey, self.B, self.N),
        }

    def _payload(self, dom, dt):
        if dt == "float32":
            return dom["r"]
        return quant.quantize_ranc(dom["r"], tile=128, code_dtype=dt)

    @staticmethod
    def _bitwise(got, want):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))

    @pytest.mark.parametrize("impl", ["scan", "pallas"])
    @pytest.mark.parametrize("dtype", _persistent_dtypes())
    @pytest.mark.parametrize("n_valid", [None, 700])
    def test_dual_output_bitwise_vs_staged(self, dom, dtype, impl, n_valid):
        p = self._payload(dom, dtype)
        ref_s = approx_topk_op(dom["e_q"], p, 20, tile=256,
                               noise=dom["noise"], mask=dom["mask"],
                               n_valid=n_valid)
        ref_p = approx_topk_op(dom["e_q"], p, 15, tile=256,
                               mask=dom["prov_mask"], n_valid=n_valid)
        s, prov = persistent_round_op(
            dom["e_q"], p, k_sample=20, k_prov=15,
            mask=dom["mask"], prov_mask=dom["prov_mask"], noise=dom["noise"],
            n_valid=n_valid, tile=256, interpret=True, impl=impl,
        )
        self._bitwise(s, ref_s)
        self._bitwise(prov, ref_p)

    @pytest.mark.parametrize("impl", ["scan", "pallas"])
    @pytest.mark.parametrize("dtype", _persistent_dtypes())
    def test_in_kernel_noise_generation_bitwise(self, dom, dtype, impl):
        """noise_key path: the kernel regenerates blocked_gumbel per tile
        from global coordinates — identical to passing the full field."""
        p = self._payload(dom, dtype)
        ref_s = approx_topk_op(dom["e_q"], p, 20, tile=256,
                               noise=dom["noise"], mask=dom["mask"])
        ref_p = approx_topk_op(dom["e_q"], p, 15, tile=256,
                               mask=dom["prov_mask"])
        s, prov = persistent_round_op(
            dom["e_q"], p, k_sample=20, k_prov=15, mask=dom["mask"],
            prov_mask=dom["prov_mask"], noise_key=dom["nkey"], tile=256,
            interpret=True, impl=impl,
        )
        self._bitwise(s, ref_s)
        self._bitwise(prov, ref_p)

    @pytest.mark.parametrize("impl", ["scan", "pallas"])
    def test_prov_only_and_fully_masked(self, dom, impl):
        ref_p = approx_topk_op(dom["e_q"], dom["r"], 15, tile=256,
                               mask=dom["prov_mask"])
        _, prov = persistent_round_op(
            dom["e_q"], dom["r"], k_prov=15, prov_mask=dom["prov_mask"],
            tile=256, interpret=True, impl=impl,
        )
        self._bitwise(prov, ref_p)
        # degenerate: every item masked — sentinel fill must match staged
        full = jnp.ones((self.B, self.N), bool)
        ref = approx_topk_op(dom["e_q"], dom["r"], 10, tile=256, mask=full)
        s, _ = persistent_round_op(dom["e_q"], dom["r"], k_sample=10, mask=full,
                                   tile=256, interpret=True, impl=impl)
        self._bitwise(s, ref)

    @pytest.mark.parametrize("impl", ["scan", "pallas"])
    @pytest.mark.parametrize("tile", [1024, 64])
    def test_degenerate_tile_sizes(self, dom, impl, tile):
        """Single-tile (tile >= N) and tiny-tile sweeps.  Compared against
        the SAME staged backend: at tile > N the two staged backends
        themselves drift an ulp on quantized payloads (a pre-existing
        scan-vs-pallas FMA fusion corner), so cross-impl comparison would
        test the staged kernels, not the persistent one."""
        p = self._payload(dom, "int4")
        ref_s = approx_topk_op(dom["e_q"], p, 20, tile=tile, mask=dom["mask"],
                               noise=dom["noise"], impl=impl, interpret=True)
        s, _ = persistent_round_op(dom["e_q"], p, k_sample=20,
                                   mask=dom["mask"], noise=dom["noise"],
                                   tile=tile, interpret=True, impl=impl)
        self._bitwise(s, ref_s)

    @pytest.mark.parametrize("impl", ["scan", "pallas"])
    def test_shard_offsets_noise_parity(self, dom, impl):
        """Sharded-style (row_offset, col_offset) in-kernel noise equals a
        slice of the globally-keyed field — the property that makes the
        sharded persistent engine bit-identical to single-device."""
        ro, co = 3, 256
        big = blocked_gumbel(dom["nkey"], self.B + ro, self.N + co)
        ref = approx_topk_op(dom["e_q"], dom["r"], 20, mask=dom["mask"],
                             tile=256, noise=big[ro:, co:])
        s, _ = persistent_round_op(
            dom["e_q"], dom["r"], k_sample=20, mask=dom["mask"],
            noise_key=dom["nkey"], row_offset=ro, col_offset=co,
            tile=256, interpret=True, impl=impl,
        )
        self._bitwise(s, ref)


class TestEmbeddingBag:
    @pytest.mark.parametrize(
        "rows,dim,b,h,mode",
        [(1000, 128, 8, 4, "sum"), (500, 64, 16, 7, "mean"), (100, 256, 3, 1, "sum")],
    )
    def test_matches_reference(self, rows, dim, b, h, mode):
        k1, k2 = jax.random.split(jax.random.PRNGKey(rows))
        table = jax.random.normal(k1, (rows, dim))
        ids = jax.random.randint(k2, (b, h), 0, rows)
        out = embedding_bag_op(table, ids, mode=mode, interpret=True)
        ref = embedding_bag_reference(table, ids, mode)
        assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        table = jax.random.normal(k1, (200, 64)).astype(dtype)
        ids = jax.random.randint(k2, (4, 5), 0, 200)
        out = embedding_bag_op(table, ids, interpret=True)
        ref = embedding_bag_reference(table, ids)
        assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
        )

    @settings(max_examples=10, deadline=None)
    @given(
        rows=st.integers(10, 500),
        h=st.integers(1, 9),
        seed=st.integers(0, 1000),
    )
    def test_property_duplicate_ids_ok(self, rows, h, seed):
        """Bags with repeated ids must sum the row multiple times."""
        k1 = jax.random.PRNGKey(seed)
        table = jax.random.normal(k1, (rows, 32))
        ids = jnp.zeros((2, h), jnp.int32)  # all duplicates of row 0
        out = embedding_bag_op(table, ids, interpret=True)
        ref = table[0] * h
        assert_allclose(np.asarray(out[0]), np.asarray(ref), atol=1e-4, rtol=1e-4)
