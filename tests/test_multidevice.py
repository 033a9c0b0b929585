"""Multi-device numerical equivalence checks — TIER-1, parametrized.

Every distributed execution path must produce the same numbers as its
single-device reference.  jax locks the device count at backend init, so
the main pytest process (which must see the one real CPU device — see
conftest.py) cannot host these: each parametrized case re-executes THIS
FILE as a subprocess whose environment — built by the ``forced_devices``
fixture — pins ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Checks (the ``CHECKS`` registry; run one directly with
``XLA_FLAGS=--xla_force_host_platform_device_count=8 python
tests/test_multidevice.py <name>``):

- sequence-parallel decode attention (LSE combine) == local decode core
- expert-parallel MoE (shard_map)                  == local MoE
- channel-TP receiver-partitioned GNN interact     == local interact
- pipeline_forward (GPipe over an axis)            == plain stage chain
- int8 hierarchical cross-pod grad reduce: mean parity + error feedback
- AnchorIndex.shard(mesh) top-k (fused per-shard + cross-shard merge)
  == the unsharded index, fp32 and int8 co-sharded payloads
- the FULL SPMD engine (engine.make_sharded_engine) on a (data x items)
  mesh: bit-identical top-k vs the single-device engine across loop modes
  x payload dtypes x a mutated padded-capacity index; the persistent
  round kernel + int4/fp8 payloads on a 2x2 mesh (bit-equal to BOTH the
  single-device persistent engine and the sharded staged engine,
  including the software-pipelined monitored loop); the property-suite
  invariants (no pair CE-scored twice, measured == planned calls) under a
  2x2 mesh; zero retraces across runtime n_rounds; first-stage candidate
  restriction (a per-query ``eligible`` mask sharded over the mesh ==
  the single-device masked engine, bit-identical); and a golden snapshot
  (tests/golden/engine_sharded.json, regenerate with GOLDEN_REGEN=1).
"""

import json
import os
import subprocess
import sys

import pytest

_THIS = os.path.abspath(__file__)
_ROOT = os.path.dirname(os.path.dirname(_THIS))
GOLDEN_SHARDED = os.path.join(os.path.dirname(_THIS), "golden", "engine_sharded.json")

TOL = dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# check implementations — run ONLY in the 8-device subprocess
# ---------------------------------------------------------------------------


def check_decode_attention():
    import jax, jax.numpy as jnp, numpy as np

    from repro.distributed import decode_attention
    from repro.models import transformer

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    b, s, kv, h, hd = 4, 64, 2, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (b, h, hd))
    k_new = jax.random.normal(ks[1], (b, kv, hd))
    v_new = jax.random.normal(ks[2], (b, kv, hd))
    ck = jax.random.normal(ks[3], (b, s, kv, hd))
    cv = jax.random.normal(ks[4], (b, s, kv, hd))
    pos = jnp.int32(37)

    ref_o, ref_ck, ref_cv = transformer._local_decode_core(q, k_new, v_new, ck, cv, pos)
    core = decode_attention.make_decode_core(mesh, ("data",), ("model",), s)
    with mesh:
        o, ck2, cv2 = jax.jit(core)(q, k_new, v_new, ck, cv, pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref_o), **TOL)
    np.testing.assert_allclose(np.asarray(ck2), np.asarray(ref_ck), **TOL)
    np.testing.assert_allclose(np.asarray(cv2), np.asarray(ref_cv), **TOL)

    # seq sharded over BOTH axes (the long_500k layout), batch unsharded
    core2 = decode_attention.make_decode_core(mesh, (), ("data", "model"), s)
    with mesh:
        o2, _, _ = jax.jit(core2)(q, k_new, v_new, ck, cv, pos)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(ref_o), **TOL)


def check_moe_ep():
    import jax, jax.numpy as jnp, numpy as np

    from repro.configs.base import MoEConfig
    from repro.models import layers, moe as moe_lib

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    cfg = MoEConfig(n_experts=8, top_k=2, d_expert=16)
    params, _ = layers.split_tree(moe_lib.moe_init(jax.random.PRNGKey(0), 12, cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 12))
    y_ref, _ = moe_lib.moe_apply_local(params, x, cfg, capacity_factor=8.0)
    # EP computes the aux loss per data GROUP (GShard's per-group definition):
    # the reference is the mean of per-shard auxes, not the global aux.
    n_dp = mesh.shape["data"]
    aux_ref = np.mean([
        float(moe_lib.moe_apply_local(params, xs, cfg, capacity_factor=8.0)[1])
        for xs in jnp.split(x, n_dp)
    ])
    moe_fn = moe_lib.make_moe_fn(mesh, cfg, ("data",), "model", capacity_factor=8.0)
    with mesh:
        y, aux = jax.jit(moe_fn)(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **TOL)
    np.testing.assert_allclose(float(aux), aux_ref, rtol=1e-4)


def check_gnn_interact():
    import dataclasses

    import jax, jax.numpy as jnp, numpy as np

    from repro.configs import registry
    from repro.models.gnn import nequip

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    cfg = dataclasses.replace(registry.smoke_config("nequip"), d_hidden=8)
    params, _ = nequip.init_nequip(jax.random.PRNGKey(0), cfg)
    h = 8
    n_per, n_shards = 8, mesh.shape["data"]
    n = n_per * n_shards
    e_per = 16
    e = e_per * n_shards
    pos = jax.random.normal(jax.random.PRNGKey(3), (n, 3)) * 2
    # receiver-partitioned edges: shard i's receivers live in its node range
    recv = jnp.concatenate([
        jax.random.randint(jax.random.PRNGKey(10 + i), (e_per,), i * n_per, (i + 1) * n_per)
        for i in range(n_shards)
    ])
    send = jax.random.randint(jax.random.PRNGKey(4), (e,), 0, n)
    feats = {
        "s": jax.random.normal(jax.random.PRNGKey(5), (n, h)),
        "v": jax.random.normal(jax.random.PRNGKey(6), (n, h, 3)) * 0.1,
        "t": jax.random.normal(jax.random.PRNGKey(7), (n, h, 3, 3)) * 0.1,
    }
    rhat, y2, rbf = nequip._edge_geometry(pos, send, recv, cfg)
    lp = params["layers"][0]
    ref = nequip._interact(lp, feats, send, recv, rhat, y2, rbf, n, h)
    interact = nequip.make_sharded_interact(mesh, "data", "model")
    with mesh:
        out = jax.jit(
            lambda *a: interact(*a)
        )(lp, feats, send, recv, rhat, y2, rbf, n, h)
    for k in ("s", "v", "t"):
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]), **TOL)


def check_pipeline():
    import jax, jax.numpy as jnp, numpy as np

    from repro.distributed import pipeline

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    n_stages = mesh.shape["data"]
    d = 6
    keys = jax.random.split(jax.random.PRNGKey(0), n_stages)
    ws = jnp.stack([jax.random.normal(k, (d, d)) / jnp.sqrt(d) for k in keys])

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
    ref = x
    for i in range(n_stages):
        ref = stage_fn(ws[i], ref)
    piped = pipeline.pipeline_forward(mesh, stage_fn, "data", n_microbatches=4)
    with mesh:
        out = jax.jit(piped)(ws, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def check_cross_pod_reduce():
    """int8 hierarchical cross-pod grad reduce: mean parity + error-feedback
    convergence over repeated steps (multi-pod mesh (2, 2, 2))."""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    g_pod = [
        {"w": jax.random.normal(jax.random.PRNGKey(i), (8, 8))} for i in range(2)
    ]
    true_mean = {"w": (g_pod[0]["w"] + g_pod[1]["w"]) / 2}
    full = {"w": jnp.stack([g_pod[0]["w"], g_pod[1]["w"]])}   # (2, 8, 8)

    # shared-scale int8 reduce (mirrors cross_pod.make_hierarchical_grad_reduce)
    def cross_pod_body(g, e):
        def one(gl, el):
            g32 = gl.astype(jnp.float32) + el
            scale = jax.lax.pmax(jnp.max(jnp.abs(g32)), "pod") / 127.0 + 1e-12
            q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
            q_sum = jax.lax.psum(q.astype(jnp.int32), "pod")
            deq = q_sum.astype(jnp.float32) * scale / 2
            return deq, g32 - q.astype(jnp.float32) * scale
        pairs = jax.tree.map(one, g, e)
        return (
            jax.tree.map(lambda t: t[0], pairs, is_leaf=lambda x: isinstance(x, tuple)),
            jax.tree.map(lambda t: t[1], pairs, is_leaf=lambda x: isinstance(x, tuple)),
        )

    def driver(full_g, err):
        def body(gp, e):
            g = {"w": gp["w"][0]}          # this pod's partial
            out, new_e = cross_pod_body(g, e)
            return out, new_e
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=({"w": P("pod", "data", "model")}, {"w": P("data", "model")}),
            out_specs=({"w": P("data", "model")}, {"w": P("data", "model")}),
            check_vma=False,
        )(full_g, err)

    err = {"w": jnp.zeros((8, 8))}
    total_true = jnp.zeros((8, 8))
    total_comp = jnp.zeros((8, 8))
    with mesh:
        for _ in range(10):
            out, err = jax.jit(driver)(full, err)
            total_true += true_mean["w"]
            total_comp += out["w"]
    rel = float(jnp.abs(total_comp - total_true).max() / jnp.abs(total_true).max())
    assert rel < 0.05, rel


def check_anchor_index_shard():
    """shard(mesh) parity on a legacy ("data", "model") training mesh: the
    fused per-shard top-k + cross-shard merge AND the full engine (which
    auto-binds the SPMD engine with items over BOTH axes, batch replicated)
    must equal the unsharded index."""
    import jax, jax.numpy as jnp, numpy as np

    from repro.configs.base import AdaCURConfig
    from repro.core.engine import AdaCURRetriever
    from repro.core.index import AnchorIndex
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    r = jax.random.normal(jax.random.PRNGKey(0), (24, 1000))
    index = AnchorIndex.from_r_anc(r, capacity=1024)   # padded, n_valid=1000
    sharded = index.shard(mesh)
    det_mesh, det_axes = sharded._item_sharding()
    assert det_axes == ("data", "model"), det_axes
    assert det_mesh is not None

    # the placement must survive mutation (it lives in the NamedSharding)
    mutated = sharded.add_items(jnp.arange(1000, 1010),
                                cols=jnp.zeros((24, 10)))
    assert mutated._item_sharding()[1] == ("data", "model")

    # (a) latent top-k: per-shard fused approx_topk + all-gather merge
    e_q = jax.random.normal(jax.random.PRNGKey(1), (5, 24))
    v0, i0 = index.topk(e_q, 10, tile=128)
    v1, i1 = sharded.topk(e_q, 10, tile=128)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), **TOL)

    # (b) the full multi-round engine over the sharded index (shard_map SPMD)
    def score_fn(q, idx):
        return jnp.take(r, idx, axis=1).mean(axis=0) + 0.01 * q[:, None]

    cfg = AdaCURConfig(k_anchor=20, n_rounds=4, budget_ce=40, k_retrieve=10,
                       loop_mode="fori")
    q = jnp.arange(5, dtype=jnp.float32)
    res_h = AdaCURRetriever.from_index(index, score_fn, cfg).search(
        q, jax.random.PRNGKey(2)
    )
    ret_s = AdaCURRetriever.from_index(sharded, score_fn, cfg)
    assert ret_s._sharded
    res_s = ret_s.search(q, jax.random.PRNGKey(2))
    np.testing.assert_array_equal(
        np.asarray(res_h.topk_idx), np.asarray(res_s.topk_idx)
    )
    np.testing.assert_array_equal(
        np.asarray(res_h.topk_scores), np.asarray(res_s.topk_scores)
    )


def check_quantized_index_shard():
    """shard(mesh) on an int8 payload: codes and scales must land co-sharded
    on the item axis (whole quantization tiles per shard), and the sharded
    fused-dequant top-k must match the unsharded quantized index exactly."""
    import jax, jax.numpy as jnp, numpy as np

    from repro.core.index import AnchorIndex
    from repro.kernels.approx_topk.quant import QuantizedRanc
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    tile = 16
    r = jax.random.normal(jax.random.PRNGKey(0), (24, 1000))
    index = AnchorIndex.from_r_anc(r, capacity=1024).quantize("int8", tile=tile)
    sharded = index.shard(mesh)
    assert isinstance(sharded.r_anc, QuantizedRanc)
    assert sharded._item_sharding()[1] == ("data", "model"), (
        sharded._item_sharding()
    )
    # co-sharding: each shard owns whole tiles and exactly their scales
    n_shards = mesh.size
    assert sharded.capacity % (n_shards * tile) == 0
    codes_spec = sharded.r_anc.codes.sharding.spec
    scales_spec = sharded.r_anc.scales.sharding.spec
    assert tuple(codes_spec[1]) == ("data", "model"), codes_spec
    assert tuple(scales_spec[0]) == ("data", "model"), scales_spec

    e_q = jax.random.normal(jax.random.PRNGKey(1), (5, 24))
    v0, i0 = index.topk(e_q, 10, tile=128)
    v1, i1 = sharded.topk(e_q, 10, tile=128)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), **TOL)

    # mutation keeps the co-sharded placement
    mutated = sharded.add_items(jnp.arange(1000, 1010),
                                cols=jnp.zeros((24, 10)))
    assert mutated._item_sharding()[1] == ("data", "model")


# ---------------------------------------------------------------------------
# the SPMD engine checks (the PR-5 acceptance surface)
# ---------------------------------------------------------------------------


def _engine_domain():
    import jax, jax.numpy as jnp, numpy as np

    from repro.data.synthetic import make_synthetic_ce

    n_aq, n_tq, n = 24, 8, 1024
    ce = make_synthetic_ce(jax.random.PRNGKey(0), n_queries=n_aq + n_tq, n_items=n)
    m = np.asarray(ce.full_matrix(jnp.arange(n_aq + n_tq)))
    return m, jnp.asarray(m[:n_aq]), jnp.arange(n_aq, n_aq + n_tq)


def check_engine_spmd_parity():
    """Full engine_search under shard_map on a (data x items) mesh is
    BIT-IDENTICAL to the single-device engine: all three loop modes x
    fp32/int8 payloads, with measured CE calls equal to the plan."""
    import jax, numpy as np

    from repro.configs.base import AdaCURConfig
    from repro.core.engine import ce_call_plan, make_engine, make_sharded_engine
    from repro.core.scorer import TabulatedScorer

    m, r_anc, test_q = _engine_domain()
    mesh = jax.make_mesh((2, 4), ("data", "items"))
    key = jax.random.PRNGKey(11)
    n_tq = test_q.shape[0]
    for mode, strat, payload in [
        ("fori", "topk", "float32"),
        ("fori", "softmax", "float32"),
        ("fori", "random", "int8"),
        ("unrolled", "topk", "int8"),
        ("early", "topk", "float32"),
        ("early", "softmax", "int8"),
    ]:
        cfg = AdaCURConfig(
            k_anchor=16, n_rounds=4, budget_ce=32, k_retrieve=10,
            strategy=strat, use_fused_topk=True, fused_tile=128,
            payload_dtype=payload, payload_tile=128,
            loop_mode="unrolled" if mode == "unrolled" else "fori",
            early_exit_tol=0.3 if mode == "early" else 0.0,
        )
        scorer = TabulatedScorer(m)
        r1 = make_engine(TabulatedScorer(m), cfg)(r_anc, test_q, key)
        r2 = jax.block_until_ready(
            make_sharded_engine(scorer, cfg, mesh)(r_anc, test_q, key)
        )
        label = f"{mode}/{strat}/{payload}"
        np.testing.assert_array_equal(
            np.asarray(r1.topk_idx), np.asarray(r2.topk_idx), err_msg=label
        )
        np.testing.assert_array_equal(
            np.asarray(r1.topk_scores), np.asarray(r2.topk_scores), err_msg=label
        )
        np.testing.assert_array_equal(
            np.asarray(r1.anchor_idx), np.asarray(r2.anchor_idx), err_msg=label
        )
        rounds = int(r2.rounds_done)
        assert rounds == int(r1.rounds_done), label
        assert scorer.stats.ce_calls == ce_call_plan(cfg, rounds) * n_tq, label


def check_engine_spmd_persistent():
    """The persistent round kernel + sub-int8 payloads under the SPMD
    engine on a 2x2 (data x items) mesh: bit-identical to the SAME config
    single-device, and bit-identical to the STAGED sharded engine — the
    fused sweep changes how each shard reads its payload slab, never the
    numbers.  Covers the software-pipelined monitored loop ('early') and
    the packed int4 / fp8 payload tiles."""
    import jax, numpy as np

    from repro.configs.base import AdaCURConfig
    from repro.core.engine import make_engine, make_sharded_engine
    from repro.core.scorer import TabulatedScorer
    from repro.kernels.approx_topk import quant

    m, r_anc, test_q = _engine_domain()
    mesh = jax.make_mesh((2, 2), ("data", "items"))
    key = jax.random.PRNGKey(11)
    cases = [("fori", "int4"), ("early", "int4"), ("early", "float32")]
    if quant.fp8_supported():
        cases.append(("fori", "fp8"))
    for mode, payload in cases:
        base = dict(
            k_anchor=16, n_rounds=4, budget_ce=32, k_retrieve=10,
            use_fused_topk=True, fused_tile=128,
            payload_dtype=payload, payload_tile=128, loop_mode="fori",
            early_exit_tol=0.3 if mode == "early" else 0.0,
        )
        cfg = AdaCURConfig(round_kernel="persistent", **base)
        label = f"{mode}/{payload}"
        r1 = make_engine(TabulatedScorer(m), cfg)(r_anc, test_q, key)
        r2 = jax.block_until_ready(
            make_sharded_engine(TabulatedScorer(m), cfg, mesh)(
                r_anc, test_q, key
            )
        )
        r3 = jax.block_until_ready(
            make_sharded_engine(
                TabulatedScorer(m), AdaCURConfig(round_kernel="staged", **base),
                mesh,
            )(r_anc, test_q, key)
        )
        for ref, tag in ((r1, "single-device"), (r3, "sharded-staged")):
            np.testing.assert_array_equal(
                np.asarray(r2.topk_idx), np.asarray(ref.topk_idx),
                err_msg=f"{label} vs {tag}",
            )
            np.testing.assert_array_equal(
                np.asarray(r2.topk_scores), np.asarray(ref.topk_scores),
                err_msg=f"{label} vs {tag}",
            )
            assert int(r2.rounds_done) == int(ref.rounds_done), (label, tag)


def check_engine_spmd_mutated_index():
    """Sharded parity survives the index lifecycle: a padded-capacity index
    mutated by remove_items + add_items serves bit-identical results (and
    identical EXTERNAL ids) through the SPMD engine, fp32 and int8."""
    import jax, jax.numpy as jnp, numpy as np

    from repro.configs.base import AdaCURConfig
    from repro.core.engine import AdaCURRetriever
    from repro.core.index import AnchorIndex
    from repro.core.scorer import TabulatedScorer

    m, r_anc, test_q = _engine_domain()
    mesh = jax.make_mesh((2, 4), ("data", "items"))

    class WrapScorer(TabulatedScorer):
        # external ids >= 5000 map back onto matrix columns
        def _host(self, qids, idx):
            self.stats.ce_calls += int(idx.size)
            return self.matrix[qids[:, None], np.where(idx >= 5000, idx - 5000, idx)]

    for payload in ("float32", "int8"):
        cfg = AdaCURConfig(
            k_anchor=16, n_rounds=4, budget_ce=32, k_retrieve=10,
            use_fused_topk=True, fused_tile=128, loop_mode="fori",
            payload_dtype=payload, payload_tile=128,
        )
        base = AnchorIndex.from_r_anc(r_anc[:, :1000], capacity=1024)
        if payload == "int8":
            base = base.quantize("int8", tile=128)
        cols = jnp.asarray(m[:24, :6])

        def mutate(ix):
            return ix.remove_items(jnp.arange(30, 40)).add_items(
                jnp.arange(5000, 5006), cols=cols
            )

        mut_ref = mutate(base)
        mut_sh = mutate(base.shard(mesh))
        key = jax.random.PRNGKey(3)
        a = AdaCURRetriever.from_index(mut_ref, WrapScorer(m), cfg).search(test_q, key)
        b = AdaCURRetriever.from_index(mut_sh, WrapScorer(m), cfg).search(test_q, key)
        np.testing.assert_array_equal(
            np.asarray(a.topk_idx), np.asarray(b.topk_idx), err_msg=payload
        )
        np.testing.assert_array_equal(
            np.asarray(mut_ref.gather_item_ids(a.topk_idx)),
            np.asarray(mut_sh.gather_item_ids(b.topk_idx)), err_msg=payload,
        )
        np.testing.assert_array_equal(
            np.asarray(a.topk_scores), np.asarray(b.topk_scores), err_msg=payload
        )


def check_engine_spmd_invariants():
    """The property suite's invariants hold under a 2x2 (data x items) mesh:
    no (query, item) pair is CE-scored twice within a search row, measured
    calls equal ce_call_plan exactly (the cond-gated scorer fires once per
    round system-wide), and runtime n_rounds overrides never retrace."""
    import jax

    from repro.configs.base import AdaCURConfig
    from repro.core.engine import ce_call_plan, make_sharded_engine
    from repro.core.scorer import TabulatedScorer

    m, r_anc, test_q = _engine_domain()
    mesh = jax.make_mesh((2, 2), ("data", "items"))
    n_tq = test_q.shape[0]
    for split, strat in [(True, "topk"), (True, "softmax"), (False, "softmax")]:
        cfg = AdaCURConfig(
            k_anchor=16, n_rounds=4, budget_ce=32 if split else 16,
            split_budget=split, strategy=strat, round_epsilon=0.25,
            k_retrieve=8, use_fused_topk=True, fused_tile=128, loop_mode="fori",
        )
        scorer = TabulatedScorer(m, record_pairs=True)
        run = make_sharded_engine(scorer, cfg, mesh)
        res = jax.block_until_ready(run(r_anc, test_q, jax.random.PRNGKey(5)))
        rows = {}
        for qids, idx in scorer.call_log:
            for r in range(idx.shape[0]):
                rows.setdefault(int(qids[r]), []).extend(
                    (int(qids[r]), int(i)) for i in idx[r]
                )
        assert len(rows) == n_tq, (split, strat, sorted(rows))
        for qid, pairs in rows.items():
            assert len(pairs) == len(set(pairs)), (
                f"qid {qid}: {len(pairs) - len(set(pairs))} pairs scored twice "
                f"(split={split}, strat={strat})"
            )
        planned = ce_call_plan(cfg, int(res.rounds_done)) * n_tq
        assert scorer.stats.ce_calls == planned, (
            scorer.stats.ce_calls, planned, split, strat
        )

    # zero retraces across runtime n_rounds on the compiled SPMD program
    traces = []
    import jax.numpy as jnp

    def counting(q, idx):
        traces.append(1)
        return jnp.take(jnp.asarray(m), idx, axis=1).mean(0) + 0.01 * q[:, None].astype(jnp.float32)

    cfg = AdaCURConfig(k_anchor=16, n_rounds=4, budget_ce=32, k_retrieve=8,
                       use_fused_topk=True, fused_tile=128, loop_mode="fori")
    run = make_sharded_engine(counting, cfg, mesh)
    jax.block_until_ready(run(r_anc, test_q, jax.random.PRNGKey(5), n_rounds=2))
    n0 = len(traces)
    for r in (4, 1, 3):
        jax.block_until_ready(run(r_anc, test_q, jax.random.PRNGKey(5), n_rounds=r))
    assert len(traces) == n0, f"runtime n_rounds retraced: {len(traces)} vs {n0}"


def check_engine_spmd_eligible():
    """First-stage candidate restriction under the SPMD engine: a per-query
    ``eligible`` mask sharded over the (data x items) mesh produces BIT-
    IDENTICAL results to the single-device masked engine, every returned
    item is a candidate, and measured CE calls still equal the plan."""
    import jax, jax.numpy as jnp, numpy as np

    from repro.configs.base import AdaCURConfig
    from repro.core.candidates import candidate_eligibility
    from repro.core.engine import ce_call_plan, make_engine, make_sharded_engine
    from repro.core.scorer import TabulatedScorer

    m, r_anc, test_q = _engine_domain()
    n_items = r_anc.shape[1]
    n_tq = int(test_q.shape[0])
    mesh = jax.make_mesh((2, 2), ("data", "items"))
    key = jax.random.PRNGKey(13)

    # imperfect first stage: noisy exact top-96 per query
    noisy = jnp.asarray(m)[test_q] + 1.5 * jax.random.normal(
        jax.random.PRNGKey(3), (n_tq, n_items)
    )
    cand = jax.lax.top_k(noisy, 96)[1]
    eligible = candidate_eligibility(cand, n_items, per_query=True)

    for strat, payload in [("topk", "float32"), ("random", "int8")]:
        cfg = AdaCURConfig(
            k_anchor=16, n_rounds=4, budget_ce=32, k_retrieve=8,
            strategy=strat, use_fused_topk=True, fused_tile=128,
            loop_mode="fori", payload_dtype=payload, payload_tile=128,
        )
        ref = make_engine(TabulatedScorer(m), cfg)(
            r_anc, test_q, key, eligible=eligible
        )
        scorer = TabulatedScorer(m)
        run = make_sharded_engine(scorer, cfg, mesh)
        res = jax.block_until_ready(
            run(r_anc, test_q, key, eligible=eligible)
        )
        for f in ("topk_idx", "topk_scores", "anchor_idx", "anchor_scores"):
            assert np.array_equal(
                np.asarray(getattr(res, f)), np.asarray(getattr(ref, f))
            ), (strat, payload, f)
        cand_sets = [set(int(i) for i in row) for row in np.asarray(cand)]
        for r, row in enumerate(np.asarray(res.topk_idx)):
            assert set(int(i) for i in row) <= cand_sets[r], (
                f"row {r} returned non-candidates ({strat}, {payload})"
            )
        planned = ce_call_plan(cfg, int(res.rounds_done)) * n_tq
        assert scorer.stats.ce_calls == planned, (
            scorer.stats.ce_calls, planned, strat, payload
        )

    # a (N,) batch-union mask shards over the items axis only
    union = candidate_eligibility(cand, n_items, per_query=False)
    cfg = AdaCURConfig(
        k_anchor=16, n_rounds=4, budget_ce=32, k_retrieve=8,
        use_fused_topk=True, fused_tile=128, loop_mode="fori",
    )
    ref = make_engine(TabulatedScorer(m), cfg)(r_anc, test_q, key, eligible=union)
    res = make_sharded_engine(TabulatedScorer(m), cfg, mesh)(
        r_anc, test_q, key, eligible=union
    )
    assert np.array_equal(np.asarray(res.topk_idx), np.asarray(ref.topk_idx))
    assert np.array_equal(np.asarray(res.topk_scores), np.asarray(ref.topk_scores))


def check_engine_spmd_golden():
    """Golden regression for one sharded engine config: catches cross-shard
    merge-order / collective regressions by tolerance compare against a
    pinned snapshot.  GOLDEN_REGEN=1 regenerates (sharded == single-device
    bit parity means the snapshot is mesh-independent, but it is always
    RECORDED through the 2x4 sharded program)."""
    import jax, numpy as np

    from repro.configs.base import AdaCURConfig
    from repro.core.engine import make_sharded_engine
    from repro.core.scorer import TabulatedScorer

    m, r_anc, test_q = _engine_domain()
    mesh = jax.make_mesh((2, 4), ("data", "items"))
    cfg = AdaCURConfig(
        k_anchor=16, n_rounds=4, budget_ce=32, k_retrieve=10,
        use_fused_topk=True, fused_tile=128, loop_mode="fori",
        payload_dtype="int8", payload_tile=128,
    )
    res = make_sharded_engine(TabulatedScorer(m), cfg, mesh)(
        r_anc, test_q, jax.random.PRNGKey(11)
    )
    idx = np.asarray(res.topk_idx, dtype=np.int64)
    scores = np.asarray(res.topk_scores, dtype=np.float64)

    if os.environ.get("GOLDEN_REGEN"):
        os.makedirs(os.path.dirname(GOLDEN_SHARDED), exist_ok=True)
        with open(GOLDEN_SHARDED, "w") as f:
            json.dump(
                {"mesh": "2x4", "topk_idx": idx.tolist(),
                 "topk_scores": np.round(scores, 6).tolist()}, f, indent=1,
            )
        print(f"regenerated {GOLDEN_SHARDED}")
        return
    assert os.path.exists(GOLDEN_SHARDED), (
        f"missing golden snapshot {GOLDEN_SHARDED}; run this check with "
        "GOLDEN_REGEN=1"
    )
    with open(GOLDEN_SHARDED) as f:
        snap = json.load(f)
    g_idx = np.asarray(snap["topk_idx"])
    g_scores = np.asarray(snap["topk_scores"])
    np.testing.assert_allclose(scores, g_scores, atol=1e-3, rtol=0)
    same = (idx[:, :, None] == g_idx[:, None, :]).any(-1).mean()
    assert same >= 0.9, f"sharded top-k id overlap {same:.3f} < 0.9"


def check_engine_device_ce():
    """The tentpole acceptance surface: the REAL transformer cross-encoder
    as a device-resident stage of the one shard_map program (DeviceCEScorer)
    on a 2x2 (data x items) mesh — no host callback, no nested launch, no
    psum-rendezvous deadlock.  Exact top-k parity vs the single-device
    exact-matrix search AND the single-device device-resident engine;
    exactly-once system-wide CE accounting with item-shard pad rows
    excluded; zero retraces across runtime n_rounds / n_valid."""
    import jax, jax.numpy as jnp, numpy as np

    from repro.configs import registry
    from repro.configs.base import AdaCURConfig, replace
    from repro.core.engine import ce_call_plan, make_engine, make_sharded_engine
    from repro.core.scorer import (
        CrossEncoderScorer, DeviceCEScorer, TabulatedScorer,
    )
    from repro.data.synthetic import make_zeshel_like
    from repro.models import cross_encoder

    # capacity 256 = 2 item shards x NOISE_BLOCK(128): shardable unpadded
    ds = make_zeshel_like(0, n_items=256, n_queries=24, item_len=12, query_len=8)
    cfg_lm = replace(
        registry.CE_TINY, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab_size=ds.vocab_size, dtype="float32",
        remat=False,
    )
    params, _ = cross_encoder.init_cross_encoder(jax.random.PRNGKey(0), cfg_lm)
    host = CrossEncoderScorer(
        params, cfg_lm, ds.pair_tokens, micro_batch=16, flash_block=(16, 16),
        len_buckets=(32, 64),
    )
    m = np.asarray(host._host(np.arange(24), np.tile(np.arange(256), (24, 1))))

    def device_scorer():
        return DeviceCEScorer(
            params, cfg_lm,
            query_token_fn=lambda q: np.asarray(ds.query_tokens)[q],
            item_tokens=ds.item_tokens, len_buckets=(32, 64),
            flash_block=(16, 16),
        )

    cfg = AdaCURConfig(k_anchor=12, n_rounds=4, budget_ce=24, k_retrieve=10,
                       loop_mode="fori")
    r_anc = jnp.asarray(m[:16])
    q = jnp.arange(16, 22)          # 6 rows -> b_local=3 per data shard
    key = jax.random.PRNGKey(7)

    mesh = jax.make_mesh((2, 2), ("data", "items"))
    sc = device_scorer()
    run = make_sharded_engine(sc, cfg, mesh)
    q_tok = sc.tokenize_queries(q)
    res = jax.block_until_ready(run(r_anc, q_tok, key))

    # (a) exact parity vs the single-device exact-matrix search...
    ref = jax.block_until_ready(
        make_engine(TabulatedScorer(m), cfg)(r_anc, q, key)
    )
    np.testing.assert_array_equal(
        np.asarray(res.topk_idx), np.asarray(ref.topk_idx)
    )
    np.testing.assert_allclose(
        np.asarray(res.topk_scores), np.asarray(ref.topk_scores), **TOL
    )
    # ...and vs the single-device device-resident engine
    sc1 = device_scorer()
    res1 = jax.block_until_ready(
        make_engine(sc1, cfg)(r_anc, sc1.tokenize_queries(q), key)
    )
    np.testing.assert_array_equal(
        np.asarray(res.topk_idx), np.asarray(res1.topk_idx)
    )

    # (b) exactly-once accounting under the mesh: each round's 3x3=9 local
    # pair rows pad to 10 over 2 item shards (batch_pad counts them), yet
    # measured CE calls equal the plan with pad rows excluded
    rounds = int(res.rounds_done)
    planned = ce_call_plan(cfg, rounds) * int(q.shape[0])
    assert sc.stats.ce_calls == planned, (sc.stats.ce_calls, planned)
    assert sc.stats.batch_pad > 0, "expected item-shard pad rows"
    assert sc1.stats.ce_calls == planned, (sc1.stats.ce_calls, planned)

    # (c) zero retraces across runtime n_rounds and corpus n_valid
    n0 = sc.n_traces
    for r in (1, 4, 2):
        jax.block_until_ready(run(r_anc, q_tok, key, n_rounds=r))
    jax.block_until_ready(run(r_anc, q_tok, key, n_valid=192))
    assert sc.n_traces == n0, (sc.n_traces, n0)


def check_bulk_score_mesh():
    """The offline bulk CE build split over a mesh: every device scores an
    equal share of the items (N not a multiple of the device count) through
    the flash kernel, and the gathered rows equal the single-device
    build's, on one device."""
    import jax, jax.numpy as jnp, numpy as np

    from repro.configs import registry
    from repro.configs.base import replace
    from repro.core.scorer import DeviceCEScorer
    from repro.data.synthetic import make_zeshel_like
    from repro.launch.mesh import make_serving_mesh
    from repro.models import cross_encoder

    ds = make_zeshel_like(0, n_items=13, n_queries=6, item_len=12, query_len=8)
    cfg_lm = replace(
        registry.CE_TINY, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab_size=ds.vocab_size, dtype="float32",
        remat=False,
    )
    params, _ = cross_encoder.init_cross_encoder(jax.random.PRNGKey(0), cfg_lm)
    sc = DeviceCEScorer(
        params, cfg_lm, query_token_fn=lambda q: np.asarray(ds.query_tokens)[q],
        item_tokens=ds.item_tokens, len_buckets=(32, 64), attn_impl="flash",
        flash_block=(16, 16),
    )
    sc.BULK_PAIRS = 4         # 2 items per step: few interpreted flash calls
    q, items = jnp.arange(2), jnp.arange(13)
    one = np.asarray(sc.bulk_score(q, items))
    out = sc.bulk_score(q, items, mesh=make_serving_mesh(2, 4))
    assert out.shape == (2, 13) and len(out.devices()) == 1, out.sharding
    np.testing.assert_array_equal(np.asarray(out), one)


CHECKS = {
    "bulk_score_mesh": check_bulk_score_mesh,
    "decode_attention": check_decode_attention,
    "moe_ep": check_moe_ep,
    "gnn_interact": check_gnn_interact,
    "pipeline": check_pipeline,
    "cross_pod_reduce": check_cross_pod_reduce,
    "anchor_index_shard": check_anchor_index_shard,
    "quantized_index_shard": check_quantized_index_shard,
    "engine_spmd_parity": check_engine_spmd_parity,
    "engine_spmd_persistent": check_engine_spmd_persistent,
    "engine_spmd_mutated_index": check_engine_spmd_mutated_index,
    "engine_spmd_invariants": check_engine_spmd_invariants,
    "engine_spmd_eligible": check_engine_spmd_eligible,
    "engine_spmd_golden": check_engine_spmd_golden,
    "engine_device_ce": check_engine_device_ce,
}


# ---------------------------------------------------------------------------
# pytest driver (runs in the normal 1-device process)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def forced_devices():
    """Environment for the check subprocesses: 8 forced host devices (the
    flag must be set before jax's backend initializes, hence a fresh
    process), src on PYTHONPATH, GOLDEN_REGEN passed through."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.mark.timeout(600)
@pytest.mark.parametrize("check", sorted(CHECKS))
def test_multidevice(check, forced_devices):
    proc = subprocess.run(
        [sys.executable, _THIS, check],
        env=forced_devices, capture_output=True, text=True, timeout=560,
    )
    assert proc.returncode == 0, (
        f"[{check}] failed\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    assert f"OK {check}" in proc.stdout


@pytest.mark.timeout(600)
def test_serve_real_ce_mesh():
    """The config this PR un-rejects: ``--scorer real-ce --mesh 2x2`` must
    serve end-to-end through the CLI — index built by the bulk CE path,
    token table sharded with the payload, DeviceCEScorer inside the SPMD
    program — with measured-accounting output and no deadlock."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--scorer", "real-ce", "--mesh", "2x2", "--n-items", "128",
         "--item-len", "24", "--query-len", "16", "--anchor-queries", "32",
         "--requests", "8", "--batch", "8", "--budget", "16", "--rounds", "2"],
        env=env, capture_output=True, text=True, timeout=560, cwd=_ROOT,
    )
    assert proc.returncode == 0, (
        f"serve --scorer real-ce --mesh 2x2 failed\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    assert "served 8 requests" in proc.stdout, proc.stdout
    assert "device-resident CE" in proc.stdout, proc.stdout


if __name__ == "__main__":
    import faulthandler

    name = sys.argv[1] if len(sys.argv) > 1 else None
    names = [name] if name else sorted(CHECKS)
    watchdog_s = float(os.environ.get("MULTIDEVICE_WATCHDOG_S", "480"))
    for n in names:
        # deadlock watchdog: a future collective/callback hang dumps every
        # thread's stack and exits nonzero instead of sitting silent until
        # the outer subprocess timeout kills it with no diagnostics
        faulthandler.dump_traceback_later(watchdog_s, exit=True)
        CHECKS[n]()
        faulthandler.cancel_dump_traceback_later()
        print(f"OK {n}")
