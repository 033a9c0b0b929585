"""SPMD engine scaling bench -> BENCH_sharded.json.

Measures the (data x items) `shard_map` engine (``engine.make_sharded_engine``)
against the single-device engine at 1/2/4/8 forced host devices:

- **strong scaling** (fixed N): per-round latency and — the acceptance
  metric — *per-shard device-buffer bytes* (the index payload slab actually
  resident on device 0, plus the engine's per-shard state slabs), which must
  shrink ~linearly in the item-shard count;
- **weak scaling** (fixed N per shard): per-shard bytes stay ~constant while
  the served corpus grows with the mesh;
- **exactness**: the sharded top-k must equal the single-device top-k
  BIT-FOR-BIT (ids and scores) — recall is identical by construction, and
  this bench asserts it on every configuration it runs;
- **device-resident real CE**: pairs/s per shard through the in-mesh
  transformer forward (DeviceCEScorer — the ``--mesh`` + ``real-ce``
  serving path), asserting measured CE calls == ce_call_plan on every
  timed execution.

jax locks the device count at backend init, so the aggregator re-executes
this file as a worker subprocess per device count
(``XLA_FLAGS=--xla_force_host_platform_device_count=<n>``) and merges the
workers' JSON.  Assertions (CI): per-shard payload bytes <= 1.1x the ideal
N/shards split at every device count, and sharded == single-device top-k
exactly everywhere.

Usage:  PYTHONPATH=src python -m benchmarks.sharded_engine [--ci]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

DEVICE_COUNTS = (1, 2, 4, 8)


def _worker(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))
    from benchmarks.common import timed
    from repro.configs.base import AdaCURConfig
    from repro.core.engine import engine_slab_bytes, make_engine, make_sharded_engine
    from repro.core.index import AnchorIndex
    from repro.data.synthetic import make_synthetic_ce
    from repro.launch.mesh import make_serving_mesh

    n_dev = args.worker
    assert len(jax.devices()) == n_dev, (len(jax.devices()), n_dev)
    mesh = make_serving_mesh(1, n_dev)

    def bench_one(n_items: int) -> dict:
        ce = make_synthetic_ce(jax.random.PRNGKey(0), n_queries=80,
                               n_items=n_items)
        r_anc = ce.full_matrix(jnp.arange(48))
        queries = jnp.arange(48, 48 + args.batch)
        score_fn = ce.score_fn()
        cfg = AdaCURConfig(
            k_anchor=32, n_rounds=args.rounds, budget_ce=64, k_retrieve=32,
            loop_mode="fori", use_fused_topk=True, fused_tile=1024,
        )
        index = AnchorIndex.from_r_anc(r_anc).shard(mesh)

        # actual per-device payload residency (shard 0 of each leaf)
        def shard0_bytes(x):
            return int(x.addressable_shards[0].data.nbytes)

        payload_shard = shard0_bytes(index.r_anc) + shard0_bytes(index.item_ids)
        slabs = engine_slab_bytes(
            cfg, args.batch, index.capacity, index.k_q,
            n_data_shards=1, n_item_shards=n_dev,
        )

        run_s = make_sharded_engine(score_fn, cfg, mesh)
        run_d = make_engine(score_fn, cfg)
        key = jax.random.PRNGKey(7)
        kw = dict(n_valid=index.n_valid, item_ids=index.item_ids)
        res_s, us_full = timed(
            run_s, index.r_anc, queries, key, n_iter=args.iters, warmup=1, **kw
        )
        _, us_r1 = timed(
            run_s, index.r_anc, queries, key, n_rounds=1,
            n_iter=args.iters, warmup=1, **kw,
        )
        res_d = run_d(r_anc, queries, key)

        # the acceptance bit: sharded == dense single-device, exactly
        idx_equal = bool(
            (np.asarray(res_s.topk_idx) == np.asarray(res_d.topk_idx)).all()
        )
        score_equal = bool(
            (np.asarray(res_s.topk_scores) == np.asarray(res_d.topk_scores)).all()
        )
        marginal_ms = (us_full - us_r1) / 1e3 / max(cfg.n_rounds - 1, 1)
        return {
            "n_items": n_items,
            "capacity": index.capacity,
            "payload_bytes_total": int(index.payload_nbytes),
            "payload_bytes_per_shard": payload_shard,
            "engine_slab_bytes_per_shard": slabs["total"],
            "device_buffer_bytes_per_shard": payload_shard + slabs["total"],
            "search_ms": us_full / 1e3,
            "per_round_ms": marginal_ms,
            "topk_idx_equal": idx_equal,
            "topk_scores_equal": score_equal,
        }

    def bench_real_ce() -> dict:
        """Device-resident CE stage: pairs/s through the in-mesh transformer
        forward (the --mesh + real-ce path), with measured == planned
        accounting across every timed execution."""
        from repro.configs.base import replace as cfg_replace
        from repro.configs.registry import CE_TINY
        from repro.core.engine import ce_call_plan
        from repro.core.scorer import DeviceCEScorer
        from repro.data.synthetic import make_zeshel_like
        from repro.models import cross_encoder

        n_items = 128 * n_dev          # one NOISE_BLOCK slab per item shard
        ds = make_zeshel_like(0, n_items=n_items, n_queries=48 + args.batch,
                              item_len=12, query_len=8)
        lm_cfg = cfg_replace(
            CE_TINY, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
            head_dim=16, d_ff=128, vocab_size=ds.vocab_size, dtype="float32",
            remat=False,
        )
        params, _ = cross_encoder.init_cross_encoder(
            jax.random.PRNGKey(0), lm_cfg
        )
        scorer = DeviceCEScorer(
            params, lm_cfg,
            query_token_fn=lambda q: np.asarray(ds.query_tokens)[q],
            item_tokens=ds.item_tokens, attn_impl="ref",
        )
        cfg = AdaCURConfig(k_anchor=16, n_rounds=args.rounds, budget_ce=32,
                           k_retrieve=16, loop_mode="fori")
        r_anc = jax.random.normal(jax.random.PRNGKey(1), (24, n_items))
        q_tok = scorer.tokenize_queries(jnp.arange(48, 48 + args.batch))
        run = make_sharded_engine(scorer, cfg, mesh)
        _, us = timed(run, r_anc, q_tok, jax.random.PRNGKey(7),
                      n_iter=args.iters, warmup=1)
        pairs = ce_call_plan(cfg) * args.batch
        pairs_per_s = pairs / (us / 1e6)
        return {
            "n_items": n_items,
            "pairs_per_search": pairs,
            "pairs_per_s": pairs_per_s,
            "pairs_per_s_per_shard": pairs_per_s / n_dev,
            # every timed execution (warmup included) counted exactly once,
            # item-shard pad rows excluded
            "measured_equals_planned": bool(
                scorer.stats.ce_calls == pairs * (args.iters + 1)
            ),
        }

    out = {
        "n_devices": n_dev,
        "fixed_n": bench_one(args.n_items),
        "weak_scaling": bench_one(args.n_per_shard * n_dev),
        "real_ce": bench_real_ce(),
    }
    print("BENCH_JSON " + json.dumps(out))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--ci", action="store_true", help="small shapes for CI")
    ap.add_argument("--n-items", type=int, default=None,
                    help="fixed corpus size for the strong-scaling sweep")
    ap.add_argument("--n-per-shard", type=int, default=None,
                    help="per-shard corpus size for the weak-scaling sweep")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()
    if args.n_items is None:
        args.n_items = 16384 if args.ci else 65536
    if args.n_per_shard is None:
        args.n_per_shard = 4096 if args.ci else 16384

    if args.worker is not None:
        _worker(args)
        return

    per_dev = {}
    for n_dev in DEVICE_COUNTS:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
        env.setdefault("JAX_PLATFORMS", "cpu")
        cmd = [sys.executable, "-m", "benchmarks.sharded_engine",
               "--worker", str(n_dev),
               "--n-items", str(args.n_items),
               "--n-per-shard", str(args.n_per_shard),
               "--batch", str(args.batch), "--rounds", str(args.rounds),
               "--iters", str(args.iters)]
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
        if proc.returncode != 0:
            sys.stderr.write(proc.stdout + "\n" + proc.stderr)
            raise SystemExit(f"worker for {n_dev} devices failed")
        line = [l for l in proc.stdout.splitlines() if l.startswith("BENCH_JSON ")]
        per_dev[str(n_dev)] = json.loads(line[-1][len("BENCH_JSON "):])
        f = per_dev[str(n_dev)]["fixed_n"]
        ce = per_dev[str(n_dev)]["real_ce"]
        print(f"devices={n_dev}: per-shard payload "
              f"{f['payload_bytes_per_shard']/1e6:.2f} MB "
              f"(ideal {f['payload_bytes_total']/n_dev/1e6:.2f}), "
              f"per-round {f['per_round_ms']:.1f} ms, "
              f"exact={f['topk_idx_equal'] and f['topk_scores_equal']}, "
              f"real-CE {ce['pairs_per_s_per_shard']:.0f} pairs/s/shard "
              f"(measured==planned: {ce['measured_equals_planned']})")

    snap = {
        "config": {"n_items": args.n_items, "n_per_shard": args.n_per_shard,
                   "batch": args.batch, "rounds": args.rounds},
        "devices": per_dev,
        "assertions": {},
    }

    # --- assertions: the acceptance criteria ------------------------------
    worst_ratio = 0.0
    all_exact = True
    ce_measured_ok = True
    ce_min_rate = float("inf")
    for n_dev, rec in per_dev.items():
        for sweep in ("fixed_n", "weak_scaling"):
            r = rec[sweep]
            ideal = r["payload_bytes_total"] / int(n_dev)
            worst_ratio = max(worst_ratio, r["payload_bytes_per_shard"] / ideal)
            all_exact = all_exact and r["topk_idx_equal"] and r["topk_scores_equal"]
        ce = rec["real_ce"]
        ce_measured_ok = ce_measured_ok and ce["measured_equals_planned"]
        ce_min_rate = min(ce_min_rate, ce["pairs_per_s_per_shard"])
    snap["assertions"] = {
        "per_shard_payload_over_ideal_max": worst_ratio,
        "sharded_equals_dense_exactly": all_exact,
        "real_ce_measured_equals_planned": ce_measured_ok,
        "real_ce_min_pairs_per_s_per_shard": ce_min_rate,
    }
    with open("BENCH_sharded.json", "w") as f:
        json.dump(snap, f, indent=1)
    print(json.dumps(snap["assertions"], indent=1))
    assert worst_ratio <= 1.1, (
        f"per-shard payload bytes {worst_ratio:.3f}x ideal N/shards split"
    )
    assert all_exact, "sharded engine diverged from the single-device engine"
    assert ce_measured_ok, (
        "device-resident CE measured calls diverged from ce_call_plan"
    )
    assert ce_min_rate > 0, "real-CE throughput not recorded"
    print("wrote BENCH_sharded.json")


if __name__ == "__main__":
    main()
