#!/usr/bin/env python3
"""Bring-up smoke of the served ADACUR path on a TPU.

Runs what ``python -m repro.launch.serve --scorer real-ce --fused`` runs —
the CE_TINY cross-encoder at its registry widths (random weights from a
seed), an AnchorIndex the CE builds on the chip over a ZESHEL-like corpus of
10,031 entities (the YuGiOh domain's count), and AdaCURService answering
batched requests through the device-resident CE — and checks what comes out.

    python chip_smoke.py               # one chip: 4 phases
    python chip_smoke.py --four-chips  # the --mesh 1x4 path vs one device

One chip runs four phases (round kernel staged | persistent x payload
float32 | int8).  Each phase must

- compile an engine program that holds the compiled Pallas item sweep and
  the CE's flash attention (``tpu_custom_call``), not an emulation;
- measure exactly ``ce_call_plan`` CE calls per request;
- serve CE scores that a plain float32 forward reproduces within
  ``CE_ATOL + CE_RTOL * |ref|`` (bf16 serving vs fp32 reference);
- reach a recall@10 against brute-force CE within ``RECALL_TOL`` of the
  reference implementation (``core/adacur.py`` on the host CPU over the
  same chip-computed scores);
- solve CUR as precisely as float32 does: the dense engine's final
  approximate scores, replayed on the host CPU from the anchors and anchor
  scores the chip chose, lie at most ``CUR_SLACK`` times farther from a
  float64 replay than a float32 host replay does — precision lost in the
  CUR products on the chip shows here;
- return the same top-10 as the dense (unfused) engine, up to near ties.

``--four-chips`` runs the ``--mesh 1x4`` sharded engine (index built over
the four chips) and compares it with the single-device engine on device 0
in the same process: the same top-10 in the same order up to near ties,
measured CE equal to the plan (each pair scored once system-wide), and no
device holding more than 1.1x its share of the index payload.  It also
prints where the scores part: the share of each side's served scores that
device 0's CE forward, run alone at the mesh's per-chip pair-batch shapes
and at one chip's, reproduces bit for bit.

Times printed are bring-up readings, not benchmark numbers.  The script
exits 1 on any failed check, without a result line, and refuses to run
without a TPU.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# the served configuration: corpus, CE budget, rounds, batch, requests
SERVE_ARGS = [
    "--scorer", "real-ce", "--fused", "--seed", "0",
    "--n-items", "10031", "--item-len", "128", "--query-len", "64",
    "--anchor-queries", "100",
    "--budget", "200", "--rounds", "5", "--batch", "16", "--requests", "64",
]
PHASES = (
    ("staged", "float32"), ("staged", "int8"),
    ("persistent", "float32"), ("persistent", "int8"),
)
SWEEP_KERNEL = {"staged": "approx_topk_sweep",
                "persistent": "persistent_round_sweep"}
CE_KERNEL = "flash_attention"
CHECK_QUERIES = 8        # served queries the CE and recall checks recompute
TOP = 10
# bf16 CE vs a float32 forward: 8 mantissa bits through 4 layers
CE_ATOL, CE_RTOL = 2e-2, 2e-2
RECALL_TOL = 0.05
# CUR solves: the chip's S_hat may lie at most CUR_SLACK x farther from a
# float64 host replay than the float32 host replay does (plus CUR_FLOOR).
# On v5e the served configuration read 0.98x (fp32) and 0.84x (int8) with
# full-precision CUR products and 3.9x with one-pass bf16 ones
CUR_SLACK, CUR_FLOOR = 2.0, 1e-5
# fused vs dense: positions whose ids differ must hold scores this close
TIE_TOL = 1e-3
# mesh vs one chip: the two engine programs compile the bf16 CE forward
# differently and a pair's scores differ by up to 1.09e-3 on v5e; ids may
# differ at a position only where the two scores there are within twice that
MESH_TIE_TOL = 2e-3
PAYLOAD_SLACK = 1.1


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


class Checks:
    """Collects every check of every phase; failures do not stop the run."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)


def custom_call_kernels(hlo_text: str) -> set:
    """Names of the compiled Pallas kernels (``tpu_custom_call``) in an
    executable's HLO text."""
    names = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line and "=" in line:
            lhs = line.split("=", 1)[0].strip().lstrip("%")
            names.add(lhs.split(" ")[-1].lstrip("%").split(".")[0])
    return names


def served_top(served, n_rows):
    import numpy as np

    qids = np.array([r.query_id for r in served[:n_rows]])
    ids = np.stack([np.asarray(r.item_ids[:TOP]) for r in served[:n_rows]])
    scores = np.stack([np.asarray(r.scores[:TOP]) for r in served[:n_rows]])
    return qids, ids, scores


def reference_ce_scores(stack, qids, ids):
    """Plain float32 forward (jnp attention, highest matmul precision) of the
    (query, item) pairs — the check on the served bf16 flash-attention CE."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import replace
    from repro.core.scorer import bucket_for
    from repro.models import cross_encoder

    ds, scorer = stack.ds, stack.scorer
    params = jax.tree.map(lambda x: x.astype(jnp.float32), stack.params)
    cfg = replace(stack.lm_cfg, dtype="float32")
    q_tok = jnp.asarray(ds.query_tokens[qids])
    i_tok = jnp.asarray(ds.item_tokens[ids])
    pad_to = bucket_for(q_tok.shape[1] + i_tok.shape[2] + 3, scorer.len_buckets)
    pairs = cross_encoder.build_pair_tokens(q_tok, i_tok, pad_to=pad_to)
    with jax.default_matmul_precision("highest"):
        return jax.device_get(
            cross_encoder.score_pairs(params, pairs, cfg, attn_impl="ref")
        )


def reference_recall(rows, payload, cfg, exact_top):
    """recall@10 of ``core/adacur.py`` on the host CPU backend, scoring from
    the chip-computed brute-force rows (the served key: the service's seed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.adacur import adacur_search

    cpu = jax.devices("cpu")[0]
    rows_c = jax.device_put(np.asarray(rows), cpu)
    r_c = jax.device_put(np.asarray(payload, np.float32), cpu)

    def score_fn(q, idx):
        return jnp.take_along_axis(rows_c[q], idx, axis=1)

    with jax.default_device(cpu):
        res = jax.jit(lambda r, q, k: adacur_search(score_fn, r, q, cfg, k))(
            r_c, jnp.arange(rows.shape[0]), jax.random.PRNGKey(0)
        )
        top = np.asarray(res.topk_idx)[:, :TOP]
    return recall(top, exact_top)


def recall(ids, exact_top) -> float:
    import numpy as np

    return float(np.mean([
        len(set(a.tolist()) & set(b.tolist())) / TOP
        for a, b in zip(ids, exact_top)
    ]))


def near_tie_equal(ids_a, s_a, ids_b, s_b, tol=TIE_TOL) -> bool:
    import numpy as np

    differ = ids_a != ids_b
    return bool(np.all(~differ | (np.abs(s_a - s_b) <= tol)))


def replay_s_hat(payload, res, cfg, dtype):
    """The engine's final approximate scores replayed on the host CPU in
    ``dtype``, from the anchors the chip chose and their CE scores, through
    the engine's own ``core/cur.py`` steps: the first block's pinv, one
    bordered update per further round, e_q, then e_q @ R_anc."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import cur

    assert cfg.incremental_pinv
    anchor_idx = np.asarray(res.anchor_idx)
    k_i = anchor_idx.shape[1]
    k_s = k_i // cfg.n_rounds
    filled = int(res.rounds_done) * k_s
    extend = jax.vmap(cur.block_pinv_extend_static, in_axes=(0, 0, 0, None))
    with jax.default_device(jax.devices("cpu")[0]):
        r = jnp.asarray(payload, dtype)
        cols = cur.gather_anchor_columns(r, jnp.asarray(anchor_idx))
        a = jnp.zeros_like(cols).at[..., :k_s].set(cols[..., :k_s])
        p = jnp.zeros((cols.shape[0], k_i, cols.shape[1]), dtype)
        p = p.at[:, :k_s].set(
            cur.incremental_pinv_init(cols[..., :k_s], cfg.pinv_rcond)
        )
        for start in range(k_s, filled, k_s):
            new = cols[..., start:start + k_s]
            p = extend(a, p, new, start)
            a = a.at[..., start:start + k_s].set(new)
        c = jnp.asarray(np.asarray(res.anchor_scores), dtype)
        e_q = jnp.einsum("bk,bkq->bq", c, p, precision=cur.HIGHEST)
        s_hat = jnp.matmul(e_q, r, precision=cur.HIGHEST)
        return np.asarray(s_hat, np.float64)


def cur_errors(payload, res, cfg):
    """(chip, host float32) largest per-query relative error of S_hat
    against the float64 host replay."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    payload = np.asarray(payload, np.float32)
    with jax.enable_x64(True):
        exact = replay_s_hat(payload, res, cfg, jnp.float64)
    host = replay_s_hat(payload, res, cfg, jnp.float32)
    chip = np.asarray(res.approx_scores, np.float64)
    norm = np.linalg.norm(exact, axis=1)

    def err(x):
        return float(np.max(np.linalg.norm(x - exact, axis=1) / norm))

    return err(chip), err(host)


def readings(label: str, served, **extra) -> None:
    import numpy as np

    lat = np.array([r.latency_s for r in served]) * 1e3
    more = "".join(f" {k}={v}" for k, v in extra.items())
    print(f"bring-up reading [{label}]: p50={np.percentile(lat, 50):.1f}ms "
          f"p99={np.percentile(lat, 99):.1f}ms over {len(served)} requests"
          f"{more}", flush=True)


def serve_phase(serve, args, stack):
    """(service, cfg) of one serving configuration: full batches only, every
    flush searched with the service's seed key."""
    svc, cfg = serve.real_ce_service(args, stack)
    svc.max_wait_s = float("inf")      # 64 requests = 4 full batches of 16
    svc.deterministic = True
    return svc, cfg


def one_chip(serve_argv, check: Checks) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import replace
    from repro.core.engine import AdaCURRetriever, ce_call_plan
    from repro.kernels.approx_topk import quant
    from repro.launch import serve

    args = serve.build_arg_parser().parse_args(serve_argv)
    stack = serve.build_real_ce_stack(args)
    jax.block_until_ready(stack.index.r_anc)
    print(f"bring-up reading: index build {stack.build_s:.1f}s "
          f"({stack.index.k_q} x {stack.index.n_items} CE pairs)", flush=True)
    n_items = stack.index.n_items
    lo = stack.serve_qids[0]
    exact = None                 # brute-force rows of the checked queries
    dense_top = {}               # payload -> (ids, scores) of the dense engine
    cur_err = {}                 # payload -> (chip, host) S_hat errors
    for round_kernel, payload in PHASES:
        label = f"{round_kernel}/{payload}"
        print(f"phase {label}", flush=True)
        args.round_kernel, args.payload_dtype = round_kernel, payload
        svc, cfg = serve_phase(serve, args, stack)
        plan = ce_call_plan(cfg)

        t0 = time.perf_counter()
        compiled = svc.retriever.lower(
            jnp.arange(lo, lo + args.batch), jax.random.PRNGKey(0)
        ).compile()
        compile_s = time.perf_counter() - t0
        kernels = custom_call_kernels(compiled.as_text())
        check({SWEEP_KERNEL[round_kernel], CE_KERNEL} <= kernels,
              f"{label}: compiled engine holds {sorted(kernels)}")

        before = svc.scorer_stats.copy()
        served = serve.drive_requests(svc, args, cfg, qid_range=stack.serve_qids,
                                      label=f"real-ce/{label}")
        measured = svc.scorer_stats.ce_calls - before.ce_calls
        readings(label, served, compile_s=f"{compile_s:.1f}s")
        check(len(served) == args.requests
              and all(r.status == "ok" for r in served),
              f"{label}: {len(served)} ok responses of {args.requests}")
        check(all(r.measured_ce_calls == plan for r in served)
              and measured == plan * args.requests,
              f"{label}: measured {measured} CE calls = plan {plan} x "
              f"{args.requests}")

        qids, ids, scores = served_top(served, CHECK_QUERIES)
        ref = reference_ce_scores(stack, qids, ids)
        err = np.abs(scores - ref)
        check(bool(np.all(err <= CE_ATOL + CE_RTOL * np.abs(ref))),
              f"{label}: served CE vs fp32 reference max |err| "
              f"{err.max():.2e} (tol {CE_ATOL} + {CE_RTOL}*|ref|)")

        if exact is None:
            exact = np.asarray(stack.scorer.bulk_score(qids, jnp.arange(n_items)))
        exact_top = np.argsort(-exact, axis=1, kind="stable")[:, :TOP]
        r_payload = svc.retriever.index.r_anc
        if isinstance(r_payload, quant.QuantizedRanc):
            r_payload = quant.dequantize(r_payload)
        r_payload = jax.device_get(r_payload)
        got = recall(ids, exact_top)
        want = reference_recall(exact, r_payload, cfg, exact_top)
        check(abs(got - want) <= RECALL_TOL,
              f"{label}: recall@{TOP} {got:.4f} vs reference {want:.4f} "
              f"(tol {RECALL_TOL})")

        q_all, f_ids, f_scores = served_top(served, args.batch)
        if payload not in dense_top:
            dense = AdaCURRetriever.from_index(
                svc.retriever.index, stack.scorer,
                replace(cfg, use_fused_topk=False, round_kernel="staged"),
            )
            res = dense.search(jnp.asarray(q_all), jax.random.PRNGKey(0))
            dense_top[payload] = (np.asarray(res.topk_idx)[:, :TOP],
                                  np.asarray(res.topk_scores)[:, :TOP])
            cur_err[payload] = cur_errors(r_payload, res, cfg)
        e_chip, e_host = cur_err[payload]
        check(e_chip <= CUR_SLACK * e_host + CUR_FLOOR,
              f"{label}: CUR S_hat vs float64 host replay rel err "
              f"{e_chip:.3e} (float32 host replay {e_host:.3e}; tol "
              f"{CUR_SLACK} x that + {CUR_FLOOR})")
        d_ids, d_scores = dense_top[payload]
        overlap = recall(f_ids, d_ids)
        check(near_tie_equal(f_ids, f_scores, d_ids, d_scores),
              f"{label}: fused vs dense top-{TOP} overlap {overlap:.4f} "
              f"(differing positions within {TIE_TOL})")


def forward_witness(stack, qids, ids, scores, pair_rows) -> float:
    """Share of the served (query, item) scores that device 0's CE forward,
    run alone on batches of ``m`` pairs for each ``m`` in ``pair_rows``,
    reproduces bit for bit at one of those batch shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    scorer, ds = stack.scorer, stack.ds
    q_tok = jnp.asarray(ds.query_tokens[np.repeat(qids, ids.shape[1])])
    rows = jnp.asarray(ds.item_tokens[ids.reshape(-1)])[:, None]
    flat = scorer.build_pairs(q_tok, rows).reshape(q_tok.shape[0], -1)
    want = scores.reshape(-1)
    forward = jax.jit(scorer.forward)
    hit = np.zeros(want.shape, bool)
    for m in pair_rows:
        pad = jnp.full((-flat.shape[0] % m, flat.shape[1]), scorer.pad_id,
                       flat.dtype)
        x = jnp.concatenate([flat, pad])
        got = np.concatenate([
            np.asarray(forward(x[i:i + m]), np.float32)
            for i in range(0, x.shape[0], m)
        ])[:want.size]
        hit |= got == want
    return float(hit.mean())


def four_chips(serve_argv, check: Checks) -> None:
    import jax
    import numpy as np

    from repro.core.engine import ce_call_plan
    from repro.kernels.approx_topk.quant import QuantizedRanc
    from repro.launch import serve

    args = serve.build_arg_parser().parse_args(serve_argv + ["--mesh", "1x4"])
    stack = serve.build_real_ce_stack(args)      # scored over the 4 chips
    jax.block_until_ready(stack.index.r_anc)
    print(f"bring-up reading: index build {stack.build_s:.1f}s", flush=True)

    runs = {}
    for mesh in (None, "1x4"):
        args.mesh = mesh
        label = f"mesh {mesh}" if mesh else "device 0"
        svc, cfg = serve_phase(serve, args, stack)
        before = stack.scorer.stats.copy()
        t0 = time.perf_counter()
        served = serve.drive_requests(svc, args, cfg, qid_range=stack.serve_qids,
                                      label=f"real-ce/{label}")
        wall = time.perf_counter() - t0
        readings(label, served, wall_s=f"{wall:.1f}s")
        delta = stack.scorer.stats - before
        plan = ce_call_plan(cfg)
        check(all(r.status == "ok" for r in served) and len(served) == args.requests,
              f"{label}: {len(served)} ok responses")
        check(all(r.measured_ce_calls == plan for r in served)
              and delta.ce_calls == plan * args.requests,
              f"{label}: measured {delta.ce_calls} CE calls = plan {plan} x "
              f"{args.requests} ({delta.batch_pad} shard pad rows excluded)")
        runs[label] = (svc, served_top(served, args.requests))

    (_, (q1, ids1, s1)), (svc4, (q4, ids4, s4)) = runs["device 0"], runs["mesh 1x4"]
    assert np.array_equal(q1, q4)
    check(near_tie_equal(ids4, s4, ids1, s1, MESH_TIE_TOL),
          f"mesh 1x4 top-{TOP} equals device 0 in order up to near ties "
          f"(overlap {recall(ids4, ids1):.4f}; same order in "
          f"{np.mean(np.all(ids1 == ids4, axis=1)):.4f} of requests; "
          f"differing positions within {MESH_TIE_TOL})")
    print(f"  scores bit-identical to device 0: {bool(np.array_equal(s1, s4))} "
          f"(max |diff| {np.abs(s1 - s4).max():.3e})", flush=True)
    # the engine scores a round's B x k_s anchors and the B x k_r rerank
    # pairs per CE call; the mesh splits each call over its 4 item shards
    k_s = cfg.k_anchor // cfg.n_rounds
    one = (args.batch * k_s, args.batch * (cfg.budget_ce - cfg.k_anchor))
    per_chip = tuple(m // 4 for m in one)
    print(f"  witness: device 0's CE forward alone at the mesh's per-chip pair "
          f"batches {per_chip} reproduces "
          f"{forward_witness(stack, q4, ids4, s4, per_chip):.4f} of the mesh's "
          f"scores bit for bit and "
          f"{forward_witness(stack, q1, ids1, s1, per_chip):.4f} of device 0's; "
          f"at one chip's {one}: "
          f"{forward_witness(stack, q1, ids1, s1, one):.4f} of device 0's, "
          f"{forward_witness(stack, q4, ids4, s4, one):.4f} of the mesh's",
          flush=True)

    index = svc4.retriever.index
    payload = index.r_anc
    leaves = [payload.codes, payload.scales] if isinstance(payload, QuantizedRanc) else [payload]
    total = sum(int(x.nbytes) for x in leaves)
    per_dev = {}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            per_dev[shard.device.id] = per_dev.get(shard.device.id, 0) + shard.data.nbytes
    share = total / 4
    print(f"  payload {total} bytes; per device {dict(sorted(per_dev.items()))}",
          flush=True)
    check(len(per_dev) == 4 and max(per_dev.values()) <= PAYLOAD_SLACK * share,
          f"each device holds <= {PAYLOAD_SLACK} x total/4 = "
          f"{PAYLOAD_SLACK * share:.0f} payload bytes")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the --mesh 1x4 path and its single-device "
                         "comparison (needs 4 TPU chips)")
    opts = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"found no TPU: JAX's devices are {devices[0].platform} "
             f"({len(devices)}); this smoke runs only on a TPU")
    want = 4 if opts.four_chips else 1
    if len(devices) < want:
        fail(f"needs {want} TPU chips, JAX sees {len(devices)}")
    if not (SRC / "repro").is_dir():
        fail(f"the repro package is not at {SRC / 'repro'}; run from a checkout")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    check = Checks()
    if opts.four_chips:
        four_chips(SERVE_ARGS, check)
    else:
        one_chip(SERVE_ARGS, check)
    if check.failed:
        fail(f"{len(check.failed)} check(s) failed: {check.failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
