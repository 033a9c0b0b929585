"""Kernel microbenchmarks: Pallas (interpret) vs jnp oracle on CPU.

Interpret-mode wall times are NOT TPU times — the derived column carries
the analytic HBM-traffic reduction each kernel buys on the TPU target,
which is what the roofline credits them for."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.approx_topk import quant
from repro.kernels.approx_topk.ops import approx_topk_op
from repro.kernels.approx_topk.persistent import persistent_round_op
from repro.kernels.approx_topk.ref import approx_topk_reference
from repro.kernels.embedding_bag.ops import embedding_bag_op
from repro.kernels.embedding_bag.ref import embedding_bag_reference
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_reference

from .common import emit, timed


def run(quiet: bool = False):
    key = jax.random.PRNGKey(0)

    # flash attention: traffic reduction = O(L²) probs never hit HBM
    b, l, h, kv, hd = 1, 512, 4, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, l, h, hd))
    k = jax.random.normal(ks[1], (b, l, kv, hd))
    v = jax.random.normal(ks[2], (b, l, kv, hd))
    _, us_ref = timed(lambda: attention_reference(q, k, v, causal=True), warmup=1)
    _, us_pal = timed(lambda: flash_attention(q, k, v, causal=True, interpret=True), warmup=1)
    probs_bytes = b * h * l * l * 4
    io_bytes = (q.size + 2 * k.size + q.size) * 4
    emit("kernels/flash_attention_L512", us_pal,
         f"ref_us={us_ref:.0f};hbm_traffic_saved={probs_bytes / io_bytes:.1f}x_io")

    # approx_topk: traffic reduction = (B,N) scores never hit HBM
    bq, kq, n, kk = 8, 500, 100_000, 64
    e_q = jax.random.normal(ks[0], (bq, kq))
    r = jax.random.normal(ks[1], (kq, n))
    _, us_ref = timed(lambda: approx_topk_reference(e_q, r, kk), warmup=1)
    _, us_pal = timed(lambda: approx_topk_op(e_q, r, kk, tile=4096, interpret=True), warmup=1)
    scores_bytes = 2 * bq * n * 4                      # write + read back
    out_bytes = bq * (n // 4096) * kk * 8
    emit("kernels/approx_topk_N100k", us_pal,
         f"ref_us={us_ref:.0f};hbm_roundtrip_saved={scores_bytes / out_bytes:.1f}x")

    # persistent round: sample + provisional-monitor lists in ONE payload
    # sweep, per payload dtype.  Staged cost = two approx_topk sweeps (the
    # monitored-loop shape); traffic reduction = the second payload pass
    mask = jnp.zeros((bq, n), bool)
    for dt in ("float32", "int8", "int4") + (("fp8",) if quant.fp8_supported() else ()):
        payload = r if dt == "float32" else quant.quantize_ranc(r, 4096, code_dtype=dt)
        noise = jax.random.gumbel(ks[2], (bq, n))

        def staged():
            s = approx_topk_op(e_q, payload, kk, tile=4096, interpret=True,
                               noise=noise, mask=mask)
            p = approx_topk_op(e_q, payload, kk, tile=4096, interpret=True,
                               mask=mask)
            return s, p

        _, us_staged = timed(staged, warmup=1)
        _, us_per = timed(
            lambda: persistent_round_op(
                e_q, payload, k_sample=kk, k_prov=kk, mask=mask,
                noise=noise, prov_mask=mask, tile=4096, interpret=True,
            ),
            warmup=1,
        )
        pass_bytes = payload.nbytes
        emit(f"kernels/persistent_round_N100k_{dt}", us_per,
             f"staged2pass_us={us_staged:.0f};payload_pass_saved="
             f"{pass_bytes / 1e6:.1f}MB")

    # embedding bag: gathered rows never hit HBM
    rows, dim, bb, hh = 100_000, 128, 256, 8
    table = jax.random.normal(ks[2], (rows, dim))
    ids = jax.random.randint(ks[0], (bb, hh), 0, rows)
    _, us_ref = timed(lambda: embedding_bag_reference(table, ids), warmup=1)
    _, us_pal = timed(lambda: embedding_bag_op(table, ids, interpret=True), warmup=1)
    emit("kernels/embedding_bag_B256xH8", us_pal,
         f"ref_us={us_ref:.0f};gathered_rows_saved={hh}x_bag_width")
    return True


if __name__ == "__main__":
    run()
