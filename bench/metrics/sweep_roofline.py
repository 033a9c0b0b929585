"""The staged item sweep's (``approx_topk_sweep``) share of its roofline in
the traced window.  Each searched batch runs one sweep per adaptive round
after the first and one for the rerank; bytes are the payload, the int8
mask and the per-tile top-k lists, against HBM bandwidth."""

KERNEL = "approx_topk_sweep"
PAYLOAD_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1, "fp8": 1}


def read(ctx):
    if ctx.trace is None or not ctx.trace_batches or ctx.engine["tile"] is None:
        return None
    from xplane import kernel_seconds

    t = kernel_seconds(ctx.trace, KERNEL)
    if t <= 0:
        return None
    e = ctx.engine
    payload_bytes = PAYLOAD_BYTES[ctx.cfg["engine"]["payload_dtype"]]
    cost = ctx.kernel_cost(KERNEL).cost
    flops = nbytes = 0.0
    for b in ctx.trace_batches:
        for k in [e["k_s"]] * (e["rounds"] - 1) + [e["k_r"]]:
            f, n = cost(b, e["k_q"], e["n_items"], k, e["tile"], payload_bytes)
            flops, nbytes = flops + f, nbytes + n
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
