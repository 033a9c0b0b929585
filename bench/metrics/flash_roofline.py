"""``flash_attention``'s share of its roofline in the traced window: the
least time its FLOPs and bytes allow (bf16 peak, HBM bandwidth) over the
device time of its events.  The calls are every CE pair the device
computed while traced (padding rows included: the kernel ran them), once
per layer."""

KERNEL = "flash_attention"


def read(ctx):
    if ctx.trace is None or not ctx.trace_pairs:
        return None
    from xplane import kernel_seconds

    t = kernel_seconds(ctx.trace, KERNEL)
    if t <= 0:
        return None
    e = ctx.engine
    flops, nbytes = ctx.kernel_cost(KERNEL).cost(
        ctx.trace_pairs, e["seq_len"], e["heads"], e["head_dim"],
        v_head_dim=e["v_head_dim"], causal=e["causal"])
    flops, nbytes = flops * e["layers"], nbytes * e["layers"]
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
