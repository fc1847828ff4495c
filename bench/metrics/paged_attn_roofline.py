"""Paged attention: the least time the window's attention needs (per
call, the larger of its ops over the bf16 peak and its bytes over HBM
bandwidth; ``work/paged_attn.py``) over the device time of the
``flash_attention_paged_quantized`` kernel in the trace."""

KERNELS = ("flash_attention_paged_quantized",)


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s(KERNELS)
    if not t:
        return None
    p, bw = ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    least = sum(max(o / p, b / bw) for s in ctx.steps
                for o, b in ctx.work("paged_attn").calls(ctx.cfg, s.lanes))
    return 100.0 * least / t
