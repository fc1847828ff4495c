"""Fused quantized linear: the least time the window's linear work needs
on the chip (per call, the larger of its int8 ops over the int8 peak and
its bytes over HBM bandwidth; ``work/apmm.py``) over the device time of
the ``apmm_fused_linear`` kernel in the trace."""

KERNELS = ("apmm_fused_linear",)


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s(KERNELS)
    if not t:
        return None
    p, bw = ctx.peaks["int8_ops"], ctx.peaks["hbm_bytes_per_s"]
    least = sum(max(o / p, b / bw) for s in ctx.steps
                for o, b in ctx.work("apmm").calls(ctx.cfg, s.lanes))
    return 100.0 * least / t
