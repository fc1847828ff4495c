"""Model step: operations the live tokens of the traced window need
(every linear, the LM head and the attention, ``work/model_step.py``)
over the window's seconds times the chip's int8 peak (the linears run
A8 integer arithmetic)."""


def read(ctx):
    if ctx.trace is None or not ctx.steps:
        return None
    ops = sum(o for s in ctx.steps
              for o, _ in ctx.work("model_step").calls(ctx.cfg, s.lanes))
    return 100.0 * ops / (ctx.trace.window_s * ctx.peaks["int8_ops"])
