"""Grouped MoE expert kernel: the least time the traced window's expert
work needs on the chip over the device time of the ``moe_expert_linear``
kernel in the trace.  The least time is the larger of the window's int8
operations of the live routed assignments over the int8 peak, and its
bytes over HBM bandwidth: the live rows' activations in and out
(``work/moe.py``) plus each touched expert's planes and scales once per
MoE layer call, the touched experts counted by the program
(``repro_moe_experts_touched_total`` kept in ``ServingObs.profiled``).
A program without that count gives nothing."""

from bench import program_obs

KERNELS = ("moe_expert_linear",)


def read(ctx):
    prof = program_obs.profiled(ctx)
    touched = getattr(prof, "moe_touched", None)
    if ctx.trace is None or not touched:
        return None
    t = ctx.trace.kernel_s(KERNELS)
    if not t:
        return None
    work = ctx.work("moe")
    calls = [c for s in ctx.steps for c in work.calls(ctx.cfg, s.lanes)]
    ops = sum(o for o, _ in calls)
    nbytes = sum(b for _, b in calls) + touched * work.expert_bytes(ctx.cfg)
    least = max(ops / ctx.peaks["int8_ops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
