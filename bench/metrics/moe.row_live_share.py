"""Grouped MoE expert kernel: live routed assignments over the rows the
kernel computes for them (whole tiles of one expert each, so tile
padding is the rest), over the traced window's MoE layer calls, from the
program's ``repro_moe_assignments_total`` counts kept in
``ServingObs.profiled``.  A program without them gives nothing."""

from bench import program_obs


def read(ctx):
    prof = program_obs.profiled(ctx)
    computed = getattr(prof, "moe_computed", None)
    if not computed:
        return None
    return 100.0 * prof.moe_live / computed
