"""Paged attention: block-table entries that hold a sequence's blocks
over the entries the kernel's grid walks (batch lanes x table width,
the rest pad to the bucketed width), over the traced window's
dispatches, from the program's ``on_tables`` hook."""

from bench import program_obs


def read(ctx):
    prof = program_obs.profiled(ctx)
    if prof is None or not prof.entries_walked:
        return None
    return 100.0 * prof.entries_live / prof.entries_walked
