"""Paged attention: block-table entries walked by the head-batched
decode body over all entries the kernel's grid walks, over the traced
window's dispatches, from the program's ``on_tables`` hook.  A program
that counts no body gives nothing."""

from bench import program_obs


def read(ctx):
    prof = program_obs.profiled(ctx)
    decode = getattr(prof, "entries_decode_body", None)
    if decode is None or not prof.entries_walked:
        return None
    return 100.0 * decode / prof.entries_walked
