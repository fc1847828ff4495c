"""Engine step loop: live token rows over dispatched token rows (padding
to batch and chunk buckets is the rest), from ``on_dispatch``."""


def read(ctx):
    lanes = sum(s.tok_lanes for s in ctx.steps)
    if not lanes:
        return None
    return 100.0 * sum(s.tok_live for s in ctx.steps) / lanes
