"""KV pool: mean over steps of blocks in use over usable blocks, from the
occupancy ``on_step`` reports."""


def read(ctx):
    occ = [s.occupancy for s in ctx.steps if s.occupancy is not None]
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ)
