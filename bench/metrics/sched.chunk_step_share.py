"""Scheduler: share of steps that carry a prefill chunk, from
``on_chunk`` and ``on_step``."""


def read(ctx):
    if not ctx.steps:
        return None
    return 100.0 * sum(s.chunk_tokens > 0 for s in ctx.steps) / len(ctx.steps)
