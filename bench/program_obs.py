"""The program's own totals of a traced window, for per-layer readers.

A reader gets only its ``Context``.  The engine's ``ServingObs`` keeps
what it saw while the profiler recorded in ``profiled`` (span host
seconds and block-table entries, ``repro.obs.hooks.Profiled``), and the
run still holds that object while its readers run: it is the hook
object whose ``steps`` hold the context's step records.  A program
without ``profiled`` gives ``None``, and so does a window with no step.
"""

from __future__ import annotations

import gc


def profiled(ctx):
    """``ServingObs.profiled`` of the engine that ran ``ctx.steps``, or
    None."""
    if not ctx.steps:
        return None
    from repro.obs import ServingObs
    first = ctx.steps[0]
    for obj in gc.get_objects():
        if isinstance(obj, ServingObs) and any(
                s is first for s in getattr(obj, "steps", ())):
            return getattr(obj, "profiled", None)
    return None
