"""The program's own totals of a traced window, for per-layer readers.

The engine's ``ServingObs`` keeps what it saw while the profiler
recorded in ``profiled`` (span host seconds and block-table entries,
``repro.obs.hooks.Profiled``); a traced run hands that hook object to
its readers as ``Context.obs``.  A program without ``profiled`` gives
``None``, and so do a window with no step and a context with no hook
object.
"""

from __future__ import annotations


def profiled(ctx):
    """``ServingObs.profiled`` of the engine that ran ``ctx.steps``, or
    None."""
    if not ctx.steps or ctx.obs is None:
        return None
    return getattr(ctx.obs, "profiled", None)
