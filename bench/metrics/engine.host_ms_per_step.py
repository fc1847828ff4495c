"""Engine step loop: host milliseconds per step over the traced window,
the mean of each ``engine.step`` span's length less what its
``engine.fetch`` spans (the wait for the device and the logits'
read-back) take.  Timed by the program's spans (``ServingObs.span``)."""

from bench import program_obs


def read(ctx):
    prof = program_obs.profiled(ctx)
    if prof is None:
        return None
    n, step_s = prof.spans.get("engine.step", (0, 0.0))
    if not n:
        return None
    return 1e3 * (step_s - prof.spans.get("engine.fetch", (0, 0.0))[1]) / n
