"""Readers of the program's own totals of the traced window
(``program_obs``): host milliseconds per engine step and the share of
block-table entries that are live, on hand-made totals and on a tiny
engine served under a profiler trace on the CPU."""

from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, program_obs, serve, spec, weights
from bench import traffic as T
from bench.serve import StepRecord
from bench.tests import tiny

READERS = ("engine.host_ms_per_step", "paged_attn.live_entry_share")


def _ctx(steps):
    return harness.Context(cfg=None, peaks={}, steps=steps, trace=None,
                           compiles=0)


def _records(n):
    return [StepRecord([(100 + i, 1)], 1, 1, 0, 0.5) for i in range(n)]


def test_readers_read_the_engine_that_ran_the_window():
    obs = serve.make_obs()
    obs.steps.extend(_records(4))
    obs.profiled.spans.update({"engine.step": [2, 0.072],
                               "engine.fetch": [3, 0.046],
                               "sched.plan": [2, 0.004]})
    obs.profiled.entries_live, obs.profiled.entries_walked = 62, 128
    ctx = _ctx(obs.steps[1:3])
    assert program_obs.profiled(ctx) is obs.profiled
    # (72 - 46) ms of host time over two steps
    assert spec.metric_reader(READERS[0])(ctx) == pytest.approx(13.0)
    assert spec.metric_reader(READERS[1])(ctx) == pytest.approx(
        100 * 62 / 128)


def test_readers_leave_out_what_the_program_does_not_give():
    obs = serve.make_obs()
    obs.steps.extend(_records(2))
    # a window the profiler saw no step or table of
    for name in READERS:
        assert spec.metric_reader(name)(_ctx(obs.steps)) is None
    # steps no engine ran, and no steps at all
    for steps in (_records(2), []):
        for name in READERS:
            assert spec.metric_reader(name)(_ctx(steps)) is None
    # a program whose hooks keep no totals
    obs.profiled.spans["engine.step"] = [1, 0.01]
    obs.profiled.entries_walked = 8
    del obs.profiled
    for name in READERS:
        assert spec.metric_reader(name)(_ctx(obs.steps)) is None


def _spans(log_dir):
    from jax.profiler import ProfileData
    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in ProfileData.from_file(str(path)).planes
            if not p.name.startswith("/device:")
            for ln in p.lines for e in ln.events
            if e.name in ("engine.step", "engine.fetch")]


def test_readers_on_a_tiny_engine_under_a_profiler_trace(tmp_path):
    from repro.serving.engine import Engine
    cfg_file = tiny.config("stablelm-3b.w3a8kv8")
    cfg = harness.model_config(cfg_file)
    quant = harness.quant_config(cfg_file)
    traffic = T.Traffic.from_dict(tiny.TINY_TRAFFIC)
    params = weights.make_params(weights.structure(cfg, quant), 3)
    obs = serve.make_obs()
    eng = Engine(params, cfg, quant=quant, metrics=obs,
                 **serve.engine_args(cfg_file, traffic))
    tables: list = []
    step_caches = eng.pool.step_caches

    def recording(tbl, lens, **kw):
        tables.append(np.array(tbl))
        return step_caches(tbl, lens, **kw)
    eng.pool.step_caches = recording
    loop = serve.Loop(eng, traffic, 5, cfg.vocab)
    loop.start()
    loop.ramp()
    for _ in range(3):          # untraced steps count nowhere
        loop.step()
    tables.clear()
    n0 = len(obs.steps)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(6):
            loop.step()
    finally:
        jax.profiler.stop_trace()
    ctx = _ctx(obs.steps[n0:])
    assert len(ctx.steps) == 6
    assert obs.profiled.spans["engine.step"][0] == 6
    # block 0 is the null block: every other entry came from a sequence
    live = sum(int(np.count_nonzero(t)) for t in tables)
    walked = sum(t.size for t in tables)
    assert 0 < live < walked
    assert spec.metric_reader(READERS[1])(ctx) == pytest.approx(
        100 * live / walked)
    # the same spans in the trace give the same host time
    spans = _spans(tmp_path)
    steps = [s for s in spans if s[0] == "engine.step"]
    assert len(steps) == 6
    host = [(b - a) - sum(y - x for n, x, y in spans
                          if n == "engine.fetch" and a <= x and y <= b)
            for _, a, b in steps]
    assert spec.metric_reader(READERS[0])(ctx) == pytest.approx(
        1e-6 * np.mean(host), rel=0.05, abs=0.05)
