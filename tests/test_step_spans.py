"""Step-phase spans and the block-table counter of the paged engine.

With a live ``ServingObs`` the paged step writes host spans on the
profiler's clock (``ServingObs.span``): ``engine.step`` holds one
``sched.plan``, then per dispatch ``engine.prepare`` (holding
``pool.step_caches``), ``engine.dispatch`` and ``engine.fetch``, then
``engine.advance``.  ``on_tables`` counts the block-table entries each
dispatch walks, those that hold a sequence's blocks, and the walked
entries by the paged attention body that runs them.  While the
profiler records, the facade keeps these totals, and each span's count
and host seconds, in ``profiled``.  With ``NULL_OBS`` the same requests
give the same tokens and no span.
"""

from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.obs import NULL_OBS, ServingObs

PREFIXES = ("engine.", "sched.", "pool.")
PHASES = ("engine.prepare", "engine.dispatch", "engine.fetch")


@pytest.fixture(scope="module")
def model():
    from repro.models import model as M
    cfg = get_config("stablelm-3b").reduced(n_layers=2, vocab=256)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(3))


def _requests(cfg):
    from repro.serving import engine as E
    rng = np.random.default_rng(7)
    return [E.Request(prompt=rng.integers(0, cfg.vocab, (n,),
                                          dtype=np.int32),
                      max_new_tokens=5) for n in (5, 11, 19)]


@pytest.fixture(scope="module")
def gqa_model():
    """The same model with one kv head for its four query heads: a
    decode tile holds 4 query rows a lane, a fused chunk tile 4 x 8."""
    import dataclasses
    from repro.models import model as M
    cfg = dataclasses.replace(get_config("stablelm-3b").reduced(
        n_layers=2, vocab=256), n_kv_heads=1)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(3))


def _traced_run(model, metrics, log_dir, tables=None):
    """Serve the requests under a profiler trace (none when ``log_dir``
    is None); returns their tokens, the engine, and the program's spans
    as ``(name, start, end, stats)``.  ``tables`` collects the block
    tables handed to the pool, each as ``(dispatch kind, table)``."""
    from jax.profiler import ProfileData
    from repro.models.config import QuantConfig
    from repro.serving import engine as E
    cfg, params = model
    eng = E.Engine(params, cfg, n_slots=3, max_len=64, paged=True,
                   block_size=4, chunk_tokens=8, metrics=metrics,
                   quant=QuantConfig(kv_bits=8))
    if tables is not None:
        step_caches = eng.pool.step_caches
        kind = [None]

        def recording(tbl, lens, **kw):
            tables.append((kind[0], np.array(tbl)))
            return step_caches(tbl, lens, **kw)
        eng.pool.step_caches = recording
        for k in ("decode", "fused"):
            dispatch = getattr(eng, f"_{k}_dispatch")

            def marked(*a, _k=k, _dispatch=dispatch):
                kind[0] = _k
                return _dispatch(*a)
            setattr(eng, f"_{k}_dispatch", marked)
    reqs = _requests(cfg)
    for r in reqs:
        eng.submit(r)
    if log_dir is None:
        eng.run()
        return [r.out for r in reqs], eng, []
    jax.profiler.start_trace(str(log_dir))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    assert all(r.done and r.error is None for r in reqs)
    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for p in ProfileData.from_file(str(path)).planes
             if not p.name.startswith("/device:")
             for ln in p.lines for e in ln.events
             if e.name.startswith(PREFIXES)]
    return [r.out for r in reqs], eng, sorted(spans, key=lambda s: s[1])


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_each_step_nests_its_phases(model, tmp_path):
    _, eng, spans = _traced_run(model, ServingObs(), tmp_path)
    steps = [s for s in spans if s[0] == "engine.step"]
    assert len(steps) == eng.steps > 0
    assert [s[3]["step_num"] for s in steps] == list(range(eng.steps))
    assert {s[3]["kind"] for s in steps} == {"decode", "fused"}
    assert all(1 <= s[3]["lanes"] <= 3 for s in steps)
    for step in steps:
        inner = [s for s in spans if s is not step and _inside(s, step)]
        names = [s[0] for s in inner]
        assert names.count("sched.plan") == 1
        assert names.count("engine.advance") == 1
        for phase in PHASES:
            assert names.count(phase) >= 1, (phase, names)
        fetches = [s for s in inner if s[0] == "engine.fetch"]
        assert all(f[3]["bytes"] > 0 for f in fetches)
        plan = next(s for s in inner if s[0] == "sched.plan")
        first = min(s[1] for s in inner if s[0] in PHASES)
        assert plan[2] <= first
        for prep in (s for s in inner if s[0] == "engine.prepare"):
            assert sum(_inside(s, prep) for s in inner
                       if s[0] == "pool.step_caches") == 1
    # every program span lies inside some step
    assert all(any(_inside(s, st) for st in steps) for s in spans)
    # the facade's totals count the same spans, for as long
    prof = eng.obs.profiled.spans
    assert {n: c for n, (c, _) in prof.items()} == {
        n: [s[0] for s in spans].count(n) for n in {s[0] for s in spans}}
    for name, (_, secs) in prof.items():
        traced = 1e-9 * sum(s[2] - s[1] for s in spans if s[0] == name)
        assert 0 < secs <= traced


def test_null_obs_is_token_identical_and_writes_no_span(model, tmp_path):
    on, _, spans_on = _traced_run(model, ServingObs(), tmp_path / "on")
    off, eng, spans_off = _traced_run(model, None, tmp_path / "off")
    assert eng.obs is NULL_OBS
    assert off == on
    assert spans_on and not spans_off


def test_table_counter_matches_the_dispatched_tables(model, tmp_path):
    tables: list = []
    _, eng, _ = _traced_run(model, True, tmp_path, tables=tables)
    assert tables
    reg = eng.obs.registry
    # block 0 is the null block: every other entry came from a sequence
    live = sum(int(np.count_nonzero(t)) for _, t in tables)
    walked = sum(t.size for _, t in tables)
    assert reg.value("repro_engine_table_entries", kind="live") == live
    assert reg.value("repro_engine_table_entries", kind="walked") == walked
    assert 0 < live < walked
    assert "repro_engine_table_entries_total{kind=\"live\"}" \
        in reg.render()
    prof = eng.obs.profiled
    assert (prof.entries_live, prof.entries_walked) == (live, walked)


def test_body_counter_splits_decode_from_fused_dispatches(gqa_model,
                                                          tmp_path):
    """Run through the Pallas kernels (interpret mode), decode dispatches
    (4 query rows a lane) walk their entries in the head-batched decode
    body, fused chunk dispatches (4 x 8 rows) in the tile body; the
    profiled total keeps the decode body's share."""
    from repro.kernels import ops
    tables: list = []
    ops.set_impl("interpret")
    try:
        _, eng, _ = _traced_run(gqa_model, True, tmp_path, tables=tables)
    finally:
        ops.set_impl(None)
    walked = {k: sum(t.size for kind, t in tables if kind == k)
              for k in ("decode", "fused")}
    assert walked["decode"] > 0 and walked["fused"] > 0
    reg = eng.obs.registry
    assert reg.value("repro_paged_attn_entries",
                     body="decode") == walked["decode"]
    assert reg.value("repro_paged_attn_entries",
                     body="tile") == walked["fused"]
    assert reg.value("repro_paged_attn_entries", body="reference") == 0
    assert "repro_paged_attn_entries_total{body=\"decode\"}" \
        in reg.render()
    assert eng.obs.profiled.entries_decode_body == walked["decode"]


def test_profiled_totals_count_only_while_a_trace_records(model):
    tables: list = []
    _, eng, _ = _traced_run(model, True, None, tables=tables)
    walked = sum(t.size for _, t in tables)
    reg = eng.obs.registry
    assert reg.value("repro_engine_table_entries",
                     kind="walked") == walked > 0
    # the CPU's default reference path runs no kernel body
    assert reg.value("repro_paged_attn_entries", body="reference") == walked
    assert reg.value("repro_paged_attn_entries", body="decode") == 0
    assert eng.obs.profiled.spans == {}
    assert eng.obs.profiled.entries_walked == 0
    assert eng.obs.profiled.entries_decode_body == 0
    assert eng.obs.span("engine.step", step_num=0) is NULL_OBS.span("x")


def test_unread_step_series_are_gone():
    reg = ServingObs().registry
    for name in ("repro_engine_step_seconds", "repro_engine_batch_lanes",
                 "repro_engine_padding_waste",
                 "repro_engine_chunk_budget_utilization"):
        assert reg.get(name) is None


def test_null_span_is_one_shared_no_op():
    span = NULL_OBS.span("engine.step", step_num=1)
    assert span is NULL_OBS.span("engine.fetch", bytes=4)
    with span as s:
        s.set_metadata(kind="decode", lanes=1)
    NULL_OBS.on_tables(live=1, walked=2, body="decode")
