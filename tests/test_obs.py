"""Observability subsystem: metrics registry, request traces, hooks.

ISSUE 7 added ``src/repro/obs``: a dependency-free metrics registry
(Prometheus text exposition), per-request lifecycle span trees with
Chrome/Perfetto export, and the ``ServingObs`` facade the engine /
scheduler / pool report through.  This suite is its contract:

* **Registry semantics**: get-or-create declaration, kind-conflict
  rejection, label children, cumulative histogram exposition, and the
  exact Prometheus text format ``render()`` promises.
* **Span discipline**: double-begin / end-unopened / double-close all
  raise; ``finish`` auto-closes; ``validate`` rejects events outside
  the request envelope.
* **Trace integrity under churn**: a scheduler walk mixing submits,
  chunked steps, cancellations and preemptions -- plus engine-level
  cancellation and deadline expiry -- leaves EVERY submitted request
  with a balanced span tree (``Tracer.validate_all``), mirroring the
  zero-leak block/slot invariants in tests/test_continuous_batching.py
  on the metrics side: the registry's accounting must agree with the
  pool's ``validate()``-checked state after the drain.
* **Token identity off**: ``metrics=None`` (the default) produces
  byte-identical outputs to an instrumented run and leaves no trace
  state on the requests -- observability is a pure overlay.
* **Deterministic timestamps**: under an injected clock two identical
  runs export identical Perfetto JSON.
"""

import json

import numpy as np
import pytest

from repro.configs import get_config
from repro.obs import (NULL_OBS, LATENCY_BUCKETS, Counter, Histogram,
                       MetricsRegistry, ServingObs, Tracer)
from repro.obs.trace import RequestTrace
from repro.serving.paged_cache import PagedKVPool
from repro.serving.scheduler import Scheduler


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_renders_prometheus_total_convention():
    r = MetricsRegistry()
    c = r.counter("repro_test_events", "things that happened")
    c.inc()
    c.inc(2)
    text = r.render()
    assert "# HELP repro_test_events things that happened" in text
    assert "# TYPE repro_test_events counter" in text
    assert "repro_test_events_total 3" in text
    assert r.value("repro_test_events") == 3


def test_labeled_counter_children_are_cached_and_rendered_sorted():
    r = MetricsRegistry()
    c = r.counter("repro_test_finished", "by reason",
                  labelnames=("reason",))
    a = c.labels(reason="length")
    assert c.labels(reason="length") is a      # cached child
    a.inc()
    c.labels(reason="cancelled").inc(2)
    text = r.render()
    i_c = text.index('repro_test_finished_total{reason="cancelled"} 2')
    i_l = text.index('repro_test_finished_total{reason="length"} 1')
    assert i_c < i_l                           # children sorted by value
    assert r.value("repro_test_finished", reason="cancelled") == 2
    with pytest.raises(ValueError):
        c.labels(kind="length")                # wrong label name


def test_registry_get_or_create_and_kind_conflict():
    r = MetricsRegistry()
    c1 = r.counter("repro_test_x", "first")
    c2 = r.counter("repro_test_x", "ignored duplicate help")
    assert c1 is c2
    with pytest.raises(ValueError):
        r.gauge("repro_test_x", "now a gauge")
    with pytest.raises(ValueError):
        r.counter("repro_test_x", "relabeled", labelnames=("a",))


def test_histogram_cumulative_buckets_sum_count_percentile():
    r = MetricsRegistry()
    h = r.histogram("repro_test_lat", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    text = h.render()
    assert 'repro_test_lat_bucket{le="0.1"} 1' in text
    assert 'repro_test_lat_bucket{le="1"} 3' in text    # cumulative
    assert 'repro_test_lat_bucket{le="10"} 4' in text
    assert 'repro_test_lat_bucket{le="+Inf"} 5' in text
    assert "repro_test_lat_sum 56.05" in text
    assert "repro_test_lat_count 5" in text
    assert h.percentile(50) == 1.0             # upper edge of q-bucket
    assert h.percentile(99) == float("inf")    # overflow bucket
    assert Histogram("empty", "").percentile(50) == 0.0


def test_gauge_set_inc_dec():
    r = MetricsRegistry()
    g = r.gauge("repro_test_occ", "occupancy")
    g.set(0.5)
    g.inc(0.25)
    g.dec(0.5)
    assert g.value == pytest.approx(0.25)
    snap = r.snapshot()
    assert snap["repro_test_occ"] == pytest.approx(0.25)


def test_snapshot_flattens_all_kinds():
    r = MetricsRegistry()
    r.counter("repro_test_c").inc(2)
    r.histogram("repro_test_h", buckets=LATENCY_BUCKETS).observe(0.5)
    snap = r.snapshot()
    assert snap["repro_test_c_total"] == 2
    assert snap["repro_test_h_sum"] == pytest.approx(0.5)
    assert snap["repro_test_h_count"] == 1


# ---------------------------------------------------------------------------
# Span discipline and trace validation
# ---------------------------------------------------------------------------

def test_span_double_begin_end_unopened_double_close_raise():
    tr = RequestTrace(0, "r", t_submit=0.0)
    tr.begin("queued", 1.0)
    with pytest.raises(RuntimeError, match="already open"):
        tr.begin("queued", 2.0)
    with pytest.raises(RuntimeError, match="unopened"):
        tr.end("decode", 2.0)
    tr.end("queued", 2.0)
    with pytest.raises(RuntimeError, match="unopened"):
        tr.end("queued", 3.0)                  # popped: cannot end twice
    s = tr.spans[0]
    with pytest.raises(RuntimeError, match="closed twice"):
        s.close(4.0)


def test_finish_autocloses_open_spans_and_validate_passes():
    tr = RequestTrace(0, "r", t_submit=0.0)
    tr.begin("queued", 0.0)
    tr.end("queued", 1.0)
    tr.begin("running", 1.0)
    tr.begin("decode", 2.0)                    # both left open on purpose
    tr.token(3.0, 0, 17)
    with pytest.raises(AssertionError, match="not finished"):
        tr.validate()
    tr.finish(4.0, "cancelled")
    tr.validate()                              # balanced now
    assert tr.ttft == pytest.approx(3.0)
    assert all(not s.open for s in tr.spans)
    assert tr.finish_reason == "cancelled"


def test_validate_rejects_events_outside_envelope():
    tr = RequestTrace(0, "r", t_submit=1.0)
    tr.complete("chunk_prefill", 0.2, 0.5)     # before submission
    tr.finish(2.0, "length")
    with pytest.raises(AssertionError, match="outside envelope"):
        tr.validate()
    tr2 = RequestTrace(1, "r", t_submit=0.0)
    tr2.instant("token", 5.0)
    tr2.finish(2.0, "length")
    with pytest.raises(AssertionError, match="outside envelope"):
        tr2.validate()


def test_tracer_export_perfetto_schema():
    tc = Tracer()
    tr = tc.start(0.0, "req A")
    tr.begin("queued", 0.0)
    tr.end("queued", 0.001)
    tr.complete("chunk_prefill", 0.001, 0.002, dict(index=0, tokens=4))
    tr.token(0.003, 0, 42)
    tr.finish(0.004, "length")
    doc = tc.export()
    json.loads(json.dumps(doc))                # serializable round-trip
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    roots = [e for e in evs if e["ph"] == "X" and e["name"] == "request"]
    assert len(roots) == 1
    assert roots[0]["ts"] == 0.0 and roots[0]["dur"] == \
        pytest.approx(4000.0)                  # seconds -> microseconds
    assert roots[0]["args"]["finish_reason"] == "length"
    assert roots[0]["args"]["n_tokens"] == 1
    names = {e["name"] for e in evs}
    assert {"process_name", "thread_name", "queued",
            "chunk_prefill", "token"} <= names
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and all(e["s"] == "t" for e in inst)


# ---------------------------------------------------------------------------
# Trace integrity + metrics accounting under scheduler churn
# ---------------------------------------------------------------------------

class _Tick:
    """Deterministic strictly-increasing clock (1ms per read)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.001
        return self.t


class _WalkReq:
    def __init__(self, prompt, max_new_tokens):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = 0.0
        self.out = []
        self.done = False
        self.error = None
        self.finish_reason = None


def _obs_stub_step(sch, chunk, obs):
    """One chunked engine step without the model, with the engine's hook
    placement: admit, plan, capacity, then advance exactly the way
    Engine._advance reports chunks and decode starts."""
    sch.admit_chunked()
    plan = sch.plan_step()
    plan = sch.ensure_step_capacity(plan)
    t0 = obs.t()
    for seq, n in plan:
        if seq.prefilling:
            seq.length += n
            sch.register_progress(seq)
            obs.on_chunk(seq, n, t0, obs.t())
            if seq.length < len(seq.pending):
                continue
            seq.pending = None
            obs.on_decode_begin(seq)
            if seq.req.out:                    # warm resume
                seq.last_tok = seq.req.out[-1]
                continue
            tok = int((seq.length * 13 + 7) % 97)
            seq.last_tok = tok
            seq.req.out.append(tok)
            obs.on_token(seq.req, tok)
        else:
            tok = int((seq.length * 13 + 7) % 97)
            seq.last_tok = tok
            seq.req.out.append(tok)
            obs.on_token(seq.req, tok)
            seq.length += 1
        if len(seq.req.out) >= seq.req.max_new_tokens \
                or seq.length >= sch.max_len - 1:
            sch.finish(seq)


def test_walk_every_request_traces_balanced_and_metrics_agree():
    """Deterministic churn walk: submits, chunked steps, cancellations
    (running + waiting) and preemptions, then a full drain.  Every
    request's span tree must validate, and the registry's accounting
    must mirror the pool's zero-leak state."""
    import dataclasses
    cfg = get_config("mixtral-8x7b").reduced(n_layers=2, window=8)
    kv8 = dataclasses.replace(cfg.quant, w_bits=None, kv_bits=8)
    obs = ServingObs(clock=_Tick())
    pool = PagedKVPool(cfg, n_blocks=9, block_size=4, quant=kv8,
                       metrics=obs.registry)
    sch = Scheduler(pool, max_len=32, max_batch=4, chunk_tokens=3,
                    obs=obs)
    base = np.arange(24, dtype=np.int32)
    reqs = []

    def submit(n, max_new):
        req = _WalkReq(base[:n].copy(), max_new)
        obs.on_submit(req)                     # the engine's duty
        sch.submit(req)
        reqs.append(req)
        return req

    a = submit(20, 4)
    b = submit(18, 4)                          # shares a's chain
    _obs_stub_step(sch, 3, obs)
    _obs_stub_step(sch, 3, obs)
    assert any(s.prefilling for s in sch.running)
    c = submit(6, 6)
    # preempt the youngest mid-walk: its trace reopens "queued"
    victim = max(sch.running, key=lambda s: s.admitted_at)
    sch.preempt(victim)
    _obs_stub_step(sch, 3, obs)
    assert sch.cancel(b)                       # cancel wherever b lives
    d = submit(2, 30)                          # long decode, then cancel
    for _ in range(6):
        _obs_stub_step(sch, 3, obs)
    assert sch.cancel(d)
    steps = 0
    while sch.has_work:
        _obs_stub_step(sch, 3, obs)
        steps += 1
        assert steps < 500
    # every submitted request finished with a balanced span tree
    assert all(r.done for r in reqs)
    obs.tracer.validate_all()
    assert len(obs.tracer.traces) == len(reqs)
    for r in (b, d):
        assert r._trace.finish_reason == "cancelled"
    assert victim.req._trace.n_preemptions >= 1
    q_spans = [s for s in victim.req._trace.spans if s.name == "queued"]
    assert len(q_spans) >= 2, "preemption must re-open the queued span"
    # metrics mirror the pool's zero-leak invariants
    pool.validate()
    assert pool.free_blocks == pool.n_usable
    reg = obs.registry
    pool.sync_gauges()
    assert reg.value("repro_pool_blocks", state="used") == 0
    assert reg.value("repro_pool_blocks", state="free") \
        + reg.value("repro_pool_blocks", state="cached") == pool.n_usable
    # lifecycle accounting: everything submitted was finished, queue
    # waits were observed once per admission, tokens balance.  The
    # finished{reason} label set is Request.FINISH_REASONS -- summing
    # over THE enum (not a hand list) proves no reason escapes it
    from repro.serving.engine import Request
    fin = reg.get("repro_requests_finished")
    assert set(fin._children) <= {(rs,) for rs in Request.FINISH_REASONS}, \
        (set(fin._children), Request.FINISH_REASONS)
    n_fin = sum(reg.value("repro_requests_finished", reason=rs)
                for rs in Request.FINISH_REASONS)
    assert reg.value("repro_requests_submitted") == len(reqs) == n_fin
    hq = reg.get("repro_request_queue_wait_seconds")
    assert hq.count == reg.value("repro_sched_admissions")
    n_toks = sum(len(r.out) for r in reqs)
    assert reg.value("repro_engine_tokens") == n_toks
    emitted = sum(1 for r in reqs if r.out)
    assert reg.get("repro_request_ttft_seconds").count == emitted
    assert reg.get("repro_request_intertoken_seconds").count \
        == n_toks - emitted
    assert reg.value("repro_sched_preemptions") == sch.n_preemptions >= 1


def test_scheduler_without_obs_runs_on_null_obs():
    """A standalone scheduler (no engine) must run against NULL_OBS and
    untraced requests without error -- hooks tolerate both."""
    cfg = get_config("mamba2-130m").reduced()
    pool = PagedKVPool(cfg, n_blocks=4, block_size=4, n_state_slots=4,
                       prefix_cache=False)
    sch = Scheduler(pool, max_len=32, max_batch=4, chunk_tokens=3)
    assert sch.obs is NULL_OBS
    req = _WalkReq(np.arange(5, dtype=np.int32), 2)
    sch.submit(req)
    while sch.has_work:
        _obs_stub_step(sch, 3, NULL_OBS)
    assert req.done and not hasattr(req, "_trace")
    # a TRACED scheduler still accepts untraced requests (e.g. mixed
    # callers): hooks fall through on the missing _trace
    obs = ServingObs(clock=_Tick())
    sch2 = Scheduler(PagedKVPool(cfg, n_blocks=4, block_size=4,
                                 n_state_slots=4, prefix_cache=False,
                                 metrics=obs.registry),
                     max_len=32, max_batch=4, chunk_tokens=3, obs=obs)
    req2 = _WalkReq(np.arange(5, dtype=np.int32), 2)
    sch2.submit(req2)                          # no on_submit first
    while sch2.has_work:
        _obs_stub_step(sch2, 3, obs)
    assert req2.done and not obs.tracer.traces


# ---------------------------------------------------------------------------
# Engine integration (real model, reduced configs)
# ---------------------------------------------------------------------------

def _engine(cfg, params, **kw):
    from repro.serving import engine as E
    return E.Engine(params, cfg, n_slots=2, max_len=32, **kw)


def _setup(name="mamba2-130m", **red):
    import jax
    from repro.models import model as M
    cfg = get_config(name).reduced(**red)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _mk_reqs(cfg, lens, max_new=4, seed=3, **kw):
    from repro.serving import engine as E
    rng = np.random.default_rng(seed)
    return [E.Request(prompt=rng.integers(0, cfg.vocab, (n,),
                                          dtype=np.int32),
                      max_new_tokens=max_new, **kw) for n in lens]


def test_metrics_disabled_is_token_identical_and_traceless():
    """``metrics=None`` (the default) must be a pure overlay: the same
    tokens as an instrumented run, NULL_OBS on the engine, and no trace
    state attached to the requests."""
    cfg, params = _setup()
    outs = {}
    for on in (False, True):
        eng = _engine(cfg, params, paged=True, block_size=4,
                      chunk_tokens=3, metrics=(True if on else None))
        reqs = _mk_reqs(cfg, (5, 9, 14))
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done and r.error is None for r in reqs)
        outs[on] = [r.out for r in reqs]
        if on:
            eng.obs.tracer.validate_all()
            assert all(hasattr(r, "_trace") for r in reqs)
        else:
            assert eng.obs is NULL_OBS
            assert not any(hasattr(r, "_trace") for r in reqs)
    assert outs[False] == outs[True]


def test_engine_mixed_workload_traces_prometheus_and_report_agree():
    """The acceptance scenario: a mixed workload (prefix sharing, a
    mid-flight cancellation) on the instrumented chunked engine yields
    (a) valid Perfetto JSON, (b) a Prometheus snapshot whose counters
    exactly match the pool's validate()-checked accounting and the
    legacy report() dict, (c) balanced traces for every request."""
    import dataclasses
    cfg, params = _setup("mixtral-8x7b", n_layers=2, window=8)
    kv8 = dataclasses.replace(cfg.quant, w_bits=None, kv_bits=8)
    eng = _engine(cfg, params, quant=kv8, paged=True, block_size=4,
                  chunk_tokens=3, metrics=True, clock=_Tick())
    base = np.arange(20, dtype=np.int32)
    from repro.serving import engine as E
    a = E.Request(prompt=base.copy(), max_new_tokens=4)
    b = E.Request(prompt=base[:14].copy(), max_new_tokens=4)  # shares a
    c = E.Request(prompt=base[:16].copy(), max_new_tokens=8)
    eng.submit(a)
    eng.run()                                  # a's chain lands + parks
    eng.submit(b)                              # re-acquires a's blocks
    eng.submit(c)
    eng.step()
    eng.step()
    assert eng.cancel(c)                       # mid-flight cancellation
    eng.run()
    assert a.done and b.done and a.finish_reason == "length"

    reg = eng.obs.registry
    eng.obs.tracer.validate_all()
    assert len(eng.obs.tracer.traces) == 3
    assert c._trace.finish_reason == "cancelled"
    doc = eng.obs.tracer.export()
    json.loads(json.dumps(doc))                # valid Perfetto JSON
    assert len([e for e in doc["traceEvents"]
                if e["name"] == "request"]) == 3

    # registry == legacy report() == pool properties, one source of truth
    rep = eng.report()
    assert reg.value("repro_pool_cow") == eng.pool.n_cow \
        == rep["cow_copies"]
    assert reg.value("repro_pool_prefix_hits") == eng.pool.n_prefix_hits \
        == rep["prefix_hits"]
    assert reg.value("repro_pool_prefix_hit_tokens") \
        == eng.pool.n_hit_tokens == rep["prefix_hit_tokens"]
    assert reg.value("repro_sched_preemptions") \
        == eng.scheduler.n_preemptions == rep["preemptions"]
    assert reg.value("repro_engine_prefill_tokens") \
        == rep["chunk_tokens_processed"] == eng.chunk_tokens_processed
    assert rep["prefix_hit_tokens"] > 0, "b must share a's chain"

    # lifecycle balance
    assert reg.value("repro_requests_submitted") == 3
    assert reg.value("repro_requests_finished", reason="length") == 2
    assert reg.value("repro_requests_finished", reason="cancelled") == 1
    n_toks = sum(len(r.out) for r in (a, b, c))
    assert reg.value("repro_engine_tokens") == n_toks
    emitted = sum(1 for r in (a, b, c) if r.out)
    assert reg.get("repro_request_ttft_seconds").count == emitted
    assert reg.get("repro_request_intertoken_seconds").count \
        == n_toks - emitted
    assert reg.value("repro_engine_steps") == eng.steps > 0

    # the Prometheus text itself carries the counters
    text = reg.render()
    assert 'repro_requests_finished_total{reason="cancelled"} 1' in text
    assert f"repro_engine_tokens_total {n_toks}" in text
    # drained: the used-blocks gauge agrees with the empty pool
    assert reg.value("repro_pool_blocks", state="used") == 0
    assert eng.pool.free_blocks == eng.pool.n_usable


def test_timeout_and_rejection_traces_close_balanced():
    """Deadline expiry (running mid-prefill AND still waiting) and
    submit-time rejection must all close their traces with the right
    finish_reason -- no dangling spans on any exit path."""
    import dataclasses
    cfg, params = _setup("mixtral-8x7b", n_layers=2, window=8)
    kv8 = dataclasses.replace(cfg.quant, w_bits=None, kv_bits=8)
    t = [0.0]
    eng = _engine(cfg, params, quant=kv8, paged=True, block_size=4,
                  max_batch=2, chunk_tokens=3, metrics=True,
                  clock=lambda: t[0])
    rng = np.random.default_rng(6)
    from repro.serving import engine as E
    a = E.Request(prompt=rng.integers(0, cfg.vocab, (4,), dtype=np.int32),
                  max_new_tokens=6)
    b = E.Request(prompt=rng.integers(0, cfg.vocab, (24,), dtype=np.int32),
                  max_new_tokens=2, timeout=5.0)
    c = E.Request(prompt=rng.integers(0, cfg.vocab, (4,), dtype=np.int32),
                  max_new_tokens=2, timeout=7.0)
    big = E.Request(prompt=rng.integers(0, cfg.vocab, (40,),
                                        dtype=np.int32),
                    max_new_tokens=2)          # prompt >= max_len - 1
    for r in (a, b, c, big):
        eng.submit(r)
    assert big.done and big.finish_reason == "rejected"
    for _ in range(3):                         # b mid-prefill, c waiting
        assert eng.step()
    assert any(s.req is b and s.prefilling
               for s in eng.scheduler.running)
    t[0] = 10.0
    eng.run()
    assert a.done and a.finish_reason == "length"
    for r in (b, c):
        assert r.finish_reason == "timeout" and r.out == []
    eng.obs.tracer.validate_all()
    reg = eng.obs.registry
    assert reg.value("repro_requests_finished", reason="timeout") == 2
    assert reg.value("repro_requests_finished", reason="rejected") == 1
    assert big._trace.finish_reason == "rejected"
    assert b._trace.finish_reason == "timeout"
    # timeout/rejection emitted nothing: no token instants on them
    for r in (b, c, big):
        assert r._trace.token_times == []
    assert eng.pool.free_blocks == eng.pool.n_usable


def test_contiguous_engine_is_instrumented_too():
    """The same hooks cover the contiguous (non-paged) engine: traces
    balance through queue-cancel, lane expiry, and length finish."""
    cfg, params = _setup()
    t = [0.0]
    eng = _engine(cfg, params, metrics=True, clock=lambda: t[0])
    from repro.serving import engine as E
    rng = np.random.default_rng(12)
    mk = lambda n, **kw: E.Request(
        prompt=rng.integers(0, cfg.vocab, (4,), dtype=np.int32),
        max_new_tokens=n, **kw)
    a, b, c = mk(6), mk(8, timeout=5.0), mk(2)
    eng.submit(a), eng.submit(b), eng.submit(c)
    assert eng.cancel(c)                       # straight from the queue
    eng.step()
    t[0] = 10.0
    eng.run()
    assert a.finish_reason == "length" and b.finish_reason == "timeout"
    eng.obs.tracer.validate_all()
    reg = eng.obs.registry
    assert reg.value("repro_requests_submitted") == 3
    assert reg.value("repro_requests_finished", reason="cancelled") == 1
    assert reg.value("repro_requests_finished", reason="timeout") == 1
    assert reg.value("repro_engine_tokens") \
        == len(a.out) + len(b.out)


def test_identical_runs_export_identical_timelines():
    """Full determinism under an injected clock: two engines driven by
    identical tick clocks over identical workloads must export equal
    Perfetto documents and equal metric snapshots."""
    cfg, params = _setup()
    docs, snaps = [], []
    for _ in range(2):
        eng = _engine(cfg, params, paged=True, block_size=4,
                      chunk_tokens=3, metrics=True, clock=_Tick())
        reqs = _mk_reqs(cfg, (5, 9), max_new=3)
        for r in reqs:
            eng.submit(r)
        eng.run()
        docs.append(eng.obs.tracer.export())
        snaps.append(eng.obs.registry.snapshot())
    assert docs[0] == docs[1]
    assert snaps[0] == snaps[1]


def test_engine_adopts_obs_clock_and_binds_its_own():
    """Clock unification (satellite 2): an engine given a ServingObs
    with a clock adopts it for deadlines; an engine given its own clock
    binds that clock onto the obs facade."""
    cfg, params = _setup()
    tick = _Tick()
    obs = ServingObs(clock=tick)
    eng = _engine(cfg, params, metrics=obs)
    assert eng._clock is tick and eng.obs is obs
    t = [0.0]
    reg = MetricsRegistry()
    eng2 = _engine(cfg, params, metrics=reg, clock=lambda: t[0])
    assert eng2.obs.clock() == 0.0 and eng2.obs.registry is reg
    with pytest.raises(TypeError):
        _engine(cfg, params, metrics=object())
