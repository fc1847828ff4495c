"""Chaos suite: the serving-hardening contract under deterministic,
seeded fault injection (ISSUE 9).

The claims under test, each locked by a property or a pinned scenario:

* **Determinism of the injector itself**: one seed -> one fault
  schedule, so every failure found here replays exactly; the
  ``NULL_FAULTS`` twin is inert.
* **Memory-fault transparency**: injected alloc/slot exhaustion,
  forced prefix-cache eviction, admission races and preemption storms
  may delay requests but never change their tokens -- every request
  completes bit-identical to the fault-free twin, refcounts stay exact
  (external Counter model) after every walk op, and the drain leaks
  zero blocks and zero state slots.
* **Step-level containment**: a poisoned (non-finite) logits row or a
  raising ``on_token`` callback quarantines exactly the offending
  request (``finish_reason='error'``, cause on ``.error``) while the
  rest of the batch stays bit-identical to a fault-free run.
* **Watchdog recovery**: ``validate_every`` catches corrupted pool
  bookkeeping and corrupted block tables; recovery rebuilds the free
  lists from the surviving tables and quarantines only the chains it
  cannot trust -- then passes the full invariant check it guards.
* **Backpressure**: ``max_queue`` sheds with ``finish_reason=
  'rejected'`` + a ``retry_after`` hint, and ``StreamHandle.resubmit``
  gets the request back in once the queue drains.

Fault-free overhead and recovery latency are gated in
benchmarks/fault_recovery.py (bench-smoke).
"""

import dataclasses
from collections import Counter

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:    # property tests skip (not error) without hypothesis
    from _hypothesis_fallback import given, settings, st

from repro.configs import get_config
from repro.models import model as M
from repro.serving import engine as E
from repro.serving.faults import FaultInjector, NULL_FAULTS, PoolExhausted
from repro.serving.paged_cache import PagedKVPool
from repro.serving.scheduler import Scheduler


def _setup(name="mixtral-8x7b", **red):
    cfg = get_config(name).reduced(**red)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _kv8(cfg):
    return dataclasses.replace(cfg.quant, w_bits=None, kv_bits=8)


class _WalkReq:
    """Minimal stand-in for engine.Request (identity the scheduler needs)."""
    def __init__(self, prompt, max_new_tokens):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = 0.0
        self.out = []
        self.done = False
        self.error = None
        self.finish_reason = None


def _check_pool(pool, sch):
    """Exactness under chaos: pool internals self-consistent, every
    block's refcount equals the number of running tables mapping it,
    every running stateful request holds exactly one slot."""
    pool.validate()
    if pool.needs_blocks:
        model = Counter(int(b) for s in sch.running for b in s.blocks)
        actual = {b: r for b, r in pool._ref.items() if r > 0}
        assert dict(model) == actual, (dict(model), actual)
    if pool.slots is not None:
        assert all(s.slot >= 0 for s in sch.running)
        assert pool.slots.free_slots \
            == pool.slots.n_slots - len(sch.running)


def _chaos_stub_step(sch, chunk):
    """One engine step without the model, with the engine's step-level
    containment: a transient pool fault the scheduler could not absorb
    aborts the step (state intact), exactly like Engine._paged_step."""
    try:
        sch.admit_chunked()
        plan = sch.ensure_step_capacity(sch.plan_step())
    except PoolExhausted:
        return
    for seq, n in plan:
        if seq.req.done:
            continue
        if seq.prefilling:
            seq.length += n
            sch.register_progress(seq)
            if seq.length < len(seq.pending):
                continue
            seq.pending = None
            if seq.req.out:                     # warm resume
                seq.last_tok = seq.req.out[-1]
                continue
            tok = int((seq.length * 13 + 7) % 97)
            seq.last_tok = tok
            seq.req.out.append(tok)
        else:
            tok = int((seq.length * 13 + 7) % 97)
            seq.last_tok = tok
            seq.req.out.append(tok)
            seq.length += 1
        if len(seq.req.out) >= seq.req.max_new_tokens \
                or seq.length >= sch.max_len - 1:
            sch.finish(seq)


def _chaos_walk(ops, lengths, max_news, chunk, fseed, *, name="mixtral-8x7b"):
    """Random chunked traffic with memory faults armed: refcounts stay
    exact after every op and the drain leaks nothing."""
    if name == "mamba2-130m":
        faults = FaultInjector(fseed, p_slot_fail=0.3, p_admit_race=0.25,
                               p_preempt_storm=0.1)
        cfg = get_config(name).reduced()
        pool = PagedKVPool(cfg, n_blocks=4, block_size=4,
                           n_state_slots=4, prefix_cache=False,
                           faults=faults)
    else:
        faults = FaultInjector(fseed, p_alloc_fail=0.1, p_forced_evict=0.25,
                               p_admit_race=0.25, p_preempt_storm=0.1)
        cfg = get_config(name).reduced(n_layers=2, window=8)
        pool = PagedKVPool(cfg, n_blocks=9, block_size=4, quant=_kv8(cfg),
                           faults=faults)
    sch = Scheduler(pool, max_len=32, max_batch=4, chunk_tokens=chunk)
    assert sch.faults is faults, "scheduler must inherit the pool's injector"
    bases = [np.arange(24, dtype=np.int32),
             np.concatenate([np.arange(8),
                             np.arange(50, 66)]).astype(np.int32)]
    for i, op in enumerate(ops):
        ln = 1 + lengths[i % len(lengths)] % 20
        if op == 0:                                    # submit
            sch.submit(_WalkReq(bases[i % 2][:ln].copy(),
                                1 + max_news[i % len(max_news)] % 16))
        elif op in (1, 2):                             # one engine step
            _chaos_stub_step(sch, chunk)
        elif op == 3:                                  # cancel anywhere
            reqs = [s.req for s in sch.running] + list(sch.waiting)
            if reqs:
                assert sch.cancel(reqs[i % len(reqs)])
        elif op == 4 and sch.running:                  # preempt youngest
            sch.preempt(max(sch.running, key=lambda s: s.admitted_at))
        _check_pool(pool, sch)
    steps = 0
    while sch.has_work:                                # drain
        _chaos_stub_step(sch, chunk)
        _check_pool(pool, sch)
        steps += 1
        assert steps < 8000, "drain did not terminate under faults"
    assert pool.free_blocks == pool.n_usable, \
        "chaos walk leaked blocks"
    if pool.slots is not None:
        assert pool.slots.free_slots == pool.slots.n_slots, \
            "chaos walk leaked state slots"
    # a quarantine-free walk must finish (not error) every uncancelled
    # request: memory faults are transparent to the outcome
    return faults


# ---------------------------------------------------------------------------
# The injector itself
# ---------------------------------------------------------------------------

def test_injector_is_deterministic_and_null_is_inert():
    mk = lambda: FaultInjector(7, p_alloc_fail=0.4, p_admit_race=0.5,
                               p_nan_logits=0.3)
    a, b = mk(), mk()
    sched_a = [(a.alloc_fail(1), a.admit_race(), a.nan_logits(None))
               for _ in range(300)]
    sched_b = [(b.alloc_fail(1), b.admit_race(), b.nan_logits(None))
               for _ in range(300)]
    assert sched_a == sched_b, "same seed must replay the same schedule"
    assert a.fired == b.fired
    assert a.fired["alloc_fail"] > 0 and a.fired["admit_race"] > 0
    # a different seed gives a different schedule (vanishingly unlikely
    # to collide over 900 draws)
    c = FaultInjector(8, p_alloc_fail=0.4, p_admit_race=0.5,
                      p_nan_logits=0.3)
    sched_c = [(c.alloc_fail(1), c.admit_race(), c.nan_logits(None))
               for _ in range(300)]
    assert sched_c != sched_a
    # the disabled twin: constant False everywhere, nothing retained
    assert NULL_FAULTS.enabled is False
    assert not any([NULL_FAULTS.alloc_fail(5), NULL_FAULTS.slot_fail(),
                    NULL_FAULTS.forced_evict(), NULL_FAULTS.admit_race(),
                    NULL_FAULTS.preempt_storm(), NULL_FAULTS.nan_logits(0),
                    NULL_FAULTS.callback_error(0)])
    assert NULL_FAULTS.fired == Counter()
    clk = lambda: 3.5
    assert NULL_FAULTS.wrap_clock(clk) is clk


def test_wrapped_clock_jumps_forward_monotonically():
    t = [0.0]
    faults = FaultInjector(3, p_clock_jump=1.0, clock_jump=10.0)
    wrapped = faults.wrap_clock(lambda: t[0])
    reads = []
    for i in range(5):
        t[0] = float(i)
        reads.append(wrapped())
    assert reads == sorted(reads), "wrapped clock ran backward"
    assert reads[-1] >= 4.0 + 5 * 10.0 - 10.0   # jumps accumulated
    assert faults.fired["clock_jump"] == 5
    # p=0 returns the base clock untouched
    assert FaultInjector(0).wrap_clock(None)() > 0


# ---------------------------------------------------------------------------
# Property walks: the scheduler + pool under memory faults
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(ops=st.lists(st.integers(0, 4), min_size=4, max_size=40),
       lengths=st.lists(st.integers(0, 1000), min_size=1, max_size=8),
       max_news=st.lists(st.integers(0, 1000), min_size=1, max_size=8),
       chunk=st.integers(1, 6),
       fseed=st.integers(0, 1000))
def test_property_chaos_walk_windowed(ops, lengths, max_news, chunk, fseed):
    """Injected alloc failures, forced evictions, admission races and
    preemption storms: refcounts exact after every op, zero leaks."""
    _chaos_walk(ops, lengths, max_news, chunk, fseed)


@settings(max_examples=15, deadline=None)
@given(ops=st.lists(st.integers(0, 4), min_size=4, max_size=30),
       lengths=st.lists(st.integers(0, 1000), min_size=1, max_size=8),
       max_news=st.lists(st.integers(0, 1000), min_size=1, max_size=8),
       chunk=st.integers(1, 6),
       fseed=st.integers(0, 1000))
def test_property_chaos_walk_slots_only(ops, lengths, max_news, chunk,
                                        fseed):
    """Pure-SSM walk with slot-exhaustion faults: every state slot comes
    back despite injected alloc_slot failures mid-admission."""
    _chaos_walk(ops, lengths, max_news, chunk, fseed, name="mamba2-130m")


def test_chaos_walk_pinned_runs_without_hypothesis():
    """Fixed replays of the property walks so the chaos machinery is
    exercised in tier-1 even when hypothesis is not installed."""
    ops = [0, 0, 1, 0, 2, 1, 3, 1, 0, 4, 1, 2, 0, 1, 1, 3, 2, 0, 1, 4,
           1, 2, 1, 0, 1, 1]
    for fseed in (0, 7, 42, 101):
        fired = _chaos_walk(ops, [5, 17, 3], [4, 9], 3, fseed).fired
        assert sum(fired.values()) > 0, (fseed, fired)
    for fseed in (1, 13):
        _chaos_walk(ops, [8, 2], [3, 12], 2, fseed, name="mamba2-130m")


def test_admission_rollback_pinned():
    """Pinned (no hypothesis): a slot fault inside chunked admission
    rolls the acquired prefix back through the refcount path and
    re-queues the request; the next step admits it cleanly."""
    faults = FaultInjector(0, p_slot_fail=1.0)
    cfg = get_config("mamba2-130m").reduced()
    pool = PagedKVPool(cfg, n_blocks=4, block_size=4, n_state_slots=4,
                       prefix_cache=False, faults=faults)
    sch = Scheduler(pool, max_len=32, max_batch=4, chunk_tokens=3)
    sch.submit(_WalkReq(np.arange(6, dtype=np.int32), 2))
    _chaos_stub_step(sch, 3)
    assert not sch.running and len(sch.waiting) == 1, \
        "slot fault must bounce the admission back to the queue"
    assert sch._c_admit_rollbacks.value == 1
    assert pool.slots.free_slots == pool.slots.n_slots
    faults.p_slot_fail = 0.0           # fault clears; admission succeeds
    _chaos_stub_step(sch, 3)
    assert len(sch.running) == 1
    while sch.has_work:
        _chaos_stub_step(sch, 3)
    assert pool.slots.free_slots == pool.slots.n_slots


# ---------------------------------------------------------------------------
# Engine-level: token identity of survivors vs the fault-free twin
# ---------------------------------------------------------------------------

def _run_engine(params, cfg, prompts, *, quant, max_new=4, **kw):
    eng = E.Engine(params, cfg, n_slots=2, max_len=32, quant=quant,
                   paged=True, block_size=4, chunk_tokens=3, **kw)
    reqs = [E.Request(prompt=p.copy(), max_new_tokens=max_new)
            for p in prompts]
    handles = [eng.submit(r) for r in reqs]
    eng.run()
    return reqs, handles, eng


def test_memory_faults_never_change_tokens():
    """Alloc failures, forced evictions, admission races and preemption
    storms against the real engine: every request still completes, with
    tokens bit-identical to the fault-free twin, and the pool drains to
    zero leaks."""
    cfg, params = _setup("mixtral-8x7b", n_layers=2, window=8)
    kv8 = _kv8(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
               for n in (5, 9, 14)]
    base, _, _ = _run_engine(params, cfg, prompts, quant=kv8)
    faults = FaultInjector(11, p_alloc_fail=0.05, p_forced_evict=0.3,
                           p_admit_race=0.3, p_preempt_storm=0.1)
    reqs, _, eng = _run_engine(params, cfg, prompts, quant=kv8,
                               faults=faults)
    assert sum(faults.fired.values()) > 0, "the schedule must have fired"
    for r, b in zip(reqs, base):
        assert r.done and r.error is None, (r.finish_reason, r.error)
        assert r.finish_reason == "length"
        assert r.out == b.out, "memory faults changed the tokens"
    eng.pool.validate()
    assert eng.pool.free_blocks == eng.pool.n_usable
    # the injection schedule is visible in the shared registry
    reg = eng.pool.metrics
    assert reg.value("repro_faults_injected",
                     site="admit_race") == faults.fired["admit_race"] > 0


def test_nan_quarantine_contains_to_one_request():
    """A poisoned logits row quarantines exactly the offending request:
    ``finish_reason='error'``, the cause surfaced on the handle, blocks
    released with zero leaks -- and every surviving request's tokens are
    bit-identical to the fault-free twin."""
    cfg, params = _setup("mixtral-8x7b", n_layers=2, window=8)
    kv8 = _kv8(cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
               for n in (5, 9, 14)]
    base, _, _ = _run_engine(params, cfg, prompts, quant=kv8, max_new=6)
    faults = FaultInjector(2, p_nan_logits=0.06)
    reqs, handles, eng = _run_engine(params, cfg, prompts, quant=kv8,
                                     max_new=6, faults=faults)
    assert faults.fired["nan_logits"] >= 1, \
        "pick a seed whose schedule actually poisons a row"
    errored = [r for r in reqs if r.finish_reason == "error"]
    survived = [(r, b) for r, b in zip(reqs, base)
                if r.finish_reason != "error"]
    assert errored and survived, (len(errored), len(survived))
    for r in errored:
        assert r.done and "non-finite" in r.error
    for h in handles:
        if h.finish_reason == "error":
            assert h.result().error == h.error   # surfaced on the handle
    for r, b in survived:
        assert r.out == b.out, "a peer's quarantine changed these tokens"
    reg = eng.pool.metrics
    assert reg.value("repro_engine_fault_requests",
                     kind="nan_logits") == len(errored)
    eng.pool.validate()
    assert eng.pool.free_blocks == eng.pool.n_usable


def test_callback_exception_isolated_per_request():
    """A raising ``on_token`` callback (real user code, no injector)
    quarantines its own request and never wedges the step loop; the
    peer's tokens are untouched."""
    cfg, params = _setup("mamba2-130m")
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab, (5,), dtype=np.int32),
               rng.integers(0, cfg.vocab, (7,), dtype=np.int32)]
    base, _, _ = _run_engine(params, cfg, prompts, quant=None, max_new=6)

    eng = E.Engine(params, cfg, n_slots=2, max_len=32, paged=True,
                   block_size=4, chunk_tokens=3)
    a = E.Request(prompt=prompts[0].copy(), max_new_tokens=6)
    b = E.Request(prompt=prompts[1].copy(), max_new_tokens=6)

    def bad_cb(tok):
        if len(b.out) == 2:
            raise ValueError("downstream sink exploded")
    b.on_token = bad_cb
    ha, hb = eng.submit(a), eng.submit(b)
    eng.run()
    assert b.done and b.finish_reason == "error"
    assert "on_token callback raised" in b.error
    assert len(b.out) == 2             # emitted tokens stay delivered
    assert a.done and a.finish_reason == "length"
    assert a.out == base[0].out, "quarantining b changed a's tokens"
    assert hb.error == b.error and ha.error is None
    reg = eng.pool.metrics
    assert reg.value("repro_engine_fault_requests", kind="callback") == 1
    assert eng.pool.slots.free_slots == eng.pool.slots.n_slots


def test_faults_disabled_is_token_identical_to_default():
    """An armed-but-all-zero injector must be invisible: same tokens as
    the NULL_FAULTS default, nothing fired."""
    cfg, params = _setup("mamba2-130m")
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
               for n in (5, 9)]
    base, _, eng0 = _run_engine(params, cfg, prompts, quant=None)
    assert eng0.faults is NULL_FAULTS
    armed = FaultInjector(0)           # every probability 0.0
    reqs, _, _ = _run_engine(params, cfg, prompts, quant=None,
                             faults=armed)
    assert [r.out for r in reqs] == [b.out for b in base]
    assert armed.fired == Counter()


def test_clock_jump_expires_deadlines_cleanly():
    """Injected clock jumps race every deadline: requests finish with
    ``finish_reason='timeout'`` (never a crash, never a leak)."""
    cfg, params = _setup("mamba2-130m")
    faults = FaultInjector(1, p_clock_jump=1.0, clock_jump=3600.0)
    eng = E.Engine(params, cfg, n_slots=2, max_len=32, paged=True,
                   block_size=4, chunk_tokens=3, faults=faults)
    rng = np.random.default_rng(9)
    reqs = [E.Request(prompt=rng.integers(0, cfg.vocab, (5,),
                                          dtype=np.int32),
                      max_new_tokens=4, timeout=5.0) for _ in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r in reqs:
        assert r.done and r.finish_reason == "timeout", r.finish_reason
    assert faults.fired["clock_jump"] >= 1
    assert eng.pool.slots.free_slots == eng.pool.slots.n_slots


# ---------------------------------------------------------------------------
# Watchdog: pool integrity violations recover instead of raising
# ---------------------------------------------------------------------------

def test_watchdog_repairs_bookkeeping_corruption():
    """A live block id smuggled onto the free list breaks the pool
    invariants; the ``validate_every`` watchdog rebuilds the free list
    from the (intact) block tables and every request still finishes
    with fault-free tokens."""
    cfg, params = _setup("mixtral-8x7b", n_layers=2, window=8)
    kv8 = _kv8(cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
               for n in (5, 9)]
    base, _, _ = _run_engine(params, cfg, prompts, quant=kv8, max_new=6)

    eng = E.Engine(params, cfg, n_slots=2, max_len=32, quant=kv8,
                   paged=True, block_size=4, chunk_tokens=3,
                   validate_every=1)
    reqs = [E.Request(prompt=p.copy(), max_new_tokens=6) for p in prompts]
    for r in reqs:
        eng.submit(r)
    for _ in range(4):                 # get both requests decoding
        assert eng.step()
    live = next(int(b) for s in eng.scheduler.running for b in s.blocks)
    eng.pool._free.append(live)        # corrupt: live id on the free list
    eng.run()
    reg = eng.pool.metrics
    assert reg.value("repro_engine_fault_watchdog_violations") == 1
    for r, b in zip(reqs, base):
        assert r.done and r.finish_reason == "length" and r.error is None
        assert r.out == b.out, "watchdog recovery changed the tokens"
    eng.pool.validate()
    assert eng.pool.free_blocks == eng.pool.n_usable


def test_watchdog_quarantines_corrupt_chain():
    """A block table that references an impossible block id cannot be
    trusted against the refcount map: the watchdog quarantines that
    chain (``finish_reason='error'``) and rebuilds; the other request
    finishes with fault-free tokens and nothing leaks."""
    cfg, params = _setup("mixtral-8x7b", n_layers=2, window=8)
    kv8 = _kv8(cfg)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab, (5,), dtype=np.int32),
               rng.integers(0, cfg.vocab, (9,), dtype=np.int32)]
    base, _, _ = _run_engine(params, cfg, prompts, quant=kv8, max_new=6)

    eng = E.Engine(params, cfg, n_slots=2, max_len=32, quant=kv8,
                   paged=True, block_size=4, chunk_tokens=3,
                   validate_every=1)
    a = E.Request(prompt=prompts[0].copy(), max_new_tokens=6)
    b = E.Request(prompt=prompts[1].copy(), max_new_tokens=6)
    for r in (a, b):
        eng.submit(r)
    for _ in range(4):
        assert eng.step()
    seq_b = next(s for s in eng.scheduler.running if s.req is b)
    seq_b.blocks[0] = 9999             # corrupt b's table, then un-balance
    eng.pool._free.append(1)           # the pool so validate() trips
    eng.run()
    assert b.done and b.finish_reason == "error"
    assert "integrity" in b.error
    assert a.done and a.finish_reason == "length" and a.error is None
    assert a.out == base[0].out, "quarantining b changed a's tokens"
    reg = eng.pool.metrics
    assert reg.value("repro_engine_fault_watchdog_violations") == 1
    assert reg.value("repro_engine_fault_requests", kind="watchdog") == 1
    eng.pool.validate()
    assert eng.pool.free_blocks == eng.pool.n_usable


# ---------------------------------------------------------------------------
# Backpressure: bounded queue, shed, resubmit
# ---------------------------------------------------------------------------

def test_max_queue_sheds_with_retry_after_and_resubmit_recovers():
    cfg, params = _setup("mamba2-130m")
    rng = np.random.default_rng(6)
    p_a = rng.integers(0, cfg.vocab, (5,), dtype=np.int32)
    p_b = rng.integers(0, cfg.vocab, (7,), dtype=np.int32)
    base, _, _ = _run_engine(params, cfg, [p_b], quant=None, max_new=4)

    eng = E.Engine(params, cfg, n_slots=2, max_len=32, paged=True,
                   block_size=4, chunk_tokens=3, max_queue=1)
    a = E.Request(prompt=p_a.copy(), max_new_tokens=4)
    b = E.Request(prompt=p_b.copy(), max_new_tokens=4)
    ha = eng.submit(a)                 # fills the one queue seat
    hb = eng.submit(b)                 # shed: queue is at max_queue
    assert b.done and b.finish_reason == "rejected"
    assert "queue full" in b.error and hb.error == b.error
    assert hb.retry_after is not None and hb.retry_after > 0
    reg = eng.pool.metrics
    assert reg.value("repro_sched_shed_requests") == 1
    assert reg.value("repro_sched_shed_retry_after") == b.retry_after
    assert b.out == []                 # shed before any admission

    ha.result()                        # drain the queue
    assert a.done and a.finish_reason == "length"
    hint = b.retry_after               # resubmit clears the hint
    delays = []
    hb.resubmit(sleep=delays.append)   # injectable backoff clock
    assert delays and delays[0] >= min(2.0, max(hint, 0.05)) - 1e-9
    assert not b.done, "resubmit must have re-queued the request"
    out = hb.result()
    assert out.finish_reason == "length" and out.error is None
    assert b.out == base[0].out, "a shed/resubmit cycle changed tokens"
    assert eng.pool.slots.free_slots == eng.pool.slots.n_slots


def test_shed_rate_bounded_under_overload():
    """2x overload against a bounded queue: some requests shed, some
    serve, nobody hangs, and every shed carries the hint."""
    cfg, params = _setup("mamba2-130m")
    rng = np.random.default_rng(10)
    eng = E.Engine(params, cfg, n_slots=2, max_len=32, paged=True,
                   block_size=4, chunk_tokens=3, max_queue=2)
    reqs = [E.Request(prompt=rng.integers(0, cfg.vocab, (5,),
                                          dtype=np.int32),
                      max_new_tokens=2) for _ in range(8)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    shed = [r for r in reqs if r.finish_reason == "rejected"]
    served = [r for r in reqs if r.finish_reason == "length"]
    assert len(shed) + len(served) == len(reqs)
    assert shed and served, (len(shed), len(served))
    for r in shed:
        assert r.retry_after is not None and r.retry_after > 0
        assert r.out == []
    assert eng.pool.slots.free_slots == eng.pool.slots.n_slots


# ---------------------------------------------------------------------------
# Satellites: StreamHandle idempotency, mid-chunk timeout regression
# ---------------------------------------------------------------------------

def test_double_submit_is_idempotent():
    """Submitting the same request twice while it is in flight must not
    enqueue it twice (a duplicate would double-release through free()'s
    strict path at finish)."""
    cfg, params = _setup("mamba2-130m")
    rng = np.random.default_rng(14)
    eng = E.Engine(params, cfg, n_slots=2, max_len=32, paged=True,
                   block_size=4, chunk_tokens=3)
    r = E.Request(prompt=rng.integers(0, cfg.vocab, (5,), dtype=np.int32),
                  max_new_tokens=4)
    h1 = eng.submit(r)
    h2 = eng.submit(r)                 # same engine, in flight: no-op
    assert h2.req is r
    assert list(eng.scheduler.waiting).count(r) == 1
    eng.step()                         # r admitted
    eng.submit(r)                      # still in flight: no-op again
    assert r not in eng.scheduler.waiting
    h1.result()
    assert r.done and r.finish_reason == "length" and len(r.out) == 4
    assert eng.pool.slots.free_slots == eng.pool.slots.n_slots
    # contiguous engine: same guard on the plain queue
    eng2 = E.Engine(params, cfg, n_slots=2, max_len=32)
    q = E.Request(prompt=rng.integers(0, cfg.vocab, (4,), dtype=np.int32),
                  max_new_tokens=2)
    eng2.submit(q), eng2.submit(q)
    assert eng2.queue.count(q) == 1
    eng2.run()
    assert q.done and len(q.out) == 2


def test_cancel_after_finish_is_a_clean_no():
    cfg, params = _setup("mamba2-130m")
    rng = np.random.default_rng(15)
    eng = E.Engine(params, cfg, n_slots=2, max_len=32, paged=True,
                   block_size=4, chunk_tokens=3)
    r = E.Request(prompt=rng.integers(0, cfg.vocab, (5,), dtype=np.int32),
                  max_new_tokens=3)
    h = eng.submit(r)
    h.result()
    assert r.done and r.finish_reason == "length"
    n = len(r.out)
    assert h.cancel() is False         # already finished
    assert h.cancel() is False         # and again: still a clean no
    assert r.finish_reason == "length" and len(r.out) == n
    eng.pool.validate()                # no double-release happened
    assert eng.pool.slots.free_slots == eng.pool.slots.n_slots


def test_timeout_mid_chunk_releases_partial_chain():
    """Regression (ISSUE 9 satellite): a deadline expiring while the
    prompt is mid-stream through chunked prefill must release the
    partially-written chain through the refcount path -- zero leaked
    blocks, zero leaked slots, and the surviving request untouched."""
    cfg, params = _setup("mixtral-8x7b", n_layers=2, window=8)
    kv8 = _kv8(cfg)
    t = [0.0]
    eng = E.Engine(params, cfg, n_slots=2, max_len=32, quant=kv8,
                   paged=True, block_size=4, chunk_tokens=3,
                   clock=lambda: t[0])
    rng = np.random.default_rng(16)
    a = E.Request(prompt=rng.integers(0, cfg.vocab, (4,), dtype=np.int32),
                  max_new_tokens=6)
    b = E.Request(prompt=rng.integers(0, cfg.vocab, (24,), dtype=np.int32),
                  max_new_tokens=2, timeout=5.0)
    eng.submit(a), eng.submit(b)
    for _ in range(3):
        assert eng.step()
    seq_b = next(s for s in eng.scheduler.running if s.req is b)
    assert seq_b.prefilling and 0 < seq_b.length < 24, \
        "the deadline must expire with the chain partially written"
    held = len(seq_b.blocks)
    assert held > 0
    t[0] = 10.0                        # expire mid-chunk
    assert eng.step()
    assert b.done and b.finish_reason == "timeout" and b.out == []
    # b's partial chain went back through the refcount path: the only
    # live references left are a's
    model = Counter(int(blk) for s in eng.scheduler.running
                    for blk in s.blocks)
    assert dict(model) == {blk: n for blk, n in eng.pool._ref.items()
                           if n > 0}, "mid-chunk expiry leaked references"
    eng.run()
    assert a.done and a.finish_reason == "length" and len(a.out) == 6
    eng.pool.validate()
    assert eng.pool.free_blocks == eng.pool.n_usable


@pytest.mark.parametrize("chunk_tokens", [None, 8])
@pytest.mark.parametrize("exc", [
    jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: injected device OOM"),
    ValueError("injected forward bug"),
])
def test_non_pool_errors_propagate_out_of_run(monkeypatch, chunk_tokens,
                                              exc):
    """Containment covers pool exhaustion and per-request faults only: a
    device error (``JaxRuntimeError`` is a ``RuntimeError``) or a bug
    raised by the forward pass leaves ``Engine.run`` instead of being
    taken for a transient fault -- in whole-prompt mode it used to roll
    the admission back and end the run quietly with work waiting."""
    cfg, params = _setup("llama3-8b", n_layers=1)

    def broken_step(*args, **kwargs):
        raise exc

    monkeypatch.setattr(E, "prefill_step_bucketed", broken_step)
    monkeypatch.setattr(E, "serve_step", broken_step)
    eng = E.Engine(params, cfg, n_slots=2, max_len=32, quant=_kv8(cfg),
                   paged=True, block_size=8, chunk_tokens=chunk_tokens)
    eng.submit(E.Request(prompt=np.arange(5, dtype=np.int32),
                         max_new_tokens=3))
    with pytest.raises(type(exc), match="injected"):
        eng.run()
