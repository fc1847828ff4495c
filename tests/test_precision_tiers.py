"""Precision-tier property suite: the load-adaptive nested-precision
policy and its serving integration (ISSUE 10).

The claims under test, each locked by a property sweep (hypothesis,
skipping cleanly without the dev extra) plus a deterministic pinned
twin that always runs in tier-1:

* **Floor clamp**: :func:`repro.serving.engine.tier_bits` never grants
  below ``min(floor, requested)`` and never above ``max_bits``,
  whatever the queue depth.
* **Monotone degrade / full recovery**: deeper queues never grant MORE
  bits, and a drained queue grants exactly the request's choice.
* **Precision never changes mid-request**: the engine freezes the
  grant at first admission; preemption storms re-admit at the SAME
  bits even though the queue depth changed.
* **Pool exactness while tiers shift**: the chaos walk's
  exact-refcount / zero-leak invariants hold with a precision policy
  installed and the prefix cache salted per width, under injected
  faults, preemption, and cancellation.
* **Config validation**: QuantConfig rejects out-of-range bits,
  ``nested_bits`` without/above ``w_bits``, and a floor above the
  served width with descriptive ``ValueError`` at construction -- not
  deep inside pack/dispatch.
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
from repro.models.config import QuantConfig
from repro.serving import engine as E
from repro.serving.engine import tier_bits
from repro.serving.faults import FaultInjector, PoolExhausted
from repro.serving.paged_cache import PagedKVPool
from repro.serving.scheduler import Scheduler


# ---------------------------------------------------------------------------
# tier_bits: the pure policy
# ---------------------------------------------------------------------------

def _check_tier(requested, max_bits, floor, depth, pressure):
    bits = tier_bits(requested, max_bits=max_bits, floor=floor,
                     queue_depth=depth, pressure=pressure)
    top = min(requested or max_bits, max_bits)
    assert 1 <= bits <= max_bits
    assert bits <= top, "the policy never grants above the request"
    if floor is not None:
        assert bits >= min(floor, top), "floor clamp violated"
    else:
        assert bits == top, "no floor -> no degradation"
    # monotone in depth: one more waiting request never grants more
    more = tier_bits(requested, max_bits=max_bits, floor=floor,
                     queue_depth=depth + 1, pressure=pressure)
    assert more <= bits, "deeper queue granted MORE bits"
    # full recovery at zero depth
    drained = tier_bits(requested, max_bits=max_bits, floor=floor,
                        queue_depth=0, pressure=pressure)
    assert drained == top, "drained queue must grant the request's choice"


@settings(max_examples=200, deadline=None)
@given(requested=st.one_of(st.none(), st.integers(1, 12)),
       max_bits=st.integers(1, 8),
       floor=st.one_of(st.none(), st.integers(1, 8)),
       depth=st.integers(0, 200),
       pressure=st.integers(1, 16))
def test_tier_bits_properties(requested, max_bits, floor, depth, pressure):
    _check_tier(requested, max_bits, floor, depth, pressure)


def test_tier_bits_pinned():
    """Deterministic twin of the property sweep + exact spot checks."""
    for requested in (None, 1, 2, 4, 8, 12):
        for max_bits in (2, 4, 8):
            for floor in (None, 2, 4, 8):
                for depth in (0, 1, 4, 7, 8, 40, 200):
                    _check_tier(requested, max_bits, floor, depth, 4)
    assert tier_bits(None, max_bits=8) == 8
    assert tier_bits(4, max_bits=8) == 4
    assert tier_bits(12, max_bits=8) == 8          # capped at the store
    assert tier_bits(8, max_bits=8, floor=4, queue_depth=8) == 6
    assert tier_bits(8, max_bits=8, floor=4, queue_depth=999) == 4
    # an explicit request below the floor is honored (the floor bounds
    # degradation, not choice)
    assert tier_bits(2, max_bits=8, floor=4, queue_depth=999) == 2


# ---------------------------------------------------------------------------
# Engine integration: the grant freezes at first admission
# ---------------------------------------------------------------------------

def test_precision_frozen_across_preemption():
    """A tiny pool forces preemption + warm re-admission; every
    re-admission must re-grant the SAME bits the first admission froze,
    even though the queue depth (the policy input) keeps changing --
    precision never changes mid-request."""
    cfg = get_config("llama3-8b").reduced(n_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    qcfg = QuantConfig(w_bits=8, a_bits=8, kv_bits=8, precision_floor=2)
    qparams = M.quantize_params(params, qcfg)
    # pool sized so concurrent decodes evict each other
    eng = E.Engine(qparams, cfg, quant=qcfg, paged=True, n_slots=4,
                   max_len=64, block_size=4, n_blocks=6, max_batch=4)
    grants: dict = {}
    inner = eng.scheduler.precision_policy
    assert inner is not None

    def recording(req):
        bits = inner(req)
        grants.setdefault(id(req), []).append(bits)
        return bits

    eng.scheduler.precision_policy = recording
    rng = np.random.default_rng(5)
    reqs = [E.Request(prompt=rng.integers(0, cfg.vocab, (6,),
                                          dtype=np.int32),
                      max_new_tokens=8) for _ in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done and r.finish_reason == "length" for r in reqs)
    assert eng.scheduler.n_preemptions > 0, \
        "pool was meant to be small enough to force preemption"
    for r in reqs:
        seen = grants[id(r)]
        assert len(set(seen)) == 1, \
            f"precision changed across admissions: {seen}"
        assert seen[0] == r._tier_bits


def test_mixed_tier_lanes_complete_and_count():
    """Mixed premium/bulk lanes complete under one engine and the
    per-width token counters account for every emitted token."""
    cfg = get_config("llama3-8b").reduced(n_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    qcfg = QuantConfig(w_bits=8, a_bits=8, kv_bits=8)
    qparams = M.quantize_params(params, qcfg)
    eng = E.Engine(qparams, cfg, quant=qcfg, paged=True, n_slots=4,
                   max_len=64, block_size=16, metrics=True)
    rng = np.random.default_rng(7)
    precs = [8, 8, 4, 2]
    reqs = [E.Request(prompt=rng.integers(0, cfg.vocab, (6,),
                                          dtype=np.int32),
                      max_new_tokens=3, precision=b) for b in precs]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done and len(r.out) == 3 for r in reqs)
    rend = eng.pool.metrics.render()
    counts = {}
    for line in rend.splitlines():
        if line.startswith("repro_engine_precision_total{"):
            label, val = line.split("}")
            counts[label.split('"')[1]] = int(float(val))
    assert counts == {"8": 6, "4": 3, "2": 3}, counts


# ---------------------------------------------------------------------------
# Pool exactness while tiers shift (chaos-walk invariants, salted cache)
# ---------------------------------------------------------------------------

class _WalkReq:
    """Minimal stand-in for engine.Request (identity the scheduler
    needs, plus the nested-precision request knob)."""
    def __init__(self, prompt, max_new_tokens, precision=None):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.precision = precision
        self.temperature = 0.0
        self.out = []
        self.done = False
        self.error = None
        self.finish_reason = None


def _check_pool(pool, sch):
    """Exactness under chaos: pool internals self-consistent and every
    block's refcount equals the number of running tables mapping it."""
    pool.validate()
    model = Counter(int(b) for s in sch.running for b in s.blocks)
    actual = {b: r for b, r in pool._ref.items() if r > 0}
    assert dict(model) == actual, (dict(model), actual)


def _tier_stub_step(sch):
    """One model-free engine step (the chaos suite's stub) that also
    asserts the tier invariant: a running sequence's precision never
    drifts from the bits its request froze."""
    try:
        sch.admit_chunked()
        plan = sch.ensure_step_capacity(sch.plan_step())
    except PoolExhausted:
        return
    for seq in sch.running:
        assert seq.precision == seq.req._tier_bits, \
            (seq.precision, seq.req._tier_bits)
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


def _tier_walk(ops, lengths, max_news, precs, chunk, fseed):
    """Random chunked traffic with a LIVE tier policy (grants shift
    with queue depth), the prefix cache salted per width, and memory
    faults armed: refcounts stay exact after every op, grants respect
    the floor, frozen grants never change, and the drain leaks zero
    blocks."""
    faults = FaultInjector(fseed, p_alloc_fail=0.1, p_forced_evict=0.25,
                           p_admit_race=0.25, p_preempt_storm=0.1)
    cfg = get_config("mixtral-8x7b").reduced(n_layers=2, window=8)
    qcfg = dataclasses.replace(cfg.quant, w_bits=8, kv_bits=8,
                               precision_floor=2)
    pool = PagedKVPool(cfg, n_blocks=9, block_size=4, quant=qcfg,
                       faults=faults)

    def policy(req):
        frozen = getattr(req, "_tier_bits", None)
        if frozen is not None:
            return frozen
        bits = tier_bits(getattr(req, "precision", None),
                         max_bits=qcfg.w_bits, floor=qcfg.precision_floor,
                         queue_depth=len(sch.waiting))
        req._tier_bits = bits
        return bits

    sch = Scheduler(pool, max_len=32, max_batch=4, chunk_tokens=chunk,
                    precision_policy=policy)
    bases = [np.arange(24, dtype=np.int32),
             np.concatenate([np.arange(8),
                             np.arange(50, 66)]).astype(np.int32)]
    submitted = []
    for i, op in enumerate(ops):
        ln = 1 + lengths[i % len(lengths)] % 20
        if op == 0:                                    # submit
            p = precs[i % len(precs)]
            req = _WalkReq(bases[i % 2][:ln].copy(),
                           1 + max_news[i % len(max_news)] % 16,
                           precision=p if p else None)
            submitted.append(req)
            sch.submit(req)
        elif op in (1, 2):                             # one engine step
            _tier_stub_step(sch)
        elif op == 3:                                  # cancel anywhere
            reqs = [s.req for s in sch.running] + list(sch.waiting)
            if reqs:
                assert sch.cancel(reqs[i % len(reqs)])
        elif op == 4 and sch.running:                  # preempt youngest
            sch.preempt(max(sch.running, key=lambda s: s.admitted_at))
        _check_pool(pool, sch)
    steps = 0
    while sch.has_work:                                # drain
        _tier_stub_step(sch)
        _check_pool(pool, sch)
        steps += 1
        assert steps < 8000, "drain did not terminate under faults"
    assert pool.free_blocks == pool.n_usable, "tier walk leaked blocks"
    for req in submitted:
        granted = getattr(req, "_tier_bits", None)
        if granted is None:
            continue                                   # never admitted
        top = min(req.precision or qcfg.w_bits, qcfg.w_bits)
        assert granted >= min(qcfg.precision_floor, top), \
            "grant below the floor clamp"
        assert granted <= top


@settings(max_examples=25, deadline=None)
@given(ops=st.lists(st.integers(0, 4), min_size=4, max_size=40),
       lengths=st.lists(st.integers(0, 1000), min_size=1, max_size=8),
       max_news=st.lists(st.integers(0, 1000), min_size=1, max_size=8),
       precs=st.lists(st.integers(0, 8), min_size=1, max_size=6),
       chunk=st.integers(1, 6),
       fseed=st.integers(0, 1000))
def test_pool_exact_under_tier_shifts(ops, lengths, max_news, precs,
                                      chunk, fseed):
    _tier_walk(ops, lengths, max_news, precs, chunk, fseed)


def test_pool_exact_under_tier_shifts_pinned():
    """Deterministic twin: heavy submit/step/cancel/preempt mix with
    mixed requested widths, three fault seeds."""
    rng = np.random.default_rng(123)
    for fseed in (3, 11, 42):
        ops = list(rng.integers(0, 5, 36))
        _tier_walk(ops, list(rng.integers(0, 1000, 8)),
                   list(rng.integers(0, 1000, 8)),
                   [0, 8, 4, 2, 6], chunk=3, fseed=fseed)


# ---------------------------------------------------------------------------
# QuantConfig validation (fail fast, descriptive)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs,match", [
    (dict(w_bits=0), "w_bits"),
    (dict(w_bits=9), "w_bits"),
    (dict(a_bits=0), "a_bits"),
    (dict(kv_bits=12), "kv_bits"),
    (dict(nested_bits=4), "nested_bits requires w_bits"),
    (dict(w_bits=4, nested_bits=6), "exceeds"),
    (dict(w_bits=8, nested_bits=0), "nested_bits"),
    (dict(w_bits=8, precision_floor=9), "precision_floor"),
    (dict(w_bits=8, nested_bits=4, precision_floor=6), "precision_floor"),
    (dict(w_bits=4, variant="turbo"), "variant"),
])
def test_quant_config_rejects_bad_settings(kwargs, match):
    with pytest.raises(ValueError, match=match):
        QuantConfig(**kwargs)


def test_quant_config_accepts_valid_nested_settings():
    q = QuantConfig(w_bits=8, a_bits=8, kv_bits=4, nested_bits=4,
                    precision_floor=2)
    assert q.serve_bits == 4
    assert QuantConfig(w_bits=8).serve_bits == 8
    assert QuantConfig().serve_bits is None
