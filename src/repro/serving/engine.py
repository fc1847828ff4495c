"""Serving runtime: prefill/decode steps and continuous-batching engines.

The jitted steps are the units the dry-run lowers (``serve_step`` = one new
token against a KV cache of the cell's sequence length).  The engine wraps
them with continuous batching in one of two memory regimes:

* **contiguous** (``paged=False``): a fixed decode batch of ``n_slots``
  lanes, each lane owning one request's ``(max_len,)`` KV slab; finished
  lanes are refilled from the queue without stopping the others.
* **paged** (``paged=True``): requests share a refcounted copy-on-write
  block pool of packed bipolar-INT KV planes
  (:mod:`repro.serving.paged_cache`) addressed through per-request block
  tables, scheduled by :mod:`repro.serving.scheduler` -- FCFS admission
  gated on free blocks, decode batches bucketed to powers of two,
  preemption-by-eviction when the pool runs dry.  Capacity scales with
  tokens actually resident x ``kv_bits``/16, not ``n_slots x max_len``,
  and the pool's *prefix cache* shares blocks between requests with a
  common prompt prefix: admission acquires the cached blocks and
  prefills only the **suffix**, directly through the block table
  (``_paged_prefill``).  Sliding-window archs may run ``window <
  max_len``: blocks whose tokens all fall out of the window are
  reclaimed each step, so tables are rolling windows and steady-state
  decode memory is O(window) per request.  SSM/hybrid state and
  enc-dec cross caches ride a fixed-size *state slot pool* (one slot
  per request), so every config family serves from this one engine.

Prefill always runs per-request at B=1, with the prompt (paged: the
uncached suffix) *bucketed to the next power of two* (padded tokens
carry position -1 and are masked out of every attention read and every
pool write), so a stream of varied lengths compiles O(log max_len)
programs instead of one per distinct length.

**Chunked prefill** (``chunk_tokens``, paged only): a prompt no longer
prefills whole at admission -- it streams through the step loop
``chunk_tokens`` at a time, fused with the decode batch
(:meth:`Engine._fused_forward`): decode lanes carry 1 real token and
chunk lanes up to ``chunk_tokens``, all padded to one bucketed ``(B,
S)`` dispatch whose pad rows are position-masked by the Sq>=1 paged
kernel.  Running decodes therefore emit a token *every* step while a
long prompt trickles in, instead of stalling O(prompt).  SSM/hybrid
archs cannot pad the recurrence, so their mixed steps split into one
decode dispatch plus exact-length B=1 chunk dispatches riding the
cached conv/state continuation (:mod:`repro.models.ssm`); vlm/audio
frontends fill their side inputs in one pass and keep whole-prompt
admission.

The submit/stream API is asynchronous at the request level:
:meth:`Engine.submit` returns a :class:`StreamHandle` (iterate tokens
as they are emitted, poll, cancel); requests take ``on_token``
callbacks (fired in emission order), ``timeout`` deadlines (expiry
finishes the request with ``finish_reason='timeout'``), and
cancellation releases blocks and state slots through the scheduler's
refcount path mid-prefill or mid-decode.

**Observability** (``metrics=...``, default off): the engine reports
through a :class:`repro.obs.ServingObs` facade -- per-request lifecycle
traces (queued/running/chunk_prefill/decode spans, token instants,
TTFT/inter-token histograms, Perfetto export) plus step-loop gauges
and counters (running and waiting requests, pool occupancy, block-table
entries live vs walked) in the SAME metrics registry the pool's and
scheduler's counters live in, so ``report()``, ``registry.render()``,
and the benchmarks can never disagree.  Every timestamp goes through
the engine's injectable ``clock`` (traces are deterministic under
test); the paged step's phases are also marked as profiler spans
(``engine.step``, ``sched.plan``, ``engine.prepare``,
``pool.step_caches``, ``engine.dispatch``, ``engine.fetch``,
``engine.advance``) on the device trace's clock.  The default is
the no-op ``NULL_OBS`` sink: hooks cost one constant no-op call, no
clock read, no allocation -- the hot path and token-identity are
untouched when observability is off.

**Robustness** (``faults=...``, ``max_queue=...``, ``validate_every=...``):
the engine contains failures at the *request* level, never the step
level.  A non-finite logits row or an ``on_token`` callback exception
quarantines exactly the offending sequence -- ``finish_reason='error'``,
the error surfaced on ``StreamHandle.result().error``, its blocks and
state slot released through the refcount path -- while the rest of the
batch keeps producing bit-identical tokens to a fault-free run.
``finish_reason`` is always one of :attr:`Request.FINISH_REASONS`
(``length | timeout | cancelled | rejected | error``).  ``max_queue=N``
bounds the waiting queue: submits past the bound are shed with
``finish_reason='rejected'`` and a ``retry_after`` hint derived from
queue depth and pool occupancy (``StreamHandle.resubmit`` retries with
capped exponential backoff).  ``validate_every=N`` runs the pool's
invariant checker off the hot path every N steps; a violation
quarantines the corrupt chains and rebuilds the free lists instead of
raising.  ``faults=FaultInjector(seed, ...)`` threads a deterministic,
seeded fault schedule through the pool, scheduler, and engine
(:mod:`repro.serving.faults`) so tests/test_chaos.py can prove all of
the above; the default ``NULL_FAULTS`` twin keeps the hot path
token-identical with faults off.

**Nested precision** (``Request.precision``, paged): a checkpoint packed
at ``quant.w_bits`` with per-width scales serves any width ``k <=
w_bits`` by reading only the leading ``k`` bit planes
(:func:`repro.core.bipolar.nested_slice` -- no repacking, weight HBM
traffic scales with ``k``).  Each request may ask for its own width;
:func:`tier_bits` resolves it against the configured
``quant.precision_floor`` load-adaptive policy (bits shed under queue
pressure, floor-clamped, restored as the queue drains) and the result
is **frozen at first admission** -- precision never changes
mid-request, preemption re-admits at the same bits.  The step loop
groups lanes per precision (quant is jit-static: one compiled program
per served width) and the prefix cache salts its chain hashes with the
lane's bits, so equal prompts share KV only at equal precision.  Tokens
emitted per width surface as ``repro_engine_precision{bits}``.

Serving uses quantized packed weights (the paper's technique); pass
``quant=cfg.quant`` after :func:`repro.models.model.quantize_params`.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models import model as M
from repro.models.config import ModelConfig, QuantConfig
from repro.obs import NULL_OBS, MetricsRegistry, ServingObs
from repro.serving.faults import NULL_FAULTS, PoolExhausted, RequestFault


# ---------------------------------------------------------------------------
# Jitted steps
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "quant"))
def prefill_step(params, batch: dict, caches, cfg: ModelConfig,
                 quant: Optional[QuantConfig] = None):
    """Process a full prompt, filling the caches.

    Returns ``(last_logits (B, V), caches)``.
    """
    logits, caches, _ = M.forward(
        params, batch["tokens"], cfg,
        positions=batch.get("positions"),
        patch_embeds=batch.get("patch_embeds"),
        frames=batch.get("frames"),
        caches=caches, quant=quant, remat=False, logits_mode="last")
    return logits, caches


@partial(jax.jit, static_argnames=("cfg", "quant"))
def bucketed_prefill_program(params, batch: dict, caches, cfg: ModelConfig,
                             quant: Optional[QuantConfig] = None):
    """The compiled program of :func:`prefill_step_bucketed`:
    ``(logits, caches, moe)``, ``moe`` the per-MoE-layer telemetry
    (``None`` for a stack without MoE layers)."""
    out = M.forward(
        params, batch["tokens"], cfg,
        positions=batch.get("positions"),
        patch_embeds=batch.get("patch_embeds"),
        frames=batch.get("frames"),
        caches=caches, quant=quant, remat=False, logits_mode="none",
        collect_moe_stats=True)
    x, caches = out[0], out[1]
    idx = batch["last_idx"].astype(jnp.int32)           # (B,)
    xl = jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1)  # (B, 1, d)
    logits = M._logits(params, xl, cfg, quant)
    return logits[:, 0], caches, out[3]


def prefill_step_bucketed(params, batch: dict, caches, cfg: ModelConfig,
                          quant: Optional[QuantConfig] = None,
                          moe_stats: bool = False):
    """Prefill a length-bucketed prompt: tokens are padded past the real
    length (pad positions -1, masked everywhere) and the logits are taken
    at ``batch["last_idx"]`` (B,) -- the last *real* token -- instead of
    the last padded position.  Jits once per bucket, not per length.

    ``moe_stats=True`` appends the per-MoE-layer telemetry dict
    (:func:`repro.models.model.forward`) to the return.  It is computed
    either way, so a step with metrics on runs the same compiled
    program (:func:`bucketed_prefill_program`) as one with them off.
    """
    logits, caches, moe = bucketed_prefill_program(params, batch, caches,
                                                   cfg, quant)
    return (logits, caches, moe) if moe_stats else (logits, caches)


@partial(jax.jit, static_argnames=("cfg", "quant"))
def serve_program(params, batch: dict, caches, cfg: ModelConfig,
                  quant: Optional[QuantConfig] = None):
    """The compiled program of :func:`serve_step`: ``(logits, caches,
    moe)``, ``moe`` the per-MoE-layer telemetry (``None`` for a stack
    without MoE layers)."""
    out = M.forward(
        params, batch["tokens"], cfg,
        positions=batch["positions"],
        caches=caches, quant=quant, remat=False, logits_mode="last",
        collect_moe_stats=True)
    return out[0], out[1], out[3]


def serve_step(params, batch: dict, caches, cfg: ModelConfig,
               quant: Optional[QuantConfig] = None,
               moe_stats: bool = False):
    """One decode step: one new token per sequence against the caches.

    ``batch``: tokens (B, 1), positions (B, 1) (or (3, B, 1) M-RoPE).
    Returns ``(logits (B, V), caches)`` -- plus the per-MoE-layer
    telemetry dict when ``moe_stats=True``, computed either way, so
    metrics on and off run one compiled program
    (:func:`serve_program`).
    """
    logits, caches, moe = serve_program(params, batch, caches, cfg, quant)
    return (logits, caches, moe) if moe_stats else (logits, caches)


def kv_cache_bytes(caches, *, payload_only: bool = False) -> int:
    """Total bytes of the attention KV state in a cache tree.

    Counts ``k``/``v`` buffers plus (unless ``payload_only``) their
    quantization scales; positions/indices/SSM state are bookkeeping
    shared by every format and excluded.  With bipolar ``kv_bits`` caches
    the payload is exactly ``kv_bits/16`` of the bf16 payload (modulo the
    32-element word rounding of the head dim).
    """
    keys = ("k", "v") if payload_only else ("k", "v", "k_scale", "v_scale")

    def leaf_bytes(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        name = next((n for n in reversed(names) if n), "")
        if name not in keys or not hasattr(leaf, "nbytes"):
            return 0
        return int(leaf.nbytes)

    flat = jax.tree_util.tree_flatten_with_path(caches)[0]
    return sum(leaf_bytes(path, leaf) for path, leaf in flat)


def sample(logits: jax.Array, *, temperature: float = 0.0,
           key=None) -> jax.Array:
    if temperature <= 0.0:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


def _next_pow2(n: int, floor: int = 1) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def prefill_bucket(s: int, cap: int, floor: int = 8) -> int:
    """Bucket a prompt length to the next power of two (>= ``floor``,
    capped at ``cap`` = the cache ring length): a stream of varied
    prompt lengths compiles O(log cap) prefill programs.  Lengths at or
    beyond the ring stay exact -- padding past the ring would evict
    real in-window tokens through the SWA tail-store path."""
    if s >= cap:
        return s
    return min(_next_pow2(s, floor), cap)


def tier_bits(requested: Optional[int], *, max_bits: int,
              floor: Optional[int] = None, queue_depth: int = 0,
              pressure: int = 4) -> int:
    """Resolve one request's served weight width (bits).

    ``requested`` (None = full width) is capped at ``max_bits``, the
    checkpoint's stored width -- a nested checkpoint can serve fewer
    planes than it stores, never more.  Without a ``floor`` the request
    gets exactly what it asked for (no load adaptation).  With one, the
    policy is load-adaptive: every ``pressure`` waiting requests shed
    one bit off the grant, clamped at the floor -- bulk lanes degrade
    under overload and recover as the queue drains (each *new*
    admission re-reads the depth; granted requests keep their bits).
    A request explicitly asking for less than the floor is honored:
    the floor bounds degradation, not choice.
    """
    bits = min(requested or max_bits, max_bits)
    if floor is None:
        return bits
    lo = min(floor, bits)
    return max(lo, bits - queue_depth // max(pressure, 1))


# ---------------------------------------------------------------------------
# Requests and per-request state
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)    # identity equality: queue membership
class Request:                      # must never compare prompt arrays
    prompt: np.ndarray              # (s,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0        # 0 = greedy
    seed: Optional[int] = None      # per-request sampling stream; token k
                                    # is drawn from rng((seed, k)), so
                                    # preemption/recompute cannot change
                                    # the sampled sequence.  None: the
                                    # engine assigns a distinct seed at
                                    # submit (identical prompts still
                                    # sample diverse completions)
    precision: Optional[int] = None  # requested weight width (bits) for
                                     # nested-precision serving; capped
                                     # at quant.w_bits, load-adapted by
                                     # tier_bits, frozen at admission.
                                     # None: the engine's full width
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None     # rejection / quarantine detail
    # -- async streaming API -------------------------------------------------
    on_token: Optional[Callable[[int], None]] = None   # emission-order cb
    timeout: Optional[float] = None  # seconds from submit to deadline
    deadline: Optional[float] = None  # absolute (engine clock); computed
                                      # from ``timeout`` at submit if unset
    # why the request stopped: one of FINISH_REASONS (the class constant
    # below is THE enum -- obs labels and tests assert against it)
    finish_reason: Optional[str] = None
    # backpressure hint: seconds to wait before resubmitting, set when
    # the engine sheds this request off a full queue (max_queue)
    retry_after: Optional[float] = None

    # not a dataclass field (no annotation): the single definition of
    # every value ``finish_reason`` may take
    FINISH_REASONS = frozenset(
        {"length", "timeout", "cancelled", "rejected", "error"})


class StreamHandle:
    """Async view of a submitted request.

    The engine is single-threaded, so "async" means the handle *drives*
    it: :meth:`tokens` steps the engine until the request advances and
    yields each output token in emission order, which lets callers
    interleave many requests (each with its own handle or ``on_token``
    callback) without threads.  :meth:`cancel` aborts the request and
    releases its memory through the refcount path."""

    def __init__(self, engine: "Engine", req: Request):
        self.engine, self.req = engine, req

    @property
    def done(self) -> bool:
        return self.req.done

    @property
    def finish_reason(self) -> Optional[str]:
        return self.req.finish_reason

    @property
    def error(self) -> Optional[str]:
        """Rejection / quarantine detail (``finish_reason`` in
        ``{'rejected', 'error'}``), else None."""
        return self.req.error

    @property
    def retry_after(self) -> Optional[float]:
        """Backpressure hint attached when the engine shed this request
        off a full queue."""
        return self.req.retry_after

    def cancel(self) -> bool:
        return self.engine.cancel(self.req)

    def resubmit(self, max_attempts: int = 5, base_delay: float = 0.05,
                 max_delay: float = 2.0,
                 sleep: Optional[Callable[[float], None]] = None
                 ) -> "StreamHandle":
        """Client-side backoff helper: while the request sits shed
        (``finish_reason='rejected'``), wait max(engine ``retry_after``
        hint, capped exponential backoff) and submit it again.  Returns
        self once the request is back in the engine (drive it with
        :meth:`tokens`/:meth:`result` as usual) or after
        ``max_attempts`` consecutive sheds.  ``sleep`` is injectable so
        tests back off on a fake clock."""
        sleep = time.sleep if sleep is None else sleep
        for attempt in range(max_attempts):
            if not (self.req.done and self.req.finish_reason == "rejected"):
                return self
            sleep(min(max_delay, max(self.req.retry_after or 0.0,
                                     base_delay * (2 ** attempt))))
            self._reset_for_resubmit()
            self.engine.submit(self.req)
        return self

    def _reset_for_resubmit(self) -> None:
        """Clear the terminal fields a shed left behind so the request
        can go through ``submit`` again (deadline is recomputed from
        ``timeout``; emitted tokens are untouched -- a shed request
        never emitted any)."""
        r = self.req
        r.done = False
        r.error = None
        r.finish_reason = None
        r.retry_after = None
        r.deadline = None
        r._engine = None       # re-arm the double-submit guard

    def tokens(self, max_steps: int = 10_000):
        """Yield output tokens as they are emitted, stepping the engine
        as needed; returns when the request finishes (or the engine
        runs out of work / ``max_steps``)."""
        sent = steps = 0
        while True:
            while sent < len(self.req.out):
                yield self.req.out[sent]
                sent += 1
            if self.req.done or steps >= max_steps:
                return
            if not self.engine.step():
                return
            steps += 1

    def result(self, max_steps: int = 10_000) -> Request:
        """Block (drive the engine) until the request finishes."""
        for _ in self.tokens(max_steps):
            pass
        return self.req


def _tree_write_slot(batched, single, slot: int):
    """Insert a B=1 cache/state tree into batch position ``slot``.

    The batch dim is 0 for prelude caches but 1 for scanned-stack caches
    (leaves carry a leading n_units dim)."""
    def wr_at(bdim):
        def wr(b, s):
            start = (0,) * bdim + (slot,) + (0,) * (b.ndim - bdim - 1)
            return jax.lax.dynamic_update_slice(b, s.astype(b.dtype), start)
        return wr

    out = dict(batched)
    for key in batched:
        bdim = 0 if key == "prelude" else 1
        out[key] = jax.tree.map(wr_at(bdim), batched[key], single[key])
    return out


class Engine:
    """Continuous batching over the jitted steps (contiguous or paged).

    Contiguous: each of the ``n_slots`` decode lanes owns one request at
    a time; prefill runs per-request at B=1 (bucketed, see
    :func:`prefill_bucket`) and the resulting KV state is scattered into
    the lane's slice of the batched cache; decode advances all active
    lanes in lock-step.

    Paged (``paged=True``, requires ``kv_bits``): requests share a
    :class:`~repro.serving.paged_cache.PagedKVPool` of ``n_blocks``
    blocks x ``block_size`` tokens, run under the
    :class:`~repro.serving.scheduler.Scheduler`, and the decode batch is
    whatever is running, padded to the next power-of-two bucket
    (<= ``max_batch``) to bound recompiles.  With ``prefix_cache``
    (default) admission reuses pool blocks whose prompt-chain hash
    matches the head of the request and prefills only the suffix; block
    aliasing is refcounted with copy-on-write, so sharing changes
    memory management, not math: greedy decode stays token-identical to
    the contiguous engine (and to ``prefix_cache=False``) at equal
    ``kv_bits``.
    """

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int = 4,
                 max_len: int = 256, quant: Optional[QuantConfig] = None,
                 paged: bool = False, block_size: int = 16,
                 n_blocks: Optional[int] = None,
                 max_batch: Optional[int] = None,
                 prefix_cache: bool = True,
                 chunk_tokens: Optional[int] = None,
                 clock: Optional[Callable[[], float]] = None,
                 metrics=None, faults=None,
                 max_queue: Optional[int] = None,
                 validate_every: Optional[int] = None):
        self.params, self.cfg, self.quant = params, cfg, quant
        self.n_slots, self.max_len = n_slots, max_len
        self.paged = paged
        self.steps = 0
        # per-width QuantConfig cache (nested-precision serving): the
        # jitted steps treat quant as static, so each served width is
        # one compiled program, reused across steps
        self._quant_cache: dict = {}
        self._seed_counter = 0      # default per-request sampling seeds
        # fault facade (repro.serving.faults): one seeded schedule shared
        # by the pool, scheduler, and engine; NULL_FAULTS (default) is
        # the constant-False twin -- hot path and tokens untouched
        self.faults = faults if faults is not None else NULL_FAULTS
        # backpressure: bound on the waiting queue; submits past it are
        # shed with finish_reason='rejected' + a retry_after hint
        self.max_queue = max_queue
        # pool integrity watchdog cadence (steps between validate runs)
        assert validate_every is None or validate_every >= 1, validate_every
        self.validate_every = validate_every
        # deadline clock, injectable for deterministic timeout tests;
        # ALL observability timestamps route through it too (satellite
        # of ISSUE 7), so a ServingObs built with its own test clock
        # supplies the engine clock when none is injected here.  The
        # fault facade may wrap it with injected forward jumps
        if clock is None and isinstance(metrics, ServingObs):
            clock = metrics.clock
        self._clock = self.faults.wrap_clock(clock)
        # ``metrics``: None/False = off (NULL_OBS: no-op hooks, no clock
        # reads, token-identical hot path); True = fresh ServingObs;
        # or pass a MetricsRegistry / ServingObs to share a namespace
        if metrics is None or metrics is False:
            self.obs = NULL_OBS
        elif isinstance(metrics, ServingObs):
            self.obs = metrics
            self.obs.clock = self._clock
        elif isinstance(metrics, MetricsRegistry):
            self.obs = ServingObs(registry=metrics, clock=self._clock)
        elif metrics is True:
            self.obs = ServingObs(clock=self._clock)
        else:
            raise TypeError(
                f"metrics: expected None/bool/MetricsRegistry/"
                f"ServingObs, got {type(metrics).__name__}")
        self._deadlines = False     # fast-path: no deadline submitted yet
        # MoE telemetry: the steps compute it either way (one compiled
        # program); it is read back to the host only when observability
        # is on AND the stack has MoE layers
        self._moe_telemetry = bool(
            self.obs.enabled
            and any(cfg.ffn_kind(i) == "moe" for i in range(cfg.n_layers)))
        self.chunk_tokens_processed = 0
        if chunk_tokens is not None and not paged:
            raise ValueError("chunk_tokens requires paged=True (chunked "
                             "prefill writes through the block pool)")
        # whole-prompt frontends (vlm patch embeds, audio encoder frames)
        # fill their side inputs in one prefill pass; those families keep
        # whole-prompt admission
        if chunk_tokens is not None and cfg.family in ("vlm", "audio"):
            chunk_tokens = None
        self.chunk_tokens = chunk_tokens
        if paged:
            from repro.serving.paged_cache import (PagedKVPool,
                                                   needs_state_slots)
            from repro.serving.scheduler import Scheduler
            assert max_len % block_size == 0, (max_len, block_size)
            # window < max_len is fine: the scheduler reclaims blocks
            # whose tokens are all out of the attention window, so block
            # tables are rolling windows and steady-state decode memory
            # is O(window + state) per request (PR 5 tentpole; the pool
            # raises a descriptive ValueError for block_size > window)
            if n_blocks is None:
                # same token capacity as the n_slots contiguous engine,
                # plus the reserved null block
                n_blocks = n_slots * (max_len // block_size) + 1
            self.max_batch = max_batch or 2 * n_slots
            stateful = needs_state_slots(cfg)
            enc = None
            if cfg.family == "audio":
                from repro.launch.specs import enc_len
                enc = enc_len(cfg, max_len)
            # the engine's VLM frontend is a stub (zero patch embeds), but
            # real per-request patch embeds would make equal token
            # prefixes carry different KV -- keep the cache off for vlm.
            # Stateful archs (ssm/hybrid/audio) keep it off too: SSM
            # state is an order-dependent running summary (not
            # block-addressable content) and cross caches are
            # per-request, so there is no prefix to share
            self.pool = PagedKVPool(
                cfg, n_blocks, block_size, quant=quant,
                prefix_cache=(prefix_cache and cfg.family != "vlm"
                              and not stateful),
                n_state_slots=self.max_batch if stateful else 0,
                # NULL_OBS.registry is None -> the pool keeps a private
                # registry, so report() snapshots work with metrics off
                enc_len=enc, metrics=self.obs.registry,
                faults=self.faults)
            # nested-precision serving needs packed weights to slice;
            # without w_bits every lane runs the configured quant and
            # the scheduler stays unsalted (pre-nested behavior)
            tiered = quant is not None and quant.w_bits is not None
            self.scheduler = Scheduler(self.pool, max_len=max_len,
                                       max_batch=self.max_batch,
                                       chunk_tokens=self.chunk_tokens,
                                       obs=self.obs,
                                       precision_policy=(
                                           self._tier_policy if tiered
                                           else None))
            self.n_batch_blocks = max_len // block_size   # table width
        else:
            self.caches = M.init_caches(cfg, n_slots, max_len, quant=quant)
            self.slot_req: list = [None] * n_slots   # SequenceState per lane
            self.queue: list[Request] = []
        # robustness counters: in the pool's registry (paged) or the
        # obs registry / a private one (contiguous), so render() scrapes
        # faults, quarantines, and sheds next to the serving counters
        reg = self.pool.metrics if paged \
            else (self.obs.registry or MetricsRegistry())
        self._c_fault_requests = reg.counter(
            "repro_engine_fault_requests",
            "requests quarantined by step-level containment, by fault "
            "kind", labelnames=("kind",))
        self._fault_children: dict = {}
        self._c_fault_steps = reg.counter(
            "repro_engine_fault_steps",
            "steps aborted by a transient pool fault the scheduler "
            "could not absorb (state intact, step retried)")
        self._c_watchdog = reg.counter(
            "repro_engine_fault_watchdog_violations",
            "pool invariant violations caught by the validate_every "
            "watchdog (corrupt chains quarantined, free lists rebuilt)")
        self._c_shed = reg.counter(
            "repro_sched_shed_requests",
            "submits shed by the max_queue backpressure bound")
        self._g_retry_after = reg.gauge(
            "repro_sched_shed_retry_after",
            "retry_after hint attached to the most recent shed (s)")
        self._c_precision = reg.counter(
            "repro_engine_precision",
            "output tokens emitted per effective serving precision "
            "(weight bits; 'full' = unquantized weights)",
            labelnames=("bits",))
        self._precision_children: dict = {}
        self.faults.bind(reg)

    # -- request lifecycle -------------------------------------------------
    def submit(self, req: Request) -> StreamHandle:
        # double-submit is idempotent: a request this engine already
        # holds (queued or running) just gets a fresh handle -- queueing
        # it twice would double-release through free()'s strict path
        if getattr(req, "_engine", None) is self and not req.done:
            return StreamHandle(self, req)
        req._engine = self
        if getattr(req, "seed", None) is None:
            req.seed = self._seed_counter     # stable across preemption
            self._seed_counter += 1
        if getattr(req, "timeout", None) is not None \
                and getattr(req, "deadline", None) is None:
            req.deadline = self._clock() + req.timeout
        if getattr(req, "deadline", None) is not None:
            self._deadlines = True
        # trace starts BEFORE scheduler.submit so an immediate
        # rejection still closes a balanced span tree
        self.obs.on_submit(req)
        depth = len(self.scheduler.waiting) if self.paged \
            else len(self.queue)
        if self.max_queue is not None and depth >= self.max_queue:
            self._shed(req, depth)
            return StreamHandle(self, req)
        if self.paged:
            self.scheduler.submit(req)
        else:
            self.queue.append(req)
        return StreamHandle(self, req)

    def _shed(self, req: Request, depth: int) -> None:
        """Backpressure: the waiting queue is at ``max_queue`` -- finish
        the request immediately with ``finish_reason='rejected'`` and a
        ``retry_after`` hint that grows with queue depth and pool
        occupancy (deterministic, so shed/backoff behavior replays)."""
        if self.paged and self.pool.needs_blocks:
            occ = self.pool.used_blocks / max(self.pool.n_usable, 1)
        elif self.paged:
            occ = (self.pool.slots.used_slots
                   / max(self.pool.slots.n_slots, 1))
        else:
            occ = (sum(r is not None for r in self.slot_req)
                   / max(self.n_slots, 1))
        req.retry_after = 0.05 * (depth + 1) * (1.0 + occ)
        req.error = (f"rejected: queue full ({depth} waiting >= "
                     f"max_queue={self.max_queue})")
        req.done = True
        req.finish_reason = "rejected"
        self._c_shed.inc()
        self._g_retry_after.set(req.retry_after)
        self.obs.on_finish(req, "rejected")

    # -- nested-precision lanes --------------------------------------------
    def _tier_policy(self, req: Request) -> int:
        """Scheduler admission hook: resolve the request's served width
        through :func:`tier_bits` against the queue depth *now*, and
        freeze it on the request -- a preempted request re-admits at
        the SAME bits whatever the queue looks like by then (precision
        never changes mid-request, the tier property suite's
        invariant)."""
        frozen = getattr(req, "_tier_bits", None)
        if frozen is not None:
            return frozen
        q = self.quant
        bits = tier_bits(getattr(req, "precision", None),
                         max_bits=q.w_bits,
                         floor=q.precision_floor,
                         queue_depth=len(self.scheduler.waiting))
        req._tier_bits = bits
        return bits

    def _quant_for(self, bits: Optional[int]) -> Optional[QuantConfig]:
        """QuantConfig for one precision lane, cached per width.

        Full-width lanes reuse ``self.quant`` verbatim (same static jit
        key as pre-nested serving).  Narrower lanes get a cached
        ``nested_bits=bits`` copy; the floor is dropped -- it already
        did its job in :meth:`_tier_policy`, and a request granted
        bits below the configured floor (explicitly requested) must
        still validate."""
        q = self.quant
        if bits is None or q is None or bits == q.serve_bits:
            return q
        cached = self._quant_cache.get(bits)
        if cached is None:
            cached = dataclasses.replace(q, nested_bits=bits,
                                         precision_floor=None)
            self._quant_cache[bits] = cached
        return cached

    def cancel(self, req: Request) -> bool:
        """Abort ``req``: no further tokens are emitted and no further
        ``on_token`` callbacks fire; paged requests release their
        blocks and state slot through the scheduler's refcount path
        (mid-prefill included).  Returns False if the request already
        finished or is unknown to this engine."""
        if req.done:
            return False
        if self.paged:
            return self.scheduler.cancel(req)
        if req in self.queue:
            self.queue.remove(req)
        else:
            for i, seq in enumerate(self.slot_req):
                if seq is not None and seq.req is req:
                    self.slot_req[i] = None
                    break
            else:
                return False
        req.done, req.finish_reason = True, "cancelled"
        self.obs.on_finish(req, "cancelled")
        return True

    def _expire(self) -> None:
        """Finish every request whose deadline has passed: a clean
        completion with ``finish_reason='timeout'`` whose memory
        returns through the same path cancellation uses."""
        if not self._deadlines:
            return
        now = self._clock()

        def expired(req):
            dl = getattr(req, "deadline", None)
            return dl is not None and now >= dl and not req.done

        if self.paged:
            sch = self.scheduler
            stale = [r for r in list(sch.waiting) if expired(r)]
            stale += [s.req for s in list(sch.running) if expired(s.req)]
            for req in stale:
                sch.cancel(req, reason="timeout")
            return
        for req in [r for r in self.queue if expired(r)]:
            self.queue.remove(req)
            req.done, req.finish_reason = True, "timeout"
            self.obs.on_finish(req, "timeout")
        for i, seq in enumerate(self.slot_req):
            if seq is not None and expired(seq.req):
                self.slot_req[i] = None
                seq.req.done, seq.req.finish_reason = True, "timeout"
                self.obs.on_finish(seq.req, "timeout", seq=seq)

    def _emit(self, seq, tok: int) -> None:
        """Append an output token and fire ``on_token``: emission order
        == callback order, and a finished request (cancelled/expired by
        another lane's callback mid-step) never reaches here again.

        Callback *exceptions* are isolated per-request: they surface as
        a :class:`RequestFault` the step loop turns into a quarantine of
        this request alone (a callback that cancels/expires requests is
        a supported pattern and raises nothing)."""
        seq.req.out.append(tok)
        self.obs.on_token(seq.req, tok)
        bits = getattr(seq, "precision", None)
        if bits is None:
            q = self.quant
            bits = q.serve_bits if q is not None and q.w_bits else "full"
        child = self._precision_children.get(bits)
        if child is None:
            child = self._c_precision.labels(bits=str(bits))
            self._precision_children[bits] = child
        child.inc()
        if self.faults.callback_error(seq.req):
            raise RequestFault(
                f"injected on_token failure at token "
                f"{len(seq.req.out) - 1}", kind="callback")
        cb = getattr(seq.req, "on_token", None)
        if cb is not None:
            try:
                cb(tok)
            except RequestFault:
                raise
            except Exception as e:
                raise RequestFault(f"on_token callback raised: {e!r}",
                                   kind="callback") from e

    def _sample_checked(self, row: np.ndarray, seq) -> int:
        """Guarded sampling: a non-finite logits row (numerical blowup,
        or the injector's poisoned row) never reaches the sampler --
        it raises a :class:`RequestFault` that quarantines exactly this
        request.  Always on: the finiteness scan is O(V) on a row the
        step already materialized on host."""
        if self.faults.nan_logits(seq.req):
            row = np.full_like(row, np.nan)
        if not np.isfinite(row).all():
            raise RequestFault(
                f"non-finite logits row at output index "
                f"{len(seq.req.out)}", kind="nan_logits")
        return self._sample_token(row, seq)

    def _quarantine(self, seq, exc: Exception) -> None:
        """Step-level containment: retire exactly the offending
        sequence with ``finish_reason='error'``, surfacing the cause on
        ``req.error``; paged blocks and the state slot return through
        the scheduler's refcount path, a contiguous lane is simply
        vacated.  The rest of the batch never notices."""
        kind = getattr(exc, "kind", "exception")
        req = seq.req
        if req.error is None:
            req.error = f"quarantined ({kind}): {exc}"
        child = self._fault_children.get(kind)
        if child is None:
            child = self._c_fault_requests.labels(kind=kind)
            self._fault_children[kind] = child
        child.inc()
        if self.paged and seq in self.scheduler.running:
            self.scheduler.finish(seq, reason="error")
            return
        if not self.paged:
            for i, s in enumerate(self.slot_req):
                if s is seq:
                    self.slot_req[i] = None
                    break
        req.done = True
        req.finish_reason = "error"
        self.obs.on_finish(req, "error", seq=seq)

    # -- pool integrity watchdog -------------------------------------------
    def _watchdog(self) -> None:
        """``validate_every`` cadence: run the pool's full invariant
        checker off the hot path; on violation, recover instead of
        raising -- quarantine the chains whose tables are corrupt and
        rebuild the pool's bookkeeping from the survivors."""
        try:
            self.pool.validate()
        except AssertionError:
            self._c_watchdog.inc()
            self._rebuild_pool()

    def _rebuild_pool(self) -> None:
        """Recover a pool whose invariants broke: block tables are the
        ground truth.  Sequences whose table is self-evidently corrupt
        (out-of-range, null, or duplicated block ids; impossible slot)
        are quarantined *bypassing* release -- their references cannot
        be trusted against the refcount map.  Every derived structure
        is then rebuilt from the surviving tables: refcounts from a
        table-reference count, the free list as the unreferenced ids,
        the state-slot pool from the surviving slots.  The prefix cache
        is dropped wholesale (hits become misses; math unchanged) and
        chain memos reset.  Ends with a full ``validate()`` -- recovery
        must restore the invariants it is guarding, not defer them."""
        from collections import Counter as _Counter
        from repro.serving.paged_cache import ChainMemo
        pool, sch = self.pool, self.scheduler

        def table_corrupt(s) -> bool:
            seen = set()
            for b in s.blocks:
                b = int(b)
                if b < 1 or b > pool.n_usable or b in seen:
                    return True
                seen.add(b)
            return pool.slots is not None and s.slot >= 0 \
                and not 1 <= s.slot <= pool.slots.n_slots
        bad = [s for s in sch.running if table_corrupt(s)]
        for seq in bad:
            sch.running.remove(seq)
            seq.blocks = []
            seq.slot = -1
            self._quarantine(
                seq, RequestFault("pool integrity violation: block "
                                  "table corrupt", kind="watchdog"))
        counts = _Counter(int(b) for s in sch.running for b in s.blocks)
        pool._ref = dict(counts)
        pool._lru.clear()            # prefix cache dropped wholesale
        pool._meta.clear()
        pool._full_index.clear()
        pool._partial_index.clear()
        pool._free = [b for b in range(pool.n_blocks - 1, 0, -1)
                      if b not in counts]
        if pool.slots is not None:
            used = {s.slot for s in sch.running if s.slot >= 1}
            pool.slots._used = used
            pool.slots._free = [i for i in range(pool.slots.n_slots, 0, -1)
                                if i not in used]
        for seq in sch.running:
            seq.chain_memo = ChainMemo()
        pool.version += 1
        sch._blocked_head = None
        pool.validate()

    def _admit(self):
        for slot in range(self.n_slots):
            if self.slot_req[slot] is None and self.queue:
                req = self.queue.pop(0)
                self._prefill_into(req, slot)

    @property
    def _bucketable(self) -> bool:
        """Prompt lengths may pad to pow2 buckets only when every mixer
        masks by position: SSM/hybrid recurrences consume pad tokens
        regardless, so those archs prefill at exact length (one rule
        for the contiguous AND paged prefill paths -- diverging them
        would break paged-vs-contiguous token identity)."""
        return all(self.cfg.layer_kind(i) == "attn"
                   for i in range(self.cfg.n_layers))

    # -- shared bucketed B=1 prefill ---------------------------------------
    def _bucketed_prefill(self, prompt: np.ndarray):
        """Prefill one prompt at B=1 with length bucketing.

        Returns ``(logits (1, V) at the last real token, filled B=1
        cache)``.  Pad tokens carry position -1: they are masked out of
        every attention read and land in the cache as invalid slots that
        decode immediately overwrites (the ring index is rewound to the
        real length below).  SSM/hybrid archs prefill at exact length --
        the recurrence consumes every input regardless of position, so
        pads would corrupt the cached state (one jit per length; the
        bucketing win applies to the attention engines).
        """
        s = len(prompt)
        ring = min(self.max_len, self.cfg.window) if self.cfg.window \
            else self.max_len
        p = prefill_bucket(s, ring) if self._bucketable else s
        one = M.init_caches(self.cfg, 1, self.max_len, quant=self.quant)
        toks = np.zeros(p, np.int32)
        toks[:s] = np.asarray(prompt, np.int32)
        pos = np.full(p, -1, np.int32)
        pos[:s] = np.arange(s)
        batch = {"tokens": jnp.asarray(toks)[None],
                 "positions": jnp.asarray(pos)[None],
                 "last_idx": jnp.asarray([s - 1], jnp.int32)}
        if self.cfg.family == "vlm":
            batch["positions"] = jnp.broadcast_to(
                jnp.asarray(pos)[None, None], (3, 1, p))
            batch["patch_embeds"] = jnp.zeros(
                (1, min(self.cfg.n_patches, p), self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        if self.cfg.family == "audio":
            from repro.launch.specs import enc_len
            batch["frames"] = jnp.zeros(
                (1, enc_len(self.cfg, p), self.cfg.frontend_dim),
                jnp.dtype(self.cfg.dtype))
        logits, one = prefill_step_bucketed(self.params, batch, one,
                                            self.cfg, self.quant)
        return logits, self._rewind_ring_index(one, s, p)

    @staticmethod
    def _rewind_ring_index(caches, s: int, p: int):
        """Point each KV ring's write index at the first *pad* slot.

        The prefill write advanced ``index`` by the padded length ``p``;
        left alone, decode would skip the ``p - s`` pad slots (wasting
        ring capacity) or -- when ``p`` wraps the ring -- overwrite live
        prompt KV.  The first pad sits at ``s`` (normal write) or
        ``s - (p - ring)`` (SWA tail store keeps the last ``ring``
        entries), i.e. ``(s - max(0, p - ring)) % ring``.
        """
        def fix(c):
            if not (isinstance(c, dict) and "index" in c and "pos" in c):
                return c
            ring = c["pos"].shape[-1]
            idx = (s - max(0, p - ring)) % ring
            return dict(c, index=jnp.full_like(c["index"], idx))

        out = dict(caches)
        for key in ("prelude", "blocks"):
            if key in out:
                out[key] = [fix(c) for c in out[key]]
        return out

    @staticmethod
    def _sample_token(row_logits: np.ndarray, seq) -> int:
        """Sample the next token for ``seq`` (a SequenceState).

        Greedy below temperature 0+; otherwise inverse-CDF over the
        softmax using the request's stateless per-token RNG stream
        (``seq.sample_rng(k)`` for output index k) -- the draw depends
        only on (request seed, output index), never on batch composition
        or preemption history."""
        t = seq.temperature
        if t <= 0.0:
            return int(np.argmax(row_logits))
        z = row_logits.astype(np.float64) / t
        z -= z.max()
        probs = np.exp(z)
        probs /= probs.sum()
        u = seq.sample_rng(len(seq.req.out)).random()
        return int(min(np.searchsorted(np.cumsum(probs), u),
                       len(probs) - 1))

    # -- contiguous path ----------------------------------------------------
    def _prefill_into(self, req: Request, slot: int):
        from repro.serving.scheduler import SequenceState
        obs = self.obs
        seq = SequenceState(req=req, length=len(req.prompt))
        obs.on_admit(seq, prefilling=True)
        t0 = obs.t() if obs.enabled else 0.0
        logits, one = self._bucketed_prefill(req.prompt)
        self.caches = _tree_write_slot(self.caches, one, slot)
        if obs.enabled:
            obs.on_chunk(seq, len(req.prompt), t0, obs.t())
        obs.on_decode_begin(seq)
        try:
            seq.last_tok = self._sample_checked(
                np.asarray(logits[0], np.float32), seq)
            self._emit(seq, seq.last_tok)
        except RequestFault as e:
            self._quarantine(seq, e)   # lane stays free for the next admit
            return
        self.slot_req[slot] = seq

    def _contiguous_step(self) -> bool:
        obs = self.obs
        t0 = obs.t() if obs.enabled else 0.0
        self._expire()
        self._admit()
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return False
        if obs.enabled:
            obs.on_dispatch(live=len(active), lanes=self.n_slots,
                            tok_live=len(active), tok_lanes=self.n_slots)
        toks = np.zeros(self.n_slots, np.int32)
        pos = np.zeros(self.n_slots, np.int32)
        for slot, seq in enumerate(self.slot_req):
            if seq is not None:
                toks[slot], pos[slot] = seq.last_tok, seq.length
        toks = jnp.asarray(toks)[:, None]
        pos = jnp.asarray(pos)[:, None]
        if self.cfg.family == "vlm":
            pos = jnp.broadcast_to(pos[None], (3, self.n_slots, 1))
        batch = {"tokens": toks, "positions": pos}
        logits, self.caches = serve_step(self.params, batch, self.caches,
                                         self.cfg, self.quant)
        logits = np.asarray(logits, np.float32)
        self.steps += 1
        for slot in active:
            seq = self.slot_req[slot]
            if seq is None or seq.req.done:   # cancelled by a callback
                continue
            try:
                seq.last_tok = self._sample_checked(logits[slot], seq)
                self._emit(seq, seq.last_tok)
            except RequestFault as e:
                self._quarantine(seq, e)
                continue
            seq.length += 1
            if len(seq.req.out) >= seq.req.max_new_tokens \
                    or seq.length >= self.max_len - 1:
                seq.req.done = True
                seq.req.finish_reason = "length"
                self.slot_req[slot] = None
                self.obs.on_finish(seq.req, "length", seq=seq)
        if obs.enabled:
            obs.on_step(
                t0, waiting=len(self.queue),
                running=sum(r is not None for r in self.slot_req))
        return True

    # -- paged path ----------------------------------------------------------
    def _paged_prefill(self, seq, tokens: np.ndarray):
        """Scheduler admission callback (whole-prompt mode): prefill the
        whole uncached suffix in one pass, then sample the first token
        (or restore the pending input on a warm resume)."""
        start = seq.cached_len
        logits = self._suffix_forward(
            seq, np.asarray(tokens[start:], np.int32), start)
        seq.length = len(tokens)
        if seq.req.out:
            # re-admission after preemption: the pending input token is
            # already known; the recomputed logits would reproduce it
            seq.last_tok = seq.req.out[-1]
        else:
            seq.last_tok = self._sample_checked(
                np.asarray(logits[0], np.float32), seq)
            self._emit(seq, seq.last_tok)

    def _suffix_forward(self, seq, suffix: np.ndarray, start: int):
        """B=1 block-table *suffix* forward: chain positions ``start..``
        run through the model and land in ``seq``'s blocks.

        The first ``start`` tokens of the chain are already resident in
        the pool (prefix-cache hit, or -- chunked prefill -- the chunks
        a previous step landed); only ``suffix`` runs through the
        model, at B=1 with its length bucketed to the next power of two
        (pad tokens carry position -1: their pool writes are dropped
        and their attention rows masked, so a varied suffix stream
        compiles O(log max_len) programs).  The suffix K/V lands
        directly in the request's blocks via the paged scatter write,
        and its queries attend through the shared prefix blocks and the
        fresh suffix in the same kernel pass -- no contiguous B=1 cache
        or copy step exists anymore.  Stateful archs additionally
        continue the slot-resident conv/SSD state (and cross cache), so
        a chunk picks up exactly where the last one stopped.  Returns
        the ``(1, V)`` logits at the last real suffix token.
        """
        s = len(suffix)
        assert s >= 1, "suffix forward needs >= 1 token to compute"
        p = prefill_bucket(s, self.max_len) if self._bucketable else s
        toks = np.zeros(p, np.int32)
        toks[:s] = suffix
        pos = np.full(p, -1, np.int32)
        pos[:s] = np.arange(start, start + s)
        # bucket the table width like decode does: the kernel grid walks
        # one iteration per table entry
        nbw = min(_next_pow2(max(len(seq.blocks), 1)), self.n_batch_blocks)
        tables = np.zeros((1, nbw), np.int32)   # pad entries: null block
        tables[0, :len(seq.blocks)] = seq.blocks
        jpos = jnp.asarray(pos)[None]
        batch = {"tokens": jnp.asarray(toks)[None],
                 "positions": jpos,
                 "last_idx": jnp.asarray([s - 1], jnp.int32)}
        if self.cfg.family == "vlm":
            batch["positions"] = jnp.broadcast_to(jpos[None], (3, 1, p))
            batch["patch_embeds"] = jnp.zeros(
                (1, min(self.cfg.n_patches, p), self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        if self.cfg.family == "audio":
            from repro.launch.specs import enc_len
            batch["frames"] = jnp.zeros(
                (1, enc_len(self.cfg, p), self.cfg.frontend_dim),
                jnp.dtype(self.cfg.dtype))
        slots = (np.asarray([seq.slot], np.int32)
                 if self.pool.slots is not None else None)
        caches = self.pool.step_caches(
            tables, np.asarray([start], np.int32), slots=slots)
        quant = self._quant_for(getattr(seq, "precision", None))
        out = prefill_step_bucketed(self.params, batch, caches, self.cfg,
                                    quant, moe_stats=self._moe_telemetry)
        logits, caches = out[0], out[1]
        if self._moe_telemetry:
            self._report_moe(out[2])
        self.pool.absorb(caches)
        return logits

    def _report_moe(self, stats) -> None:
        """Hand one dispatch's MoE telemetry (``moe_stats=True``) to the
        hooks -- the host transfer, then ``on_moe`` and ``on_moe_rows`` --
        under the span ``engine.moe_stats``; the dispatches call it once
        their logits are fetched, so the span holds no wait for the
        device."""
        obs = self.obs
        with obs.span("engine.moe_stats"):
            stats = jax.device_get(stats)
            obs.on_moe(stats)
            obs.on_moe_rows(live=int(stats["load"].sum()),
                            computed=int(stats["computed"].sum()),
                            touched=int(stats["touched"].sum()))

    def _attn_body(self, sq: int) -> str:
        """The paged attention body a dispatch of ``sq`` query tokens a
        lane runs (``ops.paged_attention_body``)."""
        return ops.paged_attention_body(self.cfg.n_heads,
                                        self.cfg.n_kv_heads, sq)

    def _decode_bucket(self, n: int) -> int:
        return min(_next_pow2(n), self.max_batch)

    def _paged_step(self) -> bool:
        """One step of the paged engine, its phases marked with profiler
        spans (``obs.span``): ``engine.step`` holds ``sched.plan``, then
        per dispatch ``engine.prepare`` (with ``pool.step_caches``),
        ``engine.dispatch`` and ``engine.fetch``, then
        ``engine.advance``."""
        sch = self.scheduler
        obs = self.obs
        t0 = obs.t() if obs.enabled else 0.0
        with obs.span("engine.step", step_num=self.steps) as step_span:
            with obs.span("sched.plan"):
                self._expire()
                if self.validate_every is not None and self.steps \
                        and self.steps % self.validate_every == 0:
                    self._watchdog()
                try:
                    if self.chunk_tokens is None:
                        # whole-prompt mode: admission prefills, the step
                        # decodes
                        sch.admit(self._paged_prefill)
                        if not sch.running:
                            # fault-free, an empty step means an empty
                            # engine; with injection on, an admission
                            # race/rollback can leave work waiting --
                            # report it so run() retries
                            return self.faults.enabled and sch.has_work
                        # also reclaims blocks out of the window
                        sch.ensure_append_capacity()
                        plan = [(s, 1) for s in sch.running]
                    else:
                        sch.admit_chunked()
                        plan = sch.ensure_step_capacity(sch.plan_step())
                        if not plan:
                            return self.faults.enabled and sch.has_work
                except PoolExhausted:
                    # a transient pool fault the scheduler could not
                    # absorb by preempting (e.g. injected exhaustion with
                    # one request left).  Alloc is atomic and the
                    # rollback paths ran, so state is intact: consume the
                    # step and retry on the next one.  Grown blocks stay
                    # owned by their seqs (reused next step, no leak)
                    self._c_fault_steps.inc()
                    self.steps += 1
                    return sch.has_work
            chunk_used = 0
            if obs.enabled:
                fused = self._bucketable and any(n > 1 for _, n in plan)
                step_span.set_metadata(
                    kind="fused" if fused else "decode", lanes=len(plan))
                if self.chunk_tokens is not None:
                    chunk_used = sum(n for s, n in plan if s.prefilling)
            tf0 = obs.t() if obs.enabled else 0.0
            rows = self._forward_plan(plan)
            tf1 = obs.t() if obs.enabled else 0.0
            with obs.span("engine.advance"):
                self._advance(plan, rows, tf0, tf1)
                if obs.enabled:
                    self.pool.sync_gauges()
                    obs.on_step(
                        t0, running=len(sch.running),
                        waiting=len(sch.waiting), chunk_used=chunk_used,
                        chunk_budget=self.chunk_tokens,
                        occupancy=(self.pool.used_blocks
                                   / max(self.pool.n_usable, 1)
                                   if self.pool.needs_blocks else None))
        return True

    def _forward_plan(self, plan) -> list:
        """Run the planned step's forward pass(es); returns per-entry
        logits rows aligned with ``plan``.

        Attention-only configs fuse everything into ONE dispatch
        (:meth:`_fused_forward`) whenever a chunk is in flight; pure
        decode steps keep the exact ``(B, 1)`` ``serve_step`` program.
        Stateful archs (SSM/hybrid) cannot pad the recurrence, so their
        mixed steps split: one bucketed decode dispatch plus one
        exact-length B=1 dispatch per chunk lane, riding the cached
        conv/state continuation -- same scheduler step, same starvation
        bound, separate programs."""
        if any(n > 1 for _, n in plan) and self._bucketable:
            return self._fused_forward(plan)
        rows: list = [None] * len(plan)
        decodes = [(i, s) for i, (s, n) in enumerate(plan)
                   if not s.prefilling]
        for i, (seq, n) in enumerate(plan):
            if not seq.prefilling:
                continue
            toks = np.asarray(seq.pending[seq.length:seq.length + n],
                              np.int32)
            logits = self._suffix_forward(seq, toks, seq.length)
            rows[i] = np.asarray(logits[0], np.float32)
        if decodes:
            logits = self._decode_forward([s for _, s in decodes])
            for j, (i, _) in enumerate(decodes):
                rows[i] = logits[j]
        return rows

    @staticmethod
    def _precision_groups(seqs, key):
        """Distinct served widths among ``seqs`` (via ``key``), widest
        first -- a stable grouping order so mixed-precision steps
        dispatch deterministically."""
        return sorted({key(s) for s in seqs},
                      key=lambda b: (b is None, -(b or 0)))

    def _decode_forward(self, running):
        """Decode forward over ``running``, grouped per served
        precision: quant is jit-static, so each width is its own
        compiled program and a mixed batch dispatches once per distinct
        width over that width's lanes (per-lane plane masks).  A
        homogeneous batch -- the common case, and every pre-nested
        config -- is exactly one dispatch, unchanged.  Returns logits
        rows indexable by position in ``running``."""
        groups = self._precision_groups(running, lambda s: s.precision)
        if len(groups) <= 1:
            return self._decode_dispatch(running)
        rows: list = [None] * len(running)
        for bits in groups:
            idx = [i for i, s in enumerate(running) if s.precision == bits]
            logits = self._decode_dispatch([running[i] for i in idx])
            for j, i in enumerate(idx):
                rows[i] = logits[j]
        return rows

    def _decode_dispatch(self, running) -> np.ndarray:
        """One bucketed ``(B, 1)`` decode dispatch over ``running``
        (all lanes at one served precision); returns the (bucketed)
        f32 logits rows."""
        bb = self._decode_bucket(len(running))
        # bucket the table width too: the paged kernel's grid walks one
        # iteration per table entry, so a full-width (max_len/block_size)
        # table would make every decode step pay for the longest possible
        # sequence -- exactly the over-allocation paging removes.  With
        # sliding-window reclaim the tables are rolling windows, so the
        # width (and the kernel grid, and the HBM the step moves) stays
        # O(window/block_size) however long the generation runs
        nb = min(_next_pow2(max(len(s.blocks) for s in running) or 1),
                 self.n_batch_blocks)
        obs = self.obs
        with obs.span("engine.prepare"):
            if obs.enabled:
                obs.on_dispatch(live=len(running), lanes=bb,
                                tok_live=len(running), tok_lanes=bb)
                obs.on_tables(live=sum(len(s.blocks) for s in running),
                              walked=bb * nb, body=self._attn_body(1))
            toks = np.zeros(bb, np.int32)
            pos = np.full(bb, -1, np.int32)     # pad lanes: masked everywhere
            lens = np.zeros(bb, np.int32)
            tables = np.zeros((bb, nb), np.int32)  # 0 = the null block
            offsets = np.zeros(bb, np.int32)     # reclaimed logical blocks
            slot_ids = np.full(bb, -1, np.int32)  # pad lanes: no slot
            for i, seq in enumerate(running):
                toks[i], pos[i], lens[i] = \
                    seq.last_tok, seq.length, seq.length
                tables[i, :len(seq.blocks)] = seq.blocks
                offsets[i] = seq.freed_prefix
                slot_ids[i] = seq.slot
            jpos = jnp.asarray(pos)[:, None]
            if self.cfg.family == "vlm":
                jpos = jnp.broadcast_to(jpos[None], (3, bb, 1))
            batch = {"tokens": jnp.asarray(toks)[:, None], "positions": jpos}
            with obs.span("pool.step_caches"):
                caches = self.pool.step_caches(
                    tables, lens, block_offsets=offsets,
                    slots=slot_ids if self.pool.slots is not None else None)
            quant = self._quant_for(running[0].precision)
        with obs.span("engine.dispatch"):
            out = serve_step(self.params, batch, caches, self.cfg, quant,
                             moe_stats=self._moe_telemetry)
            logits, caches = out[0], out[1]
            self.pool.absorb(caches)
        with obs.span("engine.fetch", bytes=logits.nbytes):
            logits = np.asarray(logits, np.float32)
        if self._moe_telemetry:
            self._report_moe(out[2])
        return logits

    def _fused_forward(self, plan) -> list:
        """Fused decode + chunk-prefill forward, grouped per served
        precision like :meth:`_decode_forward`: one
        :meth:`_fused_dispatch` per distinct width over that width's
        plan entries.  Homogeneous plans (every pre-nested config) fuse
        into exactly ONE dispatch, unchanged."""
        groups = self._precision_groups(plan, lambda e: e[0].precision)
        if len(groups) <= 1:
            return self._fused_dispatch(plan)
        rows: list = [None] * len(plan)
        for bits in groups:
            idx = [i for i, (s, _) in enumerate(plan)
                   if s.precision == bits]
            sub = self._fused_dispatch([plan[i] for i in idx])
            for j, i in enumerate(idx):
                rows[i] = sub[j]
        return rows

    def _fused_dispatch(self, plan) -> list:
        """ONE dispatch for a mixed decode + chunk-prefill step.

        Decode lanes carry 1 real token, chunk lanes up to
        ``chunk_tokens``, padded to a common bucketed ``(B, S)``; pad
        tokens carry position -1 (attention rows masked, pool writes
        dropped) exactly like bucketed prefill pads, and the Sq>=1
        paged kernel masks causality by absolute position per row, so
        lanes of different real lengths coexist in one grid.  Per-lane
        logits are gathered at ``last_idx`` (the lane's last real
        token).  Attention-only configs (``_bucketable``); pool slots
        never exist here."""
        bb = self._decode_bucket(len(plan))
        smax = max(n for _, n in plan)
        sq = prefill_bucket(smax, self.max_len)
        nb = min(_next_pow2(max(len(s.blocks) for s, _ in plan) or 1),
                 self.n_batch_blocks)
        obs = self.obs
        with obs.span("engine.prepare"):
            if obs.enabled:
                obs.on_dispatch(live=len(plan), lanes=bb,
                                tok_live=sum(n for _, n in plan),
                                tok_lanes=bb * sq)
                obs.on_tables(live=sum(len(s.blocks) for s, _ in plan),
                              walked=bb * nb, body=self._attn_body(sq))
            toks = np.zeros((bb, sq), np.int32)
            pos = np.full((bb, sq), -1, np.int32)  # pads: masked everywhere
            last = np.zeros(bb, np.int32)
            lens = np.zeros(bb, np.int32)
            tables = np.zeros((bb, nb), np.int32)  # 0 = the null block
            offsets = np.zeros(bb, np.int32)
            for i, (seq, n) in enumerate(plan):
                if seq.prefilling:
                    toks[i, :n] = np.asarray(
                        seq.pending[seq.length:seq.length + n], np.int32)
                else:
                    toks[i, 0] = seq.last_tok
                pos[i, :n] = np.arange(seq.length, seq.length + n)
                last[i], lens[i] = n - 1, seq.length
                tables[i, :len(seq.blocks)] = seq.blocks
                offsets[i] = seq.freed_prefix
            batch = {"tokens": jnp.asarray(toks),
                     "positions": jnp.asarray(pos),
                     "last_idx": jnp.asarray(last, jnp.int32)}
            with obs.span("pool.step_caches"):
                caches = self.pool.step_caches(tables, lens,
                                               block_offsets=offsets)
            quant = self._quant_for(plan[0][0].precision)
        with obs.span("engine.dispatch"):
            out = prefill_step_bucketed(self.params, batch, caches,
                                        self.cfg, quant,
                                        moe_stats=self._moe_telemetry)
            logits, caches = out[0], out[1]
            self.pool.absorb(caches)
        with obs.span("engine.fetch", bytes=logits.nbytes):
            logits = np.asarray(logits, np.float32)
        if self._moe_telemetry:
            self._report_moe(out[2])
        return [logits[i] for i in range(len(plan))]

    def _advance(self, plan, rows, t_fwd0: float = 0.0,
                 t_fwd1: float = 0.0) -> None:
        """Consume a step's logits: advance lengths, sample/emit decode
        tokens (and the first token of a request whose prefill just
        completed), finish what is done.  ``t_fwd0``/``t_fwd1`` bound
        the step's forward pass (engine clock) -- each landed chunk is
        traced as a closed ``chunk_prefill`` span over that window."""
        sch = self.scheduler
        obs = self.obs
        self.steps += 1
        for (seq, n), row in zip(plan, rows):
            if seq.req.done:    # cancelled/expired by a callback mid-step
                continue
            try:
                if seq.prefilling:
                    seq.length += n
                    self.chunk_tokens_processed += n
                    if obs.enabled:
                        obs.on_chunk(seq, n, t_fwd0, t_fwd1)
                    sch.register_progress(seq)
                    if seq.length < len(seq.pending):
                        continue               # more chunks to stream
                    seq.pending = None
                    obs.on_decode_begin(seq)
                    if seq.req.out:
                        # warm resume: the pending input token is known
                        seq.last_tok = seq.req.out[-1]
                        continue
                    seq.last_tok = self._sample_checked(row, seq)
                    self._emit(seq, seq.last_tok)
                else:
                    seq.last_tok = self._sample_checked(row, seq)
                    self._emit(seq, seq.last_tok)
                    seq.length += 1
                if len(seq.req.out) >= seq.req.max_new_tokens \
                        or seq.length >= self.max_len - 1:
                    sch.finish(seq)
            except RequestFault as e:
                # step-level containment: retire exactly this sequence;
                # the other plan entries consume their rows untouched
                self._quarantine(seq, e)

    # -- decode loop --------------------------------------------------------
    def step(self) -> bool:
        """One batched decode step across all active requests."""
        return self._paged_step() if self.paged else self._contiguous_step()

    def run(self, max_steps: int = 10_000):
        while self.steps < max_steps and self._has_work():
            if not self.step():
                break

    def _has_work(self) -> bool:
        if self.paged:
            return self.scheduler.has_work
        return bool(self.queue) or any(r is not None for r in self.slot_req)

    def report(self) -> dict:
        """Occupancy snapshot (paged: pool accounting; contiguous: lanes)."""
        if self.paged:
            rep = self.pool.report(
                tokens_resident=self.scheduler.tokens_resident())
            rep.update(running=len(self.scheduler.running),
                       waiting=len(self.scheduler.waiting),
                       preemptions=self.scheduler.n_preemptions,
                       rejections=self.scheduler.n_rejections,
                       chunk_tokens=self.chunk_tokens,
                       chunk_tokens_processed=self.chunk_tokens_processed)
            return rep
        active = sum(r is not None for r in self.slot_req)
        return dict(n_slots=self.n_slots, running=active,
                    waiting=len(self.queue),
                    pool_bytes=kv_cache_bytes(self.caches),
                    tokens_resident=sum(r.length for r in self.slot_req
                                        if r is not None))
