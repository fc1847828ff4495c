"""ServingObs: the one facade the serving stack reports through.

The engine, scheduler, and pool do not talk to the registry or tracer
directly on timed paths -- they call lifecycle hooks on a ``ServingObs``
(``on_submit`` / ``on_admit`` / ``on_token`` / ``on_preempt`` /
``on_finish`` / ``on_step`` / ``on_dispatch`` / ``on_tables`` /
``on_moe`` / ``on_moe_rows``), which owns:

* a :class:`~repro.obs.metrics.MetricsRegistry` (shared with the pool
  and scheduler, so every counter lives in ONE namespace),
* a :class:`~repro.obs.trace.Tracer` building the per-request span
  trees, and
* the **engine's clock**: the engine binds its injectable ``clock`` to
  the facade at construction, so every timestamp -- TTFT, inter-token,
  request span edges -- is deterministic under an injected
  test clock (the same one deadline expiry already uses).

Step phases are host spans on the *profiler's* clock instead
(``span(name, **args)``, a ``jax.profiler.TraceAnnotation``): they
share the device trace's timeline, so a profiler trace places each
phase of a step (``engine.step`` > ``sched.plan``, ``engine.prepare``
> ``pool.step_caches``, ``engine.dispatch``, ``engine.fetch``,
``engine.advance``) beside the device work it waits for.  They record
nothing unless a profiler trace is running.  While one runs, the facade
also keeps the trace's summary in process (``profiled``): each span
name's count and host seconds, and the block-table entries the traced
steps dispatched, so the process that captured a trace reads those
numbers without parsing the trace file.

``NULL_OBS`` is the disabled twin: a stateless singleton whose hooks
are constant no-ops (``enabled = False``) and whose ``span`` returns one
shared no-op context manager.  The engine's hot path calls
the cheap per-event hooks unconditionally (one attribute access + one
no-op call, no clock read, no allocation) and guards anything that
would *compute* (per-step gauge math, forward-pass timing) behind
``obs.enabled`` -- which is how metrics-off keeps token-identity and
a <= 2% step-time overhead (benchmarks/obs_overhead.py measures it).

Traces ride the request object (``req._trace``): preemption re-queues
the request but the trace survives, so a preempted-then-resumed
request shows ``queued -> running -> queued -> running`` with one root
span.  Every hook tolerates a request with no trace (a scheduler used
standalone, without an engine's ``on_submit``).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import numpy as np

from repro.obs.metrics import TOKEN_BUCKETS, MetricsRegistry
from repro.obs.trace import Tracer

__all__ = ["ServingObs", "NULL_OBS"]


@dataclasses.dataclass
class Profiled:
    """What the step loop did while a profiler trace recorded: per span
    name, ``[count, host seconds]``; the block-table entries its paged
    dispatches walked, of those the entries that held a sequence's
    blocks, and the walked entries of dispatches whose paged attention
    took the head-batched decode body; and, summed over MoE layer
    calls, the live routed assignments, the rows the grouped expert
    kernel computed for them, and the experts that held a live row."""
    spans: dict = dataclasses.field(default_factory=dict)
    entries_live: int = 0
    entries_walked: int = 0
    entries_decode_body: int = 0
    moe_live: int = 0
    moe_computed: int = 0
    moe_touched: int = 0


class ServingObs:
    """Live observability: registry + tracer + clock, with the
    lifecycle hooks the serving stack calls (see module docstring)."""

    enabled = True

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 clock: Optional[Callable[[], float]] = None,
                 tracer: Optional[Tracer] = None):
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer()
        self.clock = clock or time.monotonic
        r = self.registry
        # per-request latency distributions
        self._h_ttft = r.histogram(
            "repro_request_ttft_seconds",
            "submit-to-first-token latency")
        self._h_intertok = r.histogram(
            "repro_request_intertoken_seconds",
            "gap between consecutive emitted tokens of one request")
        self._h_queue = r.histogram(
            "repro_request_queue_wait_seconds",
            "time spent waiting (initial queue + re-queues after "
            "preemption)")
        # lifecycle counters
        self._c_submitted = r.counter(
            "repro_requests_submitted", "requests handed to submit()")
        self._c_finished = r.counter(
            "repro_requests_finished",
            "finished requests by finish_reason",
            labelnames=("reason",))
        self._finished_children: dict = {}
        self._c_tokens = r.counter(
            "repro_engine_tokens", "output tokens emitted")
        self._c_steps = r.counter(
            "repro_engine_steps", "engine steps executed")
        self._c_prefill_tokens = r.counter(
            "repro_engine_prefill_tokens",
            "prompt tokens run through prefill passes (chunked "
            "step-loop chunks or whole-prompt admission)")
        # step-loop gauges (set once per step / dispatch)
        self._g_running = r.gauge(
            "repro_engine_running", "requests currently running")
        self._g_waiting = r.gauge(
            "repro_engine_waiting", "requests queued for admission")
        self._g_occupancy = r.gauge(
            "repro_pool_occupancy", "used / usable pool blocks")
        # block-table entries the paged attention grid walks, and how
        # many of them hold a sequence's blocks (the rest pad the table
        # to its bucketed width and point at the null block)
        c_tables = r.counter(
            "repro_engine_table_entries",
            "block-table entries dispatched, by kind (walked = batch "
            "lanes x table width; live = filled from a sequence)",
            labelnames=("kind",))
        self._c_entries_live = c_tables.labels(kind="live")
        self._c_entries_walked = c_tables.labels(kind="walked")
        # the same walked entries by the paged attention body that ran
        # them (ops.paged_attention_body)
        c_body = r.counter(
            "repro_paged_attn_entries",
            "block-table entries walked by paged attention, by body "
            "(decode = head-batched decode tiles, tile = per-head query "
            "tiles, reference = no kernel)", labelnames=("body",))
        self._c_entries_body = {b: c_body.labels(body=b)
                                for b in ("decode", "tile", "reference")}
        self.profiled = Profiled()
        # MoE capacity pressure (per forward dispatch, fed by the
        # engine's moe_stats-specialized steps)
        self._h_moe_load = r.histogram(
            "repro_moe_expert_load",
            "tokens dispatched to one expert in one MoE layer pass",
            buckets=TOKEN_BUCKETS)
        self._c_moe_dropped = r.counter(
            "repro_moe_dropped_tokens",
            "routed assignments lost to the expert capacity bound")
        self._g_moe_util = r.gauge(
            "repro_moe_capacity_utilization",
            "kept assignments / dispatch slots over the last forward")
        c_assign = r.counter(
            "repro_moe_assignments",
            "routed assignments of live tokens (live) and the rows the "
            "grouped expert kernel computes for them, tile padding "
            "included (computed), summed over MoE layer calls",
            labelnames=("kind",))
        self._c_moe_live = c_assign.labels(kind="live")
        self._c_moe_computed = c_assign.labels(kind="computed")
        self._c_moe_touched = r.counter(
            "repro_moe_experts_touched",
            "experts holding at least one live row, summed over MoE "
            "layer calls")

    # -- clock ---------------------------------------------------------------
    def t(self) -> float:
        return self.clock()

    # -- request lifecycle ---------------------------------------------------
    def on_submit(self, req: Any, label: Optional[str] = None) -> None:
        now = self.clock()
        self._c_submitted.inc()
        tr = self.tracer.start(now, label)
        req._trace = tr
        tr.begin("queued", now)

    def on_admit(self, seq: Any, cached_tokens: int = 0,
                 prefilling: bool = False) -> None:
        now = self.clock()
        tr = getattr(seq.req, "_trace", None)
        if tr is None:
            return
        if "queued" in tr._open:
            q = tr._open["queued"]
            tr.end("queued", now)
            self._h_queue.observe(now - q.t0)
        tr.begin("running", now)
        if cached_tokens:
            tr.prefix_hit_tokens += cached_tokens
            tr.instant("prefix_hit", now, dict(tokens=cached_tokens))
        if not prefilling:
            tr.begin("decode", now)
        self._track_blocks(tr, seq)

    def on_decode_begin(self, seq: Any) -> None:
        tr = getattr(seq.req, "_trace", None)
        if tr is not None and "decode" not in tr._open:
            tr.begin("decode", self.clock())

    def on_chunk(self, seq: Any, n: int, t0: float, t1: float) -> None:
        """One chunk of ``seq``'s prompt landed between ``t0`` and
        ``t1`` (whole-prompt admission records its single prefill pass
        through here too, as chunk 0)."""
        self._c_prefill_tokens.inc(n)
        tr = getattr(seq.req, "_trace", None)
        if tr is None:
            return
        tr.complete("chunk_prefill", t0, t1,
                    dict(index=tr.n_chunks, tokens=n))
        tr.n_chunks += 1
        self._track_blocks(tr, seq)

    def on_token(self, req: Any, tok: int) -> None:
        now = self.clock()
        self._c_tokens.inc()
        tr = getattr(req, "_trace", None)
        if tr is None:
            return
        if tr.token_times:
            self._h_intertok.observe(now - tr.token_times[-1])
        else:
            self._h_ttft.observe(now - tr.t_submit)
        tr.token(now, len(req.out) - 1, tok)

    def on_preempt(self, seq: Any) -> None:
        now = self.clock()
        tr = getattr(seq.req, "_trace", None)
        if tr is None:
            return
        tr.n_preemptions += 1
        if "decode" in tr._open:
            tr.end("decode", now)
        if "running" in tr._open:
            tr.end("running", now)
        tr.begin("queued", now)

    def on_finish(self, req: Any, reason: str,
                  seq: Any = None) -> None:
        child = self._finished_children.get(reason)
        if child is None:
            child = self._c_finished.labels(reason=reason)
            self._finished_children[reason] = child
        child.inc()
        tr = getattr(req, "_trace", None)
        if tr is None:
            return
        if seq is not None:
            self._track_blocks(tr, seq)
        tr.finish(self.clock(), reason)

    @staticmethod
    def _track_blocks(tr: Any, seq: Any) -> None:
        held = getattr(seq, "freed_prefix", 0) \
            + len(getattr(seq, "blocks", ()))
        if held > tr.peak_blocks:
            tr.peak_blocks = held

    # -- step loop -----------------------------------------------------------
    def on_step(self, t0: float, *, running: int, waiting: int,
                chunk_used: Optional[int] = None,
                chunk_budget: Optional[int] = None,
                occupancy: Optional[float] = None) -> None:
        self._c_steps.inc()
        self._g_running.set(running)
        self._g_waiting.set(waiting)
        if occupancy is not None:
            self._g_occupancy.set(occupancy)

    def on_dispatch(self, *, live: int, lanes: int,
                    tok_live: int, tok_lanes: int) -> None:
        """One forward dispatch: ``live`` real lanes padded to ``lanes``
        bucket lanes, carrying ``tok_live`` real tokens of ``tok_lanes``
        dispatched slots.  Nothing here keeps it; a subclass that reads
        bucket padding (the benchmark's) overrides it."""

    def on_tables(self, *, live: int, walked: int, body: str) -> None:
        """One paged dispatch's block tables: ``walked`` entries (batch
        lanes x table width) of which ``live`` hold a sequence's
        blocks, walked by the paged attention ``body`` (``"decode"``,
        ``"tile"`` or ``"reference"``)."""
        self._c_entries_live.inc(live)
        self._c_entries_walked.inc(walked)
        self._c_entries_body[body].inc(walked)
        from jax.profiler import TraceAnnotation
        if TraceAnnotation.is_enabled():
            self.profiled.entries_live += live
            self.profiled.entries_walked += walked
            if body == "decode":
                self.profiled.entries_decode_body += walked

    def span(self, name: str, **args):
        """A host span on the profiler's clock: a
        ``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation``
        when ``args`` holds ``step_num``) that also adds its host
        seconds to ``profiled``.  Outside a profiler trace it is the
        shared no-op span; ``set_metadata`` adds args known later."""
        from jax.profiler import TraceAnnotation
        if not TraceAnnotation.is_enabled():
            return _NO_SPAN
        return _ProfiledSpan(self.profiled.spans, name, args)

    def on_moe(self, stats: Any) -> None:
        """Record one forward pass's MoE capacity telemetry: ``stats``
        is the :func:`repro.models.model.forward` dict -- ``load``
        ``(L_moe, E)`` kept assignments per expert, ``dropped
        (L_moe,)`` assignments lost to the capacity bound, ``capacity
        (L_moe,)`` dispatch slots -- as host or device arrays."""
        if stats is None:
            return
        load = np.asarray(stats["load"])
        h = self._h_moe_load
        # every (layer, expert) load in one update: bucket i holds the
        # values at or under its edge (searchsorted, left)
        h.observe_bucketed(
            np.bincount(np.searchsorted(h.buckets, load.reshape(-1)),
                        minlength=len(h.buckets) + 1),
            float(load.sum()))
        dropped = int(np.asarray(stats["dropped"]).sum())
        if dropped:
            self._c_moe_dropped.inc(dropped)
        cap = int(np.asarray(stats["capacity"]).sum())
        if cap:
            self._g_moe_util.set(float(load.sum()) / cap)

    def on_moe_rows(self, *, live: int, computed: int,
                    touched: int) -> None:
        """One dispatch's MoE work, summed over its MoE layers: ``live``
        routed assignments of live tokens, ``computed`` rows of the
        grouped expert kernel's live tiles, ``touched`` experts with a
        live row.  Kept in ``profiled`` too while a trace records."""
        self._c_moe_live.inc(live)
        self._c_moe_computed.inc(computed)
        self._c_moe_touched.inc(touched)
        from jax.profiler import TraceAnnotation
        if TraceAnnotation.is_enabled():
            self.profiled.moe_live += live
            self.profiled.moe_computed += computed
            self.profiled.moe_touched += touched


class _NoSpan:
    """The span of a disabled facade: enters, exits and takes metadata
    without reading a clock."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        pass


_NO_SPAN = _NoSpan()


class _ProfiledSpan:
    """A profiler annotation that adds its host seconds to the
    ``[count, seconds]`` of its name in ``totals``."""

    __slots__ = ("_ann", "_rec", "_t0")

    def __init__(self, totals: dict, name: str, args: dict):
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        cls = StepTraceAnnotation if "step_num" in args else TraceAnnotation
        self._ann = cls(name, **args)
        self._rec = totals.setdefault(name, [0, 0.0])
        self._t0 = 0.0

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._rec[0] += 1
        self._rec[1] += time.perf_counter() - self._t0
        return self._ann.__exit__(*exc)

    def set_metadata(self, **args):
        self._ann.set_metadata(**args)


class _NullObs:
    """Disabled twin of :class:`ServingObs`: every hook is a constant
    no-op -- no clock reads, no allocations, nothing retained.  One
    shared singleton (``NULL_OBS``) serves every disabled engine."""

    __slots__ = ()
    enabled = False
    registry = None
    tracer = None
    profiled = None

    def t(self):
        return 0.0

    def on_submit(self, req, label=None):
        pass

    def on_admit(self, seq, cached_tokens=0, prefilling=False):
        pass

    def on_decode_begin(self, seq):
        pass

    def on_chunk(self, seq, n, t0, t1):
        pass

    def on_token(self, req, tok):
        pass

    def on_preempt(self, seq):
        pass

    def on_finish(self, req, reason, seq=None):
        pass

    def on_step(self, t0, **kw):
        pass

    def on_dispatch(self, **kw):
        pass

    def on_tables(self, **kw):
        pass

    def span(self, name, **args):
        return _NO_SPAN

    def on_moe(self, stats):
        pass

    def on_moe_rows(self, **kw):
        pass


NULL_OBS = _NullObs()
