"""Closed-loop clients over the program's ``Engine``, the hook object
that sums the engine's step-loop hooks, and the warm-up.

Only the engine's public surface is driven: ``Engine``, ``Request``,
``submit``, ``step`` and the ``ServingObs`` hook protocol.  Tokens are
timed on the host clock by each request's ``on_token`` callback.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench import traffic as T


@dataclasses.dataclass
class StepRecord:
    """One engine step as the hooks saw it."""
    lanes: list          # (start, n) per live lane
    tok_live: int
    tok_lanes: int
    chunk_tokens: int
    occupancy: float | None


def make_obs():
    """A ``ServingObs`` whose hooks only record what the per-layer
    metrics read: live lanes per step, dispatched rows, chunk tokens and
    pool occupancy."""
    from repro.obs import ServingObs

    class BenchObs(ServingObs):
        def __init__(self):
            super().__init__()
            self.steps: list = []
            self._reset()

        def _reset(self):
            self._lanes, self._live, self._rows = [], 0, 0

        def on_submit(self, req, label=None):
            pass

        def on_admit(self, seq, cached_tokens=0, prefilling=False):
            pass

        def on_decode_begin(self, seq):
            pass

        def on_chunk(self, seq, n, t0, t1):
            self._lanes.append((seq.length - n, n))

        def on_token(self, req, tok):
            k = len(req.out)
            if k >= 2:       # a decode lane: its input token sat at p+k-2
                self._lanes.append((len(req.prompt) + k - 2, 1))

        def on_preempt(self, seq):
            pass

        def on_finish(self, req, reason, seq=None):
            pass

        def on_dispatch(self, *, live, lanes, tok_live, tok_lanes):
            self._live += tok_live
            self._rows += tok_lanes

        def on_step(self, t0, *, running, waiting, chunk_used=None,
                    chunk_budget=None, occupancy=None):
            self.steps.append(StepRecord(self._lanes, self._live,
                                         self._rows, chunk_used or 0,
                                         occupancy))
            self._reset()

        def on_moe(self, stats):
            pass

    return BenchObs()


class Loop:
    """``clients`` closed-loop clients: each sends its next request as
    soon as the previous one finished."""

    def __init__(self, eng, traffic: T.Traffic, seed: int, vocab: int):
        self.eng, self.traffic, self.seed, self.vocab = \
            eng, traffic, seed, vocab
        self.sent = [0] * traffic.clients
        self.current: list = [None] * traffic.clients
        self.finished: list = []

    def _send(self, c: int, now: float) -> None:
        from repro.serving.engine import Request
        d = T.draw(self.traffic, self.seed, self.vocab, c, self.sent[c])
        self.sent[c] += 1
        times: list = []
        req = Request(prompt=d.prompt, max_new_tokens=d.max_new_tokens,
                      on_token=lambda tok, ts=times: ts.append(
                          time.perf_counter()))
        req.times, req.sent_at = times, now
        self.eng.submit(req)
        self.current[c] = req

    def start(self) -> None:
        now = time.perf_counter()
        for c in range(self.traffic.clients):
            self._send(c, now)

    def step(self) -> None:
        self.eng.step()
        now = time.perf_counter()
        for c, req in enumerate(self.current):
            if req.done:
                self.finished.append(req)
                self._send(c, now)

    def ramp(self) -> int:
        """Step until every client's request has its first token; returns
        the steps taken."""
        n = 0
        while any(not r.out for r in self.current):
            self.step()
            n += 1
        return n

    def requests(self) -> list:
        return self.finished + [r for r in self.current if r is not None]


def engine_args(cfg_file: dict, traffic: T.Traffic) -> dict:
    """Engine settings of a cell: the configuration's block size and
    chunk, a pool and a length limit that hold every client's longest
    request at once."""
    e = cfg_file["engine"]
    bs = e["block_size"]
    return dict(n_slots=traffic.clients,
                max_len=-(-traffic.max_tokens // bs) * bs,
                paged=True, block_size=bs, n_blocks=traffic.pool_blocks,
                chunk_tokens=e["chunk_tokens"])


@contextlib.contextmanager
def _recording_steps(shapes: set):
    """Replace the engine module's two step programs with stand-ins that
    record the shapes they are called with and return zero logits."""
    from repro.serving import engine as E
    saved = E.serve_step, E.prefill_step_bucketed

    def key(kind, batch, caches):
        return ((kind,) + tuple(batch["tokens"].shape)
                + (caches["block_tables"].shape[-1],))

    def stub(kind):
        def run(params, batch, caches, cfg, quant=None, moe_stats=False):
            shapes.add(key(kind, batch, caches))
            return np.zeros((batch["tokens"].shape[0], cfg.vocab_padded),
                            np.float32), caches
        return run

    E.serve_step, E.prefill_step_bucketed = stub("decode"), stub("prefill")
    try:
        yield
    finally:
        E.serve_step, E.prefill_step_bucketed = saved


def plan_shapes(cfg, quant, traffic: T.Traffic, eng_args: dict, seed: int,
                steps: int) -> tuple:
    """Step-program shapes, and sizes of block allocations, that this
    cell's traffic reaches in its ramp and the next ``steps`` steps.
    Scheduling depends on token counts alone, so the engine is run on a
    one-layer model of the same block layout with the step programs
    stood in for."""
    from repro.serving.engine import Engine
    tiny = cfg.reduced(n_layers=1)
    shapes: set = set()
    allocs: set = set()
    with _recording_steps(shapes):
        eng = Engine({}, tiny, quant=quant, **eng_args)
        # the stand-ins read only the block table's width: skip the
        # pool's device work
        eng.pool.step_caches = lambda tables, lengths, **_: {
            "block_tables": tables}
        eng.pool.absorb = lambda caches: None
        eng.pool._reset_pos = lambda ids: None
        alloc = eng.pool.alloc

        def recording_alloc(n):
            allocs.add(n)
            return alloc(n)
        eng.pool.alloc = recording_alloc
        loop = Loop(eng, traffic, seed, cfg.vocab)
        loop.start()
        loop.ramp()
        for _ in range(steps):
            loop.step()
    return shapes, allocs


def warm(eng, cfg, quant, shapes: set, allocs: set) -> None:
    """Run each planned step program once, on pad lanes whose writes are
    dropped, with the host-side preparation the engine gives it; and make
    each planned size of block allocation once.  Where this misses an
    eager operation of the engine, the window counts its compile
    (``window.compiles``)."""
    import jax
    import jax.numpy as jnp
    from repro.serving import engine as E
    for kind, b, s, nb in sorted(shapes):
        tables = np.zeros((b, nb), np.int32)
        zeros = np.zeros(b, np.int32)
        caches = eng.pool.step_caches(tables, zeros, block_offsets=zeros)
        if kind == "decode":
            pos = np.full(b, -1, np.int32)
            batch = {"tokens": jnp.asarray(zeros)[:, None],
                     "positions": jnp.asarray(pos)[:, None]}
            out = E.serve_step(eng.params, batch, caches, cfg, quant)
        else:
            batch = {"tokens": jnp.asarray(np.zeros((b, s), np.int32)),
                     "positions": jnp.asarray(np.full((b, s), -1, np.int32)),
                     "last_idx": jnp.asarray(zeros, jnp.int32)}
            out = E.prefill_step_bucketed(eng.params, batch, caches, cfg,
                                          quant)
            if b == 1:      # one prompt alone: the suffix path's own
                eng.pool.step_caches(tables, zeros)    # preparation
                jnp.asarray(np.zeros(s, np.int32))[None]
                out[0][0]
        np.asarray(jax.block_until_ready(out)[0])
    for n in sorted(allocs):
        eng.pool.free(eng.pool.alloc(n))
