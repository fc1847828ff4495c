#!/usr/bin/env python3
"""Chip smoke test: serve StableLM-3B at full size on one TPU chip.

Drives the system's main path once, through the entry points a user
calls: seeded random StableLM-3B-4E1T weights at published widths and
full depth (32 layers), packed to the config's W3A8 bipolar planes with
an 8-bit bipolar KV cache, served by ``Engine(paged=True,
chunk_tokens=256, block_size=16)`` -- continuous batching through the
``Scheduler`` and ``PagedKVPool`` -- with the Pallas kernels.  Eight
requests (seeded prompt lengths 128-1024, 64 new tokens each); two ask
for ``precision=2``, so the nested-width lanes run as well.

Phases, each printed on its own line:

1. device    -- the accelerator JAX found (a TPU, or the script fails);
2. build     -- init + quantize time and peak device memory;
3. compile   -- compile time of the decode step and the number of
                ``tpu_custom_call`` ops (Pallas kernels) in it;
4. serve     -- tokens served, finish reasons, fault counters;
5. parity    -- largest prefill-logit gap, pallas against the jnp
                ``reference`` impl on the same packed weights.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.  Everything runs in this one process.

Run (on a machine with a TPU):  python3 chip_smoke.py
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0
N_REQUESTS = 8
PROMPT_LEN = (128, 1024)          # inclusive range of seeded lengths
NEW_TOKENS = 64
LOW_PRECISION = {1: 2, 5: 2}      # request index -> requested weight bits
MAX_LEN = 2048
BLOCK_SIZE = 16
CHUNK_TOKENS = 256
PARITY_PROMPT = 256
# Largest |pallas - reference| prefill logit, relative to the largest
# |reference| logit.  The integer GEMM cores of both impls are exact and
# their dequant epilogues are the same f32 ops; the gap comes from
# attention: the kernel's online softmax over 16-token blocks against the
# reference's one-shot softmax, whose f32 differences flip roundings of
# the bf16 attention output, and each flip can move an element of the
# next linear's 8-bit per-token activation quantization by a whole step.
# On the CPU (interpret vs reference, StableLM shapes cut to d_model
# 320-640 and 4-16 layers) that gap is 3-4% of the largest logit and does
# not grow with depth; 10% leaves room for the chip's different
# reduction order while a wrong kernel (bit order, head, block or scale
# routing) gives a gap of the logits' own size.
PARITY_RTOL = 0.10


class SmokeFailure(Exception):
    """A check of the smoke test did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, **fields) -> None:
    parts = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {parts}", flush=True)


def make_requests(vocab: int, seed: int):
    import numpy as np
    from repro.serving import engine as E
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, N_REQUESTS)
    return [E.Request(prompt=rng.integers(0, vocab, int(n), dtype=np.int32),
                      max_new_tokens=NEW_TOKENS,
                      precision=LOW_PRECISION.get(i))
            for i, n in enumerate(lens)]


def peak_hbm(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f}GiB"


def build(cfg, quant, seed: int, dev):
    """Seeded bf16 init under one jit (its program needs no temporaries),
    then per-layer packing that frees each bf16 weight once it is packed.
    Returns (packed params, init s, quantize s, peak HBM after init)."""
    import jax
    from repro.models import model as M
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(M.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed)))
    t1 = time.perf_counter()
    peak_init = peak_hbm(dev)
    qparams = jax.block_until_ready(
        M.quantize_params(params, quant, donate=True))
    t2 = time.perf_counter()
    return qparams, t1 - t0, t2 - t1, peak_init


def compile_decode(eng, cfg, quant, reqs):
    """Compile the decode step the engine runs with every request in one
    batch at the widest table bucket they reach; returns (seconds,
    tpu_custom_call count)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serving import engine as E
    batch = eng._decode_bucket(len(reqs))
    nb = min(E._next_pow2(max(eng.pool.blocks_for(len(r.prompt) + NEW_TOKENS)
                              for r in reqs)), eng.n_batch_blocks)
    caches = eng.pool.step_caches(np.zeros((batch, nb), np.int32),
                                  np.zeros(batch, np.int32),
                                  block_offsets=np.zeros(batch, np.int32))
    toks = jnp.zeros((batch, 1), jnp.int32)
    t0 = time.perf_counter()
    compiled = E.serve_program.lower(
        eng.params, {"tokens": toks, "positions": toks}, caches, cfg,
        quant).compile()
    dt = time.perf_counter() - t0
    n_kernels = sum('custom_call_target="tpu_custom_call"' in line
                    for line in compiled.as_text().splitlines())
    return dt, n_kernels


def serve(eng, reqs):
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.run()
    return time.perf_counter() - t0


def check_served(eng, reqs, vocab: int) -> dict:
    reasons = sorted({str(r.finish_reason) for r in reqs})
    check(all(r.done for r in reqs), "a request did not finish")
    check(reasons == ["length"], f"finish reasons {reasons}, want length")
    for r in reqs:
        check(len(r.out) == NEW_TOKENS,
              f"request emitted {len(r.out)} tokens, want {NEW_TOKENS}")
        check(all(0 <= t < vocab for t in r.out),
              f"token outside the vocabulary in {r.out}")
    faults = {k: v for k, v in eng.pool.metrics.snapshot().items()
              if k.startswith("repro_engine_fault")}
    check(all(v == 0 for v in faults.values()),
          f"fault counters not zero: {faults}")
    eng.pool.validate()
    check(eng.pool.free_blocks == eng.pool.n_usable,
          "blocks still held after every request finished")
    return {"reasons": reasons, "faults": faults}


def prefill_logits(eng, cfg, quant, prompt, impl: str):
    """Last-token logits of one prompt's paged prefill under ``impl``.
    The impl is read at trace time, hence the cleared jit caches."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.serving import engine as E
    ops.set_impl(impl)
    jax.clear_caches()
    s = len(prompt)
    ids = eng.pool.alloc(eng.pool.blocks_for(s))
    try:
        caches = eng.pool.step_caches(np.asarray([ids], np.int32),
                                      np.zeros(1, np.int32))
        batch = {"tokens": jnp.asarray(prompt)[None],
                 "positions": jnp.arange(s, dtype=jnp.int32)[None],
                 "last_idx": jnp.asarray([s - 1], jnp.int32)}
        logits, _ = E.prefill_step_bucketed(eng.params, batch, caches,
                                            cfg, quant)
        return np.asarray(logits, np.float32)[0]
    finally:
        eng.pool.release(ids)
        ops.set_impl(None)


def parity(eng, cfg, quant, seed: int, impl: str):
    import numpy as np
    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(0, cfg.vocab, PARITY_PROMPT, dtype=np.int32)
    got = prefill_logits(eng, cfg, quant, prompt, impl)
    want = prefill_logits(eng, cfg, quant, prompt, "reference")
    live = slice(0, cfg.vocab)          # padded vocab slots are masked
    check(np.isfinite(got[live]).all(), f"non-finite {impl} logits")
    gap = float(np.abs(got[live] - want[live]).max())
    scale = float(np.abs(want[live]).max())
    return gap, scale


def run(cfg, quant, *, seed: int, expect_impl: str, dev) -> None:
    """All phases at ``cfg``; raises :class:`SmokeFailure` on a failed
    check.  ``expect_impl`` is the kernel impl the serving path must
    resolve to."""
    import numpy as np
    from repro.kernels import ops
    from repro.serving import engine as E

    qparams, t_init, t_quant, peak_init = build(cfg, quant, seed, dev)
    log("build", init_s=f"{t_init:.3f}", quantize_s=f"{t_quant:.3f}",
        peak_hbm_after_init=peak_init, peak_hbm=peak_hbm(dev))

    reqs = make_requests(cfg.vocab, seed)
    # every request's whole lifetime fits at once (+ the null block), and
    # the jitted steps return a fresh pool beside the one they read: two
    # pool copies must fit beside the weights
    n_blocks = 1 + sum(-(-(len(r.prompt) + NEW_TOKENS) // BLOCK_SIZE)
                       for r in reqs)
    eng = E.Engine(qparams, cfg, n_slots=N_REQUESTS, max_len=MAX_LEN,
                   quant=quant, paged=True, block_size=BLOCK_SIZE,
                   n_blocks=n_blocks, chunk_tokens=CHUNK_TOKENS)
    pool_bytes = E.kv_cache_bytes(eng.pool.caches)
    check(ops.default_impl() == expect_impl,
          f"kernels resolve to {ops.default_impl()!r}, want {expect_impl!r}")

    t_compile, n_kernels = compile_decode(eng, cfg, quant, reqs)
    log("compile", decode_step_s=f"{t_compile:.3f}",
        tpu_custom_call=n_kernels, impl=ops.default_impl())
    if expect_impl == "pallas":
        check(n_kernels > 0, "no Pallas kernel in the compiled decode step")

    t_serve = serve(eng, reqs)
    served = check_served(eng, reqs, cfg.vocab)
    n_tok = sum(len(r.out) for r in reqs)
    # tokens emitted per served weight width: the nested-width lanes ran
    widths = sorted({r.precision or quant.w_bits for r in reqs})
    per_width = {b: int(eng.pool.metrics.value("repro_engine_precision",
                                               bits=str(b)))
                 for b in widths}
    want = {b: NEW_TOKENS * sum((r.precision or quant.w_bits) == b
                                for r in reqs) for b in widths}
    check(per_width == want, f"tokens per width {per_width}, want {want}")
    log("serve", requests=len(reqs), tokens=n_tok,
        prompt_tokens=sum(len(r.prompt) for r in reqs),
        tokens_per_weight_bits=per_width,
        finish_reasons=served["reasons"], fault_counters=served["faults"],
        wall_s_including_compiles=f"{t_serve:.3f}", steps=eng.steps,
        n_blocks=n_blocks, pool_gib=f"{pool_bytes / 2**30:.3f}",
        peak_hbm=peak_hbm(dev))

    gap, scale = parity(eng, cfg, quant, seed, expect_impl)
    log("parity", prompt_tokens=PARITY_PROMPT, max_abs_diff=gap,
        max_abs_ref=scale, rtol=PARITY_RTOL)
    check(np.isfinite(gap) and gap <= PARITY_RTOL * scale,
          f"{expect_impl} vs reference logit gap {gap} > "
          f"{PARITY_RTOL} * {scale}")


def main() -> int:
    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
        from repro.configs import get_config
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from "
              f"{REPO}/src: {e}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devs)} "
              f"{dev.platform!r} device(s); this smoke serves only on a "
              f"TPU", file=sys.stderr)
        return 2
    log("device", platform=dev.platform, device_kind=repr(dev.device_kind),
        count=len(devs), jax=jax.__version__, compile_cache=cache_dir)
    cfg = get_config("stablelm-3b")
    quant = dataclasses.replace(cfg.quant, kv_bits=8)
    try:
        run(cfg, quant, seed=SEED, expect_impl="pallas", dev=dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED with an exception (above)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
