"""One run of one cell: set-up, a measured window, the correctness
comparison, and the result line (see ``run.py`` for the command)."""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import statistics
import sys
import time
from pathlib import Path

import numpy as np

from bench import correct, serve, spec, weights
from bench import traffic as T

# upper bound on engine steps a second, for planning the window's shapes
MAX_STEPS_PER_S = 80
# seconds of the window a --trace 1 run records with the profiler
TRACE_SECONDS = 4.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# percentiles of the gap between tokens and of time to first token that a
# cell may report as itl_p<q>_ms / ttft_p<q>_ms
PERCENTILES = (50, 90, 95, 99)

class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def process_start() -> float:
    """Wall-clock time at which this process started."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


def model_config(config: dict):
    """The program's ``ModelConfig`` with the sizes of ``config``, as its
    architecture module maps them."""
    from repro.configs import get_config
    base = get_config(config["repo_config"])
    return dataclasses.replace(
        base, **spec.architecture(config).model_fields(config))


def quant_config(config: dict):
    from repro.models.config import QuantConfig
    q = config["quant"]
    return QuantConfig(w_bits=q["w_bits"], a_bits=q["a_bits"],
                       kv_bits=q["kv_bits"])


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets (``metrics/<name>.py``)."""
    cfg: dict
    peaks: dict
    steps: list
    trace: object
    compiles: int
    obs: object = None        # the engine's ServingObs in a traced run

    @staticmethod
    def work(name: str):
        import importlib
        return importlib.import_module(f"bench.work.{name}")


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def end_to_end(requests: list, w0: float, w1: float, seconds: float,
               setup_s: float) -> dict:
    """End-to-end metrics of the window ``[w0, w1]`` from the requests'
    host-clock token times; a cell reports those ``BENCHMARK.json``
    gives it.  Gaps between tokens count where both tokens fall in the
    window; time to first token, from the send, where the first token
    does."""
    tokens, gaps, ttft = 0, [], []
    for r in requests:
        ts = [t for t in r.times if w0 <= t <= w1]
        tokens += len(ts)
        gaps += [b - a for a, b in zip(ts, ts[1:])]
        if r.times and w0 <= r.times[0] <= w1:
            ttft.append(r.times[0] - r.sent_at)
    out = {"output_tokens_per_s": tokens / seconds, "setup_s": setup_s}
    for q in PERCENTILES:
        if gaps:
            out[f"itl_p{q}_ms"] = 1e3 * _pct(gaps, q)
        if ttft:
            out[f"ttft_p{q}_ms"] = 1e3 * _pct(ttft, q)
    return out


def _free(*trees) -> None:
    import jax
    for t in trees:
        for leaf in jax.tree_util.tree_leaves(t):
            if hasattr(leaf, "delete") and not leaf.is_deleted():
                leaf.delete()


def _log(t_start: float, phase: str, **fields) -> None:
    parts = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{time.time() - t_start:9.3f}s] {phase} {parts}",
          file=sys.stderr, flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             trace_dir: Path | None = None,
             keep_trace: bool = False, warm: bool = True) -> dict:
    """Set up, measure, check; returns the result object.  A trace is
    written under ``trace_dir`` and deleted once reduced, unless
    ``keep_trace``.  ``warm=False`` skips the warm-up, for a process that
    has already run the cell's step programs (``control.py``)."""
    import jax
    log = lambda phase, **kw: _log(t_start, phase, **kw)
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu" or len(devs) < cell.chips):
        raise NoDevice(f"need {cell.chips} TPU chip(s); JAX found "
                       f"{len(devs)} {dev.platform!r} device(s)")
    peaks = spec.peaks(dev.device_kind) if require_tpu else {}
    config = cell.config
    cfg, quant = model_config(config), quant_config(config)
    traffic = T.Traffic.from_dict(cell.traffic)

    compiles = {"on": False, "n": 0}

    def on_event(event, duration, **kw):
        if event == COMPILE_EVENT and compiles["on"]:
            compiles["n"] += 1
            compiles.setdefault("names", []).append(kw.get("fun_name"))
    jax.monitoring.register_event_duration_secs_listener(on_event)

    from repro.serving.engine import Engine
    struct = weights.structure(cfg, quant)
    params = weights.make_params(struct, seed)
    log("weights", device=dev.device_kind)
    eng_args = serve.engine_args(config, traffic)
    obs = serve.make_obs() if trace else None
    eng = Engine(params, cfg, quant=quant, metrics=obs, **eng_args)
    shapes, allocs = serve.plan_shapes(cfg, quant, traffic, eng_args, seed,
                                       int(seconds * MAX_STEPS_PER_S))
    log("planned", shapes=len(shapes), allocs=len(allocs))
    if warm:
        serve.warm(eng, cfg, quant, shapes, allocs)
    log("warmed")
    loop = serve.Loop(eng, traffic, seed, cfg.vocab)
    loop.start()
    ramp_steps = loop.ramp()
    log("ramped", steps=ramp_steps)

    if trace:
        trace_dir = trace_dir or spec.ROOT / ".bench_trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    w0 = time.perf_counter()
    setup_s = time.time() - t_start
    w1 = w0 + seconds
    compiles["on"] = True
    t_traced = w0
    if trace:
        n0 = len(obs.steps)
        with jax.profiler.TraceAnnotation("bench.window"):
            while time.perf_counter() < w0 + min(TRACE_SECONDS, seconds):
                with jax.profiler.TraceAnnotation("bench.step"):
                    loop.step()
        t_traced = time.perf_counter()
        traced_steps = obs.steps[n0:]
        jax.profiler.stop_trace()
    while time.perf_counter() < w1:
        loop.step()
    compiles["on"] = False
    w_end = time.perf_counter()
    log("window", steps=eng.steps, compiles=compiles["n"],
        compiled=sorted(set(map(str, compiles.get("names", [])))))

    requests = loop.requests()
    attempted = [r for r in requests
                 if r.sent_at <= w1 and not (r.done and r.times
                                             and r.times[-1] < w0)]
    failed = sum(1 for r in attempted
                 if r.done and r.finish_reason != "length")
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    result = {"attempted": len(attempted), "failed": failed}

    if trace:
        from bench import trace as TR
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        red = TR.read_xplane(files[-1])
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        log("trace reduced", file=files[-1].name)
        ctx = Context(cfg=config, peaks=peaks, steps=traced_steps,
                      trace=red, compiles=compiles["n"], obs=obs)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown()
    else:
        e2e = end_to_end(requests, w0, w1, seconds, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    result["metrics"] = metrics
    result["device"] = device

    # the comparison: program state freed first, the reference after
    done = [r for r in loop.finished if r.times and w0 <= r.times[-1]
            and r.finish_reason == "length"]
    in_flight = [r for r in loop.current if not r.done]
    picked = correct.sample(done, in_flight, seed)
    _free(eng.pool.caches, params)
    del eng, loop, params, obs
    gc.collect()
    arch = spec.architecture(config)
    ref_params = weights.make_params(struct, seed)
    ref_w = arch.reference_weights(weights.plain(ref_params))
    gap, n_tok = correct.widest_gap(arch, ref_w, config, picked,
                                    t_max=traffic.max_tokens,
                                    m_max=traffic.output.max)
    log("reference", requests=len(picked), tokens=n_tok, gap=gap)
    limit = config["correct"]["max_logit_gap"]
    result["correct"] = bool(picked and n_tok > 0 and math.isfinite(gap)
                             and gap <= limit and failed == 0)
    result["checks"] = {
        "logit_gap": {"value": gap, "limit": limit},
        "tokens_compared": {"value": n_tok, "limit": 1},
        "failed_requests": {"value": failed, "limit": 0}}
    result["window"] = {"seconds": w_end - w0, "traced_s": t_traced - w0,
                        "requests_finished": len(done),
                        "shapes_warmed": len(shapes)}
    return result


def emit(result: dict) -> None:
    """Print the checks beside their limits on standard error, then the
    result object as the last line of standard output."""
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "window", "checks"]
    line = {k: result[k] for k in order if k in result}
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    t_start = process_start() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    cell = spec.load_cell(args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=t_start)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    emit(result)
    return 0
