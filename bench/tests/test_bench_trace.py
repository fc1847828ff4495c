"""Trace reduction: busy and idle time, time per op and per kernel, the
idle gaps by host span, and the readers built on them."""

import dataclasses
from pathlib import Path

import pytest

from bench import harness, spec
from bench import trace as TR
from bench.serve import StepRecord

MS = 1_000_000      # ns


def _planes():
    host = ("/host:CPU", [("python", [
        ("bench.window", 0, 100 * MS),
        ("bench.step", 0, 40 * MS),
        ("bench.step", 50 * MS, 40 * MS),
        ("other.span", 0, 100 * MS)])])
    dev = ("/device:TPU:0", [
        ("XLA Ops", [("apmm_fused_linear.3", 5 * MS, 10 * MS),
                     ("apmm_fused_linear.7", 15 * MS, 10 * MS),
                     ("fusion.1", 20 * MS, 10 * MS),   # overlaps the last
                     ("copy.2", 55 * MS, 20 * MS),
                     ("fusion.9", 95 * MS, 10 * MS)]),  # runs past the end
        ("XLA Modules", [("jit_serve_step", 0, 100 * MS)])])
    return [host, dev]


def test_reduction_of_a_synthetic_trace():
    red = TR.reduce_profile(_planes())
    assert red.window_s == pytest.approx(0.1)
    # busy: [5, 30] + [55, 75] + [95, 100] ms
    assert red.busy_s == pytest.approx(0.050)
    assert red.kernel_s(["apmm_fused_linear"]) == pytest.approx(0.020)
    assert red.op_s["fusion"] == pytest.approx(0.015)
    assert red.kernel_s(["flash_attention_paged_quantized"]) is None
    gaps = dict(red.gaps)
    # idle [0,5] [30,40] in step 1 .. [40,50] outside steps .. [50,55]
    # [75,90] in step 2, [90,95] outside
    assert gaps["bench.step"] == pytest.approx(0.035)
    assert gaps["bench.window"] == pytest.approx(0.015)
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["apmm_fused_linear", pytest.approx(0.02)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_a_trace_without_its_window_is_refused():
    planes = _planes()
    planes[0] = ("/host:CPU", [("python", [("bench.step", 0, 10)])])
    with pytest.raises(ValueError):
        TR.reduce_profile(planes)


def _ctx(red, steps=None):
    cfg = spec.load_json(spec.BENCH_DIR / "configs"
                         / "stablelm-3b.w3a8kv8.json")
    steps = steps if steps is not None else [
        StepRecord([(100 + i, 1) for i in range(16)], 16, 16, 0, 0.5)]
    return harness.Context(cfg=cfg, peaks=spec.peaks("TPU v5 lite"),
                           steps=steps, trace=red, compiles=0)


def test_readers_leave_out_what_they_cannot_read():
    red = TR.reduce_profile(_planes())
    ctx = _ctx(red)
    assert spec.metric_reader("paged_attn_roofline")(ctx) is None
    share = spec.metric_reader("apmm_roofline")(ctx)
    # 1.0 GB of 3-bit planes and 40 MB of activations over 819 GB/s,
    # against 20 ms of kernel time
    assert share == pytest.approx(100 * 1.0434e9 / 819e9 / 0.02, rel=0.005)
    assert spec.metric_reader("device.idle_share")(ctx) == pytest.approx(50)
    assert spec.metric_reader("engine.live_row_share")(ctx) == 100
    assert spec.metric_reader("window.compiles")(ctx) == 0
    empty = _ctx(None, steps=[])
    for name in ("apmm_roofline", "paged_attn_roofline", "model_step.mfu",
                 "device.idle_share", "engine.live_row_share",
                 "sched.chunk_step_share", "pool.blocks_used_share"):
        assert spec.metric_reader(name)(empty) is None


RECORDED = spec.BENCH_DIR / "testdata" / "decode_steps.xplane.pb.gz"


def test_reduction_of_a_trace_recorded_on_the_chip():
    """Eight StableLM-3B decode steps (16 lanes, 64-entry tables) traced
    on a v5e: the paged attention kernel took 3.55 s of the 4.14 s
    window, the fused linears 0.19 s, and the device idled 2.3%."""
    red = TR.read_xplane(RECORDED)
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(4.140, abs=0.01)
    assert red.busy_s / red.window_s == pytest.approx(0.9775, abs=0.005)
    attn = red.kernel_s(["flash_attention_paged_quantized"])
    apmm = red.kernel_s(["apmm_fused_linear"])
    assert attn == pytest.approx(3.550, abs=0.01)
    assert apmm == pytest.approx(0.194, abs=0.005)
    assert red.kernel_s(["no_such_kernel"]) is None
    bd = red.breakdown()
    assert bd["device_ops"][0][0] == "flash_attention_paged_quantized"
    assert "while" not in dict(bd["device_ops"])
    assert all(len(n) < 64 for n, _ in bd["device_ops"])
    assert sum(s for _, s in red.gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert {n for n, _ in red.gaps} <= {"bench.step", "bench.window"}
