"""MoE serving: the grouped expert-kernel rewire + capacity telemetry.

The rewire acceptance bar (ISSUE 8): with ``layers.GROUPED_MOE`` on
(one ``ap_moe_expert_linear`` launch pair per MoE layer) the paged
engine's greedy decode must be TOKEN-IDENTICAL to the pre-rewire
batched-over-E expert path on the MoE smoke configs -- equality, not
tolerance, because the grouped kernel's live rows are bit-identical to
``layers._expert_matmul`` and the combine gather never reads a dead
capacity row.  Rides along: the decode capacity clamp (satellite 1)
cannot change routing, and the ``metrics=True`` engine surfaces the
``repro_moe_*`` capacity-pressure series.
"""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import layers as L
from repro.models import model as M
from repro.serving import engine as E

MOE_SMOKE = ["mixtral-8x7b", "deepseek-moe-16b"]


@contextlib.contextmanager
def _grouped_moe(flag):
    """Flip the module-level grouped/legacy expert-path switch.

    The engine's steps are jitted with static (cfg, quant) only -- the
    flag is read at trace time, so both flips MUST drop the jit cache
    or the step would silently keep running the previously-traced
    path."""
    old = L.GROUPED_MOE
    L.GROUPED_MOE = flag
    jax.clear_caches()
    try:
        yield
    finally:
        L.GROUPED_MOE = old
        jax.clear_caches()


def _setup(name):
    cfg = get_config(name).reduced(n_layers=2)
    qcfg = dataclasses.replace(cfg.quant, kv_bits=8)
    assert qcfg.w_bits is not None, "MoE smoke configs ship quantized"
    params = M.quantize_params(M.init_params(cfg, jax.random.PRNGKey(1)),
                               qcfg)
    return cfg, qcfg, params


def _decode(params, cfg, qcfg, prompts, **kw):
    eng = E.Engine(params, cfg, n_slots=2, max_len=32, quant=qcfg,
                   paged=True, block_size=8, **kw)
    reqs = [E.Request(prompt=p.copy(), max_new_tokens=5) for p in prompts]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done and r.error is None for r in reqs)
    return [list(r.out) for r in reqs], eng


def _prompts(cfg, seed=11, n=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (5 + i,), dtype=np.int32)
            for i in range(n)]


def test_grouped_rewire_token_identical():
    """Paged greedy decode pre/post rewire, both MoE smoke archs
    (mixtral: all-MoE layers; deepseek-moe: first_dense prelude layer,
    so the dense and MoE block paths coexist in one forward)."""
    for name in MOE_SMOKE:
        cfg, qcfg, params = _setup(name)
        prompts = _prompts(cfg)
        with _grouped_moe(True):
            out_grouped, _ = _decode(params, cfg, qcfg, prompts)
        with _grouped_moe(False):
            out_legacy, _ = _decode(params, cfg, qcfg, prompts)
        assert out_grouped == out_legacy, (name, out_grouped, out_legacy)


def test_decode_capacity_clamped_without_changing_outputs():
    """Satellite 1: with t live tokens the dispatch can never hold more
    than t*k assignments per expert, so capacity rows above that bound
    are pure waste -- the clamp must remove them (smaller kernel grid)
    while keeping routing, outputs, and drop counts identical."""
    cfg, qcfg, _ = _setup("mixtral-8x7b")
    e, k = cfg.n_experts, cfg.top_k
    p = M.quantize_params(L.moe_init(jax.random.PRNGKey(3), cfg), qcfg)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (1, 1, cfg.d_model)), jnp.bfloat16)          # decode shape: t = 1
    big = dataclasses.replace(cfg, capacity_factor=16.0)  # ceil formula: 8
    y_big, _, st_big = L.moe_apply(p, x, big, quant=qcfg)
    assert int(st_big["capacity"]) == e * k, \
        "capacity must clamp to t*k live-token rows, not the ceil formula"
    assert int(st_big["dropped"]) == 0, \
        "the clamp only removes rows no token could ever occupy"
    # a factor whose ceil formula lands exactly on the clamp bound must
    # produce the same dispatch -- and therefore the same output bits
    y_ref, _, st_ref = L.moe_apply(p, x, cfg, quant=qcfg)
    np.testing.assert_array_equal(np.asarray(y_big), np.asarray(y_ref))
    assert int(st_big["load"].sum()) == int(st_ref["load"].sum()) == k


def test_moe_telemetry_surfaces_expert_load():
    """metrics=True engine on a MoE arch must emit the repro_moe_*
    series: one expert-load histogram sample per (layer, expert) per
    forward, and a capacity-utilization gauge in (0, 1]."""
    cfg, qcfg, params = _setup("mixtral-8x7b")
    _, eng = _decode(params, cfg, qcfg, _prompts(cfg), metrics=True)
    snap = eng.obs.registry.snapshot()
    n_load = snap.get("repro_moe_expert_load_count", 0.0)
    assert n_load > 0, "no expert-load samples reached the registry"
    assert n_load % cfg.n_layers == 0, \
        "each forward must report every MoE layer's expert-load row"
    util = snap.get("repro_moe_capacity_utilization", 0.0)
    assert 0.0 < util <= 1.0, snap
    # greedy decode at top_k=2, capacity clamped to t*k: nothing dropped
    assert snap.get("repro_moe_dropped_tokens_total", 0.0) == 0.0


def test_legacy_fallback_unquantized_params():
    """Float (unquantized) expert weights must keep taking the dense
    einsum fallback -- the grouped kernel only claims BipolarTensor
    experts -- and still serve end to end."""
    cfg = get_config("mixtral-8x7b").reduced(n_layers=2)
    qcfg = dataclasses.replace(cfg.quant, w_bits=None, kv_bits=8)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    out, _ = _decode(params, cfg, qcfg, _prompts(cfg))
    assert all(len(o) == 5 for o in out)


def _moe_layer(name="deepseek-moe-16b", **over):
    cfg = dataclasses.replace(get_config(name).reduced(n_layers=2), **over)
    qcfg = dataclasses.replace(cfg.quant, kv_bits=8)
    p = M.quantize_params(L.moe_init(jax.random.PRNGKey(3), cfg), qcfg)
    return cfg, qcfg, p


def test_serving_dispatch_is_dropless_and_skips_pad_rows():
    """A fused step's rows: lane 0 is a chunk of 8 real tokens, lanes 1-3
    decode lanes holding one real token ahead of 7 pads (position -1).
    With ``live`` given, every real token reaches its top-k experts,
    pads reach none and add no load, and the real rows' outputs are
    those of the real tokens dispatched alone."""
    cfg, qcfg, p = _moe_layer(capacity_factor=0.25)
    e, k = cfg.n_experts, cfg.top_k
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (4, 8, cfg.d_model)), jnp.bfloat16)
    live = np.zeros((4, 8), bool)
    live[0] = True
    live[1:, 0] = True
    y, _, st = L.moe_apply(p, x, cfg, quant=qcfg, live=jnp.asarray(live))
    n_live = int(live.sum())
    assert int(st["dropped"]) == 0
    assert int(st["load"].sum()) == k * n_live
    assert 0 < int(st["touched"]) <= e
    assert k * n_live <= int(st["computed"]) <= int(st["capacity"])
    # the real tokens alone, one lane, all live
    xr = x[jnp.asarray(live)][None]
    yr, _, str_ = L.moe_apply(p, xr, cfg, quant=qcfg,
                              live=jnp.ones(xr.shape[:2], bool))
    np.testing.assert_array_equal(np.asarray(y[jnp.asarray(live)]),
                                  np.asarray(yr[0]))
    np.testing.assert_array_equal(np.asarray(st["load"]),
                                  np.asarray(str_["load"]))
    # the capacity dispatch of the same step drops at this factor
    _, _, capped = L.moe_apply(p, x, cfg, quant=qcfg)
    assert int(capped["dropped"]) > 0


def test_dropless_rows_match_the_capacity_dispatch_where_it_drops_none():
    """Where capacity holds every assignment, both dispatches compute the
    same rows: same routing, same per-row quantization, same combine."""
    for name in MOE_SMOKE:
        cfg, qcfg, p = _moe_layer(name, capacity_factor=16.0)
        x = jnp.asarray(np.random.default_rng(2).standard_normal(
            (3, 5, cfg.d_model)), jnp.bfloat16)
        y_cap, _, st_cap = L.moe_apply(p, x, cfg, quant=qcfg)
        y, _, st = L.moe_apply(p, x, cfg, quant=qcfg,
                               live=jnp.ones((3, 5), bool))
        assert int(st_cap["dropped"]) == int(st["dropped"]) == 0
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_cap))
        np.testing.assert_array_equal(np.asarray(st["load"]),
                                      np.asarray(st_cap["load"]))


def test_moe_counters_count_live_assignments_and_kernel_rows():
    """``repro_moe_assignments_total{kind=live}`` is every token the
    model was fed, times top-k, times the MoE layers -- pads never
    count -- and the kernel computes at least those rows."""
    cfg, qcfg, params = _setup("deepseek-moe-16b")
    prompts = _prompts(cfg)
    outs, eng = _decode(params, cfg, qcfg, prompts, metrics=True,
                        chunk_tokens=4)
    snap = eng.obs.registry.snapshot()
    n_moe = cfg.n_layers - cfg.first_dense
    fed = sum(len(p) + len(o) - 1 for p, o in zip(prompts, outs))
    live = snap["repro_moe_assignments_total{kind=\"live\"}"]
    assert live == n_moe * cfg.top_k * fed
    assert snap["repro_moe_assignments_total{kind=\"computed\"}"] >= live
    touched = snap["repro_moe_experts_touched_total"]
    assert 0 < touched <= n_moe * cfg.n_experts * eng.steps
    assert snap.get("repro_moe_dropped_tokens_total", 0.0) == 0.0
