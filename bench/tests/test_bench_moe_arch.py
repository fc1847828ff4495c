"""The MoE decoder module (``arch/moe_decoder.py``): a toy DeepSeek-MoE
(a leading dense layer, then layers of 8 routed experts, top 3, and two
shared experts) served through the paged engine on the CPU and checked
by the module's plain reference; its work counts and the two readers of
the grouped expert kernel's metrics."""

import copy
import time
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, serve, spec, trace, weights
from bench.arch import dense_decoder
from bench.arch import moe_decoder as MD
from bench.serve import StepRecord
from bench.tests import tiny
from bench.work import moe

NAME = "deepseek-moe-16b.w2a8kv8"
CELL = "deepseek-moe-16b.decode-heavy"
TOY = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=4, head_dim=16, intermediate_size=128,
           vocab_size=256, n_routed_experts=8, num_experts_per_tok=3,
           n_shared_experts=2, moe_intermediate_size=32)
SEED = 2**32 + 11


def toy_config(**changes) -> dict:
    cfg = copy.deepcopy(tiny.load_config(NAME))
    cfg.update(TOY)
    cfg["engine"]["chunk_tokens"] = 32
    cfg["quant"].update({k: v for k, v in changes.items()
                         if k in ("a_bits", "kv_bits")})
    cfg.update({k: v for k, v in changes.items()
                if k not in ("a_bits", "kv_bits")})
    cfg["correct"]["max_logit_gap"] = tiny.TINY_LIMIT
    return cfg


def toy_cell(**changes) -> spec.Cell:
    return spec.Cell(name="tiny-moe", chips=1, config=toy_config(**changes),
                     traffic=tiny.TINY_TRAFFIC, end_to_end=[], per_layer=[])


def _run(cell):
    return harness.run_cell(cell, SEED, 1.0, False, t_start=time.time(),
                            require_tpu=False)


def test_the_cell_names_the_module_and_the_program_takes_its_fields():
    published = tiny.load_config(NAME)
    assert spec.architecture(published) is MD
    assert published["reduced"] == []
    cfg = harness.model_config(published)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim) == \
        (28, 2048, 16, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.n_shared_experts,
            cfg.expert_d_ff, cfg.first_dense, cfg.d_ff) == \
        (64, 6, 2, 1408, 1, 10944)
    assert not cfg.norm_topk_prob and cfg.norm_eps == 1e-6
    assert cfg.vocab == 102400 and not cfg.tie_embeddings
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["name"] == NAME
    assert {m["name"] for m in cell.per_layer} == {"moe_roofline",
                                                   "moe.row_live_share"}


class _Recorder:
    """Records every dispatch's live and dispatched token rows and every
    MoE report, over the program's own ``ServingObs``."""

    def __new__(cls):
        from repro.obs import ServingObs

        class Obs(ServingObs):
            def __init__(self):
                super().__init__()
                self.dispatches, self.moe_rows = [], []

            def on_dispatch(self, *, live, lanes, tok_live, tok_lanes):
                self.dispatches.append((tok_live, tok_lanes))

            def on_moe_rows(self, *, live, computed, touched):
                super().on_moe_rows(live=live, computed=computed,
                                    touched=touched)
                self.moe_rows.append((live, computed, touched))
        return Obs()


def _serve(norm_topk_prob: bool):
    """Three requests through the paged engine on toy weights, with
    chunks short enough that decode lanes ride fused chunk steps."""
    from repro.serving.engine import Engine, Request
    config = toy_config(norm_topk_prob=norm_topk_prob)
    cfg, quant = harness.model_config(config), harness.quant_config(config)
    params = weights.make_params(weights.structure(cfg, quant), 5)
    obs = _Recorder()
    eng = Engine(params, cfg, quant=quant, metrics=obs, n_slots=3,
                 max_len=64, paged=True, block_size=8, n_blocks=3 * 8 + 1,
                 chunk_tokens=16)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new_tokens=m) for n, m in ((5, 12), (40, 6), (23, 9))]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done and len(r.out) == r.max_new_tokens for r in reqs)
    return config, cfg, MD.reference_weights(weights.plain(params)), reqs, obs


@pytest.fixture(scope="module")
def served():
    return {flag: _serve(flag) for flag in (False, True)}


def _gaps(served_run, norm_topk_prob: bool) -> np.ndarray:
    """Gaps of the served tokens under the reference with the given
    routing rule."""
    config, _, ref_w, reqs, _ = served_run
    config = dict(config, norm_topk_prob=norm_topk_prob)
    return np.concatenate([reference.served_gaps(
        MD, ref_w, config, r.prompt, np.asarray(r.out, np.int32), 64, 16)
        for r in reqs])


def test_served_tokens_match_the_reference_through_fused_steps(served):
    """Decode and fused chunk steps (decode lanes padded to the chunk's
    rows) through the paged engine: every served token is within the
    limit of the reference's best logit, no assignment is dropped, and
    every token fed through the model -- and no pad row -- is routed to
    its top k experts in every MoE layer."""
    config, cfg, _, reqs, obs = served[False]
    # a fused step: real tokens padded to more dispatched rows
    assert any(1 < live < rows for live, rows in obs.dispatches)
    fed = sum(len(r.prompt) + r.max_new_tokens - 1 for r in reqs)
    n_moe = cfg.n_layers - cfg.first_dense
    assert sum(live for live, _, _ in obs.moe_rows) == \
        n_moe * cfg.top_k * fed
    assert all(live <= computed for live, computed, _ in obs.moe_rows)
    snap = obs.registry.snapshot()
    assert snap.get("repro_moe_dropped_tokens_total", 0.0) == 0.0
    gaps = _gaps(served[False], False)
    assert gaps.size == 27 and gaps.max() <= tiny.TINY_LIMIT, gaps


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_top_k_renormalization_is_served_and_checked(served,
                                                     norm_topk_prob):
    """Each routing rule is served as its configuration says: its tokens
    lie near the reference's best under the same rule, and far below it
    under the other rule -- beyond the limit for DeepSeek's own rule."""
    run = served[norm_topk_prob]
    assert run[1].norm_topk_prob == norm_topk_prob
    same = _gaps(run, norm_topk_prob).max()
    other = _gaps(run, not norm_topk_prob).max()
    assert other > 3 * max(same, 0.05), (same, other)
    if not norm_topk_prob:
        assert same <= tiny.TINY_LIMIT < other, (same, other)


def test_sound_run_passes_and_the_control_fails():
    sound = _run(toy_cell())
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["tokens_compared"]["value"] > 20
    gap = sound["checks"]["logit_gap"]["value"]
    control = _run(toy_cell(a_bits=4, kv_bits=4))
    assert not control["correct"]
    assert control["checks"]["logit_gap"]["value"] > 3 * max(gap, 0.05)


def test_the_dense_module_cannot_check_it():
    config = toy_config()
    cfg, quant = harness.model_config(config), harness.quant_config(config)
    flat = weights.plain(weights.make_params(weights.structure(cfg, quant),
                                             5))
    # the dense weight map looks for a dense MLP in every stacked layer
    with pytest.raises(KeyError, match="w_gate"):
        dense_decoder.reference_weights(flat)
    assert MD.reference_weights(flat)["layers"]["experts"]["w_gate"][0] \
        .shape == (2, 2, 8, 32, 2)        # layers, planes, experts, N, Kw


def test_reference_gates_keep_or_renormalize_the_top_k():
    m = jnp.asarray(np.random.default_rng(1).standard_normal((5, 64)),
                    jnp.float32)
    router = jnp.asarray(np.random.default_rng(2).standard_normal((8, 64)),
                         jnp.float32)
    cfg = dict(num_experts_per_tok=3, n_routed_experts=8)
    kept = MD.gates(m, router, dict(cfg, norm_topk_prob=False))
    renorm = MD.gates(m, router, dict(cfg, norm_topk_prob=True))
    assert ((np.asarray(kept) > 0).sum(1) == 3).all()
    np.testing.assert_allclose(np.asarray(renorm).sum(1), 1.0, rtol=1e-6)
    assert (np.asarray(kept).sum(1) < 1.0).all()
    np.testing.assert_allclose(
        np.asarray(renorm), np.asarray(kept)
        / np.asarray(kept).sum(1, keepdims=True), rtol=1e-6)


# -- work counts ---------------------------------------------------------------

def test_expert_bytes_at_published_widths():
    cfg = tiny.load_config(NAME)
    # gate and up: 1408 rows of 64 words; down: 2048 rows of 44 words;
    # two planes of 4-byte words, and an f32 scale per row
    planes = 2 * 4 * (2 * 1408 * 64 + 2048 * 44)
    assert planes == 2_162_688
    assert moe.expert_bytes(cfg) == planes + 4 * (2 * 1408 + 2048)
    # 64 experts x 27 layers: about 3.8 GB of routed weights
    assert 27 * 64 * moe.expert_bytes(cfg) == pytest.approx(3.77e9,
                                                            rel=1e-3)


@pytest.mark.parametrize("lanes", [[(100, 1)] * 16, [(0, 256), (700, 1)]])
def test_moe_calls_count_live_assignments(lanes):
    cfg = tiny.load_config(NAME)
    calls = moe.calls(cfg, lanes)
    assert len(calls) == 27
    rows = 6 * sum(n for _, n in lanes)
    assert calls[0] == (2 * rows * 3 * 2048 * 1408,
                        rows * 2 * (2 * 2048 + 2 * 1408))
    assert moe.calls(cfg, []) == []


def test_linear_work_of_dense_and_shared_layers():
    cfg = tiny.load_config(NAME)
    calls = MD.apmm_calls(cfg, [(100, 1)])
    assert len(calls) == 28 + 1                 # every layer and the head
    attn = 4 * 2048 * 2048
    dense_ops = 2 * (attn + 3 * 10944 * 2048)
    shared_ops = 2 * (attn + 3 * 2816 * 2048)
    assert calls[0][0] == dense_ops
    assert all(c[0] == shared_ops for c in calls[1:28])
    assert calls[28][0] == 2 * 102400 * 2048
    assert len(MD.WORK["paged_attn"](cfg, [(100, 1)])) == 28


# -- readers -------------------------------------------------------------------

def _ctx(obs, seconds=None, peaks=None):
    obs.steps.extend(StepRecord([(100 + i, 1)] * 16, 16, 16, 0, 0.5)
                     for i in range(3))
    red = None
    if seconds is not None:
        red = trace.Reduction(window_s=1.0, busy_s=0.9, n_devices=1,
                              op_s={"moe_expert_linear": seconds},
                              gaps=[])
    return harness.Context(
        cfg=tiny.load_config(NAME),
        peaks=peaks or spec.peaks("TPU v5 lite"), steps=obs.steps,
        trace=red, compiles=0, obs=obs)


@pytest.mark.parametrize("touched", [51 * 27, 64 * 27])
def test_roofline_reads_the_touched_experts(touched):
    obs = serve.make_obs()
    ctx = _ctx(obs, seconds=0.05)
    obs.profiled.moe_touched = 3 * touched
    cfg = ctx.cfg
    act = sum(b for _, b in moe.calls(cfg, [(100, 1)] * 16))
    least = (3 * act + 3 * touched * moe.expert_bytes(cfg)) / 819e9
    got = spec.metric_reader("moe_roofline")(ctx)
    assert got == pytest.approx(100.0 * least / 0.05)
    assert 0.0 < got <= 100.0


def test_roofline_is_left_out_without_its_inputs():
    read = spec.metric_reader("moe_roofline")
    obs = serve.make_obs()
    assert read(_ctx(obs, seconds=0.05)) is None     # no expert touched
    obs = serve.make_obs()
    ctx = _ctx(obs)                                  # no trace
    obs.profiled.moe_touched = 100
    assert read(ctx) is None
    obs = serve.make_obs()
    ctx = _ctx(obs, seconds=0.05)
    obs.profiled = SimpleNamespace(spans={}, entries_live=0,
                                   entries_walked=0)  # an older program
    assert read(ctx) is None
    obs = serve.make_obs()
    ctx = _ctx(obs, seconds=0.05)
    obs.profiled.moe_touched = 100
    ctx.trace.op_s = {"apmm_fused_linear": 0.05}     # the kernel never ran
    assert read(ctx) is None


@pytest.mark.parametrize("live,computed", [(96, 408), (1626, 2600),
                                           (300, 300)])
def test_row_live_share(live, computed):
    obs = serve.make_obs()
    ctx = _ctx(obs)
    obs.profiled.moe_live, obs.profiled.moe_computed = live, computed
    got = spec.metric_reader("moe.row_live_share")(ctx)
    assert got == pytest.approx(100.0 * live / computed)
    assert 0.0 < got <= 100.0


def test_row_live_share_is_left_out_without_its_inputs():
    read = spec.metric_reader("moe.row_live_share")
    obs = serve.make_obs()
    assert read(_ctx(obs)) is None                   # nothing computed
    obs.profiled = SimpleNamespace(spans={}, entries_live=0,
                                   entries_walked=0)  # an older program
    assert read(_ctx(obs)) is None
    assert read(harness.Context(cfg=None, peaks={}, steps=[], trace=None,
                                compiles=0, obs=obs)) is None
