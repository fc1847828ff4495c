"""The comparison that decides ``correct``, at toy widths on the CPU:
the plain reference agrees with the program, sound runs pass, and the
lower-precision control and planted faults fail."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec, weights
from bench.tests import tiny

CONFIGS = ["stablelm-3b.w3a8kv8", "glm4-9b.w2a8kv8"]


def _run(cell, seed=2**32 + 11):
    return harness.run_cell(cell, seed, 1.0, False, t_start=time.time(),
                            require_tpu=False)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_the_program_on_the_same_planes(name):
    from repro.models import model as M
    cfg_file = tiny.config(name)
    cfg = harness.model_config(cfg_file)
    quant = harness.quant_config(cfg_file)
    arch = spec.architecture(cfg_file)
    struct = weights.structure(cfg, quant)
    params = weights.make_params(struct, 5)
    ref_w = arch.reference_weights(weights.plain(params))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab, 40).astype(np.int32)
    got = np.asarray(M.forward(params, jnp.asarray(tokens)[None], cfg,
                               quant=quant, remat=False,
                               logits_mode="last")[0], np.float32)[0]
    want = np.asarray(arch.logits_at(
        ref_w, jnp.asarray(tokens), jnp.asarray([39]),
        dict(arch.static(cfg_file))))[0]
    scale = np.abs(want).max()
    # the program quantizes activations to 8 bits and runs in bf16
    assert np.abs(got[:cfg.vocab] - want).max() < 0.05 * scale
    # a wrong plane order or row scale is of the logits' own size
    bad = dict(ref_w, lm_head=(ref_w["lm_head"][0][::-1],
                               ref_w["lm_head"][1]))
    wrong = np.asarray(arch.logits_at(
        bad, jnp.asarray(tokens), jnp.asarray([39]),
        dict(arch.static(cfg_file))))[0]
    assert np.abs(got[:cfg.vocab] - wrong).max() > 0.3 * scale


@pytest.mark.parametrize("name", CONFIGS)
def test_sound_run_passes_and_the_control_fails(name):
    sound = _run(tiny.cell(name))
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["tokens_compared"]["value"] > 20
    gap = sound["checks"]["logit_gap"]["value"]
    control = _run(tiny.cell(name, a_bits=4, kv_bits=4))
    assert not control["correct"]
    assert control["checks"]["logit_gap"]["value"] > 3 * max(gap, 0.05)


def _altered_token(orig):
    def sample(row, seq):
        tok = orig(row, seq)
        if len(seq.req.out) == 3:
            tok = (tok + len(row) // 2) % len(row)
        return tok
    return staticmethod(sample)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_faults_under_the_timed_path_fail(fault, monkeypatch):
    from repro.serving import engine as E
    from repro.serving import paged_cache as P
    if fault == "token_altered":
        monkeypatch.setattr(E.Engine, "_sample_token",
                            _altered_token(E.Engine._sample_token))
    else:
        monkeypatch.setattr(P.PagedKVPool, "absorb",
                            lambda self, caches: None)
    res = _run(tiny.cell())
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > tiny.TINY_LIMIT
