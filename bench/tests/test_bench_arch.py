"""Architecture modules: a configuration finds its module by name, and a
second architecture arrives as new files alone.  The toy MiniCPM cell
(tied head; embedding, residual and logit scales) is served through the
paged engine on the CPU and checked by the module that lives with the
tests (``tests/arch/scaled_tied_decoder.py``); the dense module cannot
check it, and a planted departure in the new module fails."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec, weights
from bench.arch import dense_decoder
from bench.tests import tiny
from bench.tests.arch import scaled_tied_decoder as STD

NAME = "minicpm-2b.w2a8kv8"


def _run(cell, seed=2**32 + 11):
    return harness.run_cell(cell, seed, 1.0, False, t_start=time.time(),
                            require_tpu=False)


def test_a_configuration_finds_its_module_by_name():
    stablelm = tiny.load_config("stablelm-3b.w3a8kv8")
    assert "arch" not in stablelm
    assert spec.architecture(stablelm) is dense_decoder
    assert spec.architecture(tiny.load_config(NAME)) is STD
    with pytest.raises(KeyError, match="dense_decoder"):
        spec.architecture({"arch": "no_such_decoder"})


def test_the_program_takes_the_modules_fields():
    cfg = harness.model_config(tiny.config(NAME))
    assert cfg.tie_embeddings and cfg.n_layers == 8
    assert cfg.emb_scale == 12.0
    assert cfg.residual_scale == pytest.approx(1.4 / np.sqrt(8))
    assert cfg.logit_scale == pytest.approx(256 / 64)


def test_reference_agrees_with_the_program_on_the_same_weights():
    from repro.models import model as M
    cfg_file = tiny.config(NAME)
    cfg = harness.model_config(cfg_file)
    quant = harness.quant_config(cfg_file)
    params = weights.make_params(weights.structure(cfg, quant), 5)
    ref_w = STD.reference_weights(weights.plain(params))
    assert "lm_head" not in ref_w
    tokens = np.random.default_rng(0).integers(0, cfg.vocab, 40)
    got = np.asarray(M.forward(params, jnp.asarray(tokens, jnp.int32)[None],
                               cfg, quant=quant, remat=False,
                               logits_mode="last")[0], np.float32)[0]
    want = np.asarray(STD.logits_at(ref_w, jnp.asarray(tokens, jnp.int32),
                                    jnp.asarray([39]),
                                    dict(STD.static(cfg_file))))[0]
    scale = np.abs(want).max()
    assert np.abs(got[:cfg.vocab] - want).max() < 0.05 * scale


def test_sound_run_passes_and_the_control_fails():
    sound = _run(tiny.cell(NAME))
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["tokens_compared"]["value"] > 20
    gap = sound["checks"]["logit_gap"]["value"]
    control = _run(tiny.cell(NAME, a_bits=4, kv_bits=4))
    assert not control["correct"]
    assert control["checks"]["logit_gap"]["value"] > 3 * max(gap, 0.05)


def test_the_dense_module_cannot_check_it():
    cell = tiny.cell(NAME)
    cell.config["arch"] = "dense_decoder"
    # the dense weight map looks for an untied, packed LM head
    with pytest.raises(KeyError, match="lm_head"):
        _run(cell)


_SOUND = {"block": STD.block, "scales": STD.scales,
          "model_fields": STD.model_fields}


def _plant(monkeypatch, departure: str) -> None:
    """Leave one scale out of the module's reference; the program keeps
    the module's fields."""
    if departure == "residual_scale":
        monkeypatch.setattr(STD, "block", lambda x, lp, pos, cfg, rs:
                            _SOUND["block"](x, lp, pos, cfg, 1.0))
    else:
        monkeypatch.setattr(STD, "scales", lambda config: dict(
            _SOUND["scales"](config), **{departure: 1.0}))
        monkeypatch.setattr(STD, "model_fields", lambda config: dict(
            _SOUND["model_fields"](config), **_SOUND["scales"](config)))


@pytest.fixture
def fresh_traces():
    """Drop traced programs before and after: the reference's jitted
    forward must trace the planted module, and later tests the sound
    one."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("departure", ["residual_scale", "emb_scale"])
def test_a_planted_departure_in_the_module_fails(departure, monkeypatch,
                                                 fresh_traces):
    _plant(monkeypatch, departure)
    res = _run(tiny.cell(NAME))
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > tiny.TINY_LIMIT
