"""Per-architecture smoke tests: reduced config, one forward + train step
on CPU, asserting output shapes and no NaNs (task spec deliverable f)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.launch.specs import make_batch
from repro.models import model as M
from repro.models.config import QuantConfig

SEQ, BATCH = 32, 2


@pytest.fixture(scope="module", params=ARCHS)
def arch_setup(request):
    cfg = get_config(request.param).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_forward_shapes_and_finite(arch_setup):
    cfg, params = arch_setup
    batch = make_batch(cfg, BATCH, SEQ, "train")
    x, _, aux = M.forward(params, batch["tokens"], cfg,
                          positions=batch.get("positions"),
                          patch_embeds=batch.get("patch_embeds"),
                          frames=batch.get("frames"))
    assert x.shape == (BATCH, SEQ, cfg.d_model)
    assert np.all(np.isfinite(np.asarray(x, dtype=np.float32))), cfg.name
    assert np.isfinite(float(aux))


def test_train_step_loss_and_grads_finite(arch_setup):
    cfg, params = arch_setup
    batch = make_batch(cfg, BATCH, SEQ, "train")

    @jax.jit
    def step(p):
        return jax.value_and_grad(lambda q: M.loss_fn(q, batch, cfg))(p)

    loss, grads = step(params)
    assert np.isfinite(float(loss)), cfg.name
    # loss should be near ln(vocab) for random init
    assert 0.5 * np.log(cfg.vocab) < float(loss) < 3 * np.log(cfg.vocab)
    leaves = jax.tree.leaves(grads)
    assert leaves, "no grads"
    for g in leaves:
        assert np.all(np.isfinite(np.asarray(g, dtype=np.float32))), cfg.name


def test_quantized_forward_close_to_bf16(arch_setup):
    """Serving-time W8A8 quantization must track the bf16 forward."""
    cfg, params = arch_setup
    q8 = QuantConfig(w_bits=8, a_bits=8)
    qparams = M.quantize_params(params, q8)
    batch = make_batch(cfg, BATCH, SEQ, "train")
    kw = dict(positions=batch.get("positions"),
              patch_embeds=batch.get("patch_embeds"),
              frames=batch.get("frames"))
    x0, _, _ = M.forward(params, batch["tokens"], cfg, **kw)
    x1, _, _ = M.forward(qparams, batch["tokens"], cfg, quant=q8, **kw)
    a0 = np.asarray(x0, dtype=np.float32)
    a1 = np.asarray(x1, dtype=np.float32)
    assert np.all(np.isfinite(a1))
    rel = np.abs(a1 - a0).mean() / (np.abs(a0).mean() + 1e-9)
    assert rel < 0.15, (cfg.name, rel)


def test_paper_w2a8_forward_finite(arch_setup):
    """The arch's assigned ultra-low-bit config stays finite end to end."""
    cfg, params = arch_setup
    qcfg = cfg.quant
    qparams = M.quantize_params(params, qcfg)
    batch = make_batch(cfg, BATCH, SEQ, "train")
    x, _, _ = M.forward(qparams, batch["tokens"], cfg, quant=qcfg,
                        positions=batch.get("positions"),
                        patch_embeds=batch.get("patch_embeds"),
                        frames=batch.get("frames"))
    assert np.all(np.isfinite(np.asarray(x, dtype=np.float32))), cfg.name


@pytest.mark.parametrize("chunk_elems", [None, 40 * 64],
                         ids=["default_chunks", "uneven_row_chunks"])
def test_quantize_params_matches_whole_leaf_quantization(arch_setup,
                                                         chunk_elems,
                                                         monkeypatch):
    """``quantize_params`` packs scan-stacked leaves one unit at a time,
    in row chunks; every packed word, scale and width scale must equal
    the whole-leaf quantization of the same leaf."""
    from repro.core.bipolar import BipolarTensor
    from repro.kernels import ops
    if chunk_elems:
        monkeypatch.setattr(M, "QUANT_CHUNK_ELEMS", chunk_elems)
    cfg, params = arch_setup
    qcfg = dataclasses.replace(cfg.quant, w_bits=cfg.quant.w_bits or 4)
    qparams = M.quantize_params(params, qcfg)
    flat = jax.tree_util.tree_flatten_with_path(
        qparams, is_leaf=lambda x: isinstance(x, BipolarTensor))[0]
    checked = 0
    for path, q in flat:
        if not isinstance(q, BipolarTensor):
            continue
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        w = params
        for k in keys:
            w = w[k]
        shape, n = w.shape, qcfg.w_bits
        t = ops.quantize_rows(w.reshape(-1, shape[-1]).astype(jnp.float32),
                              n, pad_bit=1, impl="reference",
                              scale_search=True)
        packed = t.packed.reshape(n, *shape[:-1], t.packed.shape[-1])
        ws = t.width_scales.reshape(n, *shape[:-1], 1)
        if any(k in ("blocks", "cross") for k in keys):
            packed, ws = jnp.moveaxis(packed, 0, 1), jnp.moveaxis(ws, 0, 1)
        np.testing.assert_array_equal(np.asarray(q.packed), np.asarray(packed))
        np.testing.assert_array_equal(np.asarray(q.scale),
                                      np.asarray(t.scale.reshape(
                                          *shape[:-1], 1)))
        np.testing.assert_array_equal(np.asarray(q.width_scales),
                                      np.asarray(ws))
        checked += 1
    assert checked >= 2, (cfg.name, checked)


def test_quantize_params_donate_frees_packed_leaves_only(arch_setup):
    """``donate=True`` gives the same packed tree and deletes exactly the
    float weights it packed; the leaves it keeps stay usable."""
    from repro.core.bipolar import BipolarTensor
    cfg, params = arch_setup
    qcfg = dataclasses.replace(cfg.quant, w_bits=cfg.quant.w_bits or 4)
    want = M.quantize_params(params, qcfg)
    fresh = jax.tree.map(jnp.copy, params)
    got = M.quantize_params(fresh, qcfg, donate=True)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    is_q = lambda x: isinstance(x, BipolarTensor)
    kept = {id(x) for x in jax.tree.leaves(got) if not x.is_deleted()}
    n_deleted = 0
    for leaf in jax.tree.leaves(fresh):
        if leaf.is_deleted():
            n_deleted += 1
        else:
            assert id(leaf) in kept, cfg.name
    assert n_deleted == sum(
        1 for q in jax.tree.leaves(want, is_leaf=is_q) if is_q(q)), cfg.name
