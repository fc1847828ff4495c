"""Seeded serving weights, made on the device in the packed layout.

The tree's structure -- which leaves are packed bit planes, their shapes
and dtypes -- is the program's own: ``jax.eval_shape`` of
``quantize_params(init_params(...))`` on a one-unit, small-vocabulary
copy of the configuration, widened to the full depth and vocabulary.
The values come from the seed, in one jitted call: random bit planes are
valid bipolar-INT values, so no float tree and no clip search is needed.
The reference regenerates the same values from the same seed.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# a padded vocabulary no width of the served configurations equals, so
# the widening below can tell the vocabulary axis by its size
_PROBE_VOCAB = 768


def key_for(seed: int, stream: int) -> jax.Array:
    """A PRNG key for ``stream`` of ``seed`` (any non-negative int)."""
    words = np.random.SeedSequence([int(seed) % 2**64, stream]) \
        .generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def structure(cfg, quant):
    """ShapeDtypeStruct tree of the packed serving params of ``cfg``."""
    from repro.core.bipolar import BipolarTensor
    from repro.models import model as M

    prelude, unit, n_units = M.plan_split(cfg)
    probe = dataclasses.replace(cfg, n_layers=len(prelude) + len(unit),
                                vocab=_PROBE_VOCAB)
    vp_probe, vp = probe.vocab_padded, cfg.vocab_padded
    widths = {cfg.d_model, cfg.d_ff, cfg.head_dim, cfg.n_heads * cfg.head_dim,
              cfg.n_kv_heads * cfg.head_dim}
    if vp_probe in widths:
        raise ValueError(f"a width of {cfg.name} equals the probe "
                         f"vocabulary {vp_probe}; the vocabulary axis "
                         f"cannot be told apart")
    tree = jax.eval_shape(
        lambda k: M.quantize_params(M.init_params(probe, k), quant),
        jax.random.PRNGKey(0))

    def widen(shape, stacked):
        shape = tuple(vp if d == vp_probe else d for d in shape)
        return ((n_units,) + shape[1:]) if stacked else shape

    def sds(x, stacked):
        return jax.ShapeDtypeStruct(widen(x.shape, stacked), x.dtype)

    def fix(path, leaf):
        stacked = getattr(path[0], "key", None) in ("blocks", "cross")
        if isinstance(leaf, BipolarTensor):
            ws = leaf.width_scales
            return dataclasses.replace(
                leaf, packed=sds(leaf.packed, stacked),
                scale=sds(leaf.scale, stacked),
                width_scales=None if ws is None else sds(ws, stacked),
                shape=widen(leaf.shape, False))
        return sds(leaf, stacked)

    return jax.tree_util.tree_map_with_path(
        fix, tree, is_leaf=lambda x: isinstance(x, BipolarTensor))


def _bipolar_rms(n_bits: int) -> float:
    """RMS of a uniformly random n-bit bipolar-INT value."""
    return float(np.sqrt((4.0 ** n_bits - 1.0) / 3.0))


def _packed(key, t, stacked: bool):
    """Random planes and per-row scales for one packed weight: entries of
    std about 1/sqrt(K), as a float init would give, with row scales
    spread over [0.5, 1.5] of that."""
    kb, ks = jax.random.split(key)
    k_dim = t.shape[t.pack_axis]
    words = jax.random.bits(kb, t.packed.shape, jnp.uint32)
    tail = k_dim % 32
    if tail:        # weights pad K with bit 1 (the program's pad_bit=1)
        pad = jnp.uint32(0xFFFFFFFF ^ ((1 << tail) - 1))
        words = words.at[..., -1].set(words[..., -1] | pad)
    base = 1.0 / (np.sqrt(k_dim) * _bipolar_rms(t.n_bits))
    scale = base * jax.random.uniform(ks, t.scale.shape, jnp.float32,
                                      0.5, 1.5)
    ws = None
    if t.width_scales is not None:
        axis = 1 if stacked else 0
        ws = jnp.stack([scale * float(2 ** (t.n_bits - k))
                        for k in range(1, t.n_bits + 1)], axis=axis)
    return dataclasses.replace(t, packed=words, scale=scale,
                               width_scales=ws)


def _dense(key, path, leaf):
    name = getattr(path[-1], "key", "")
    if name == "scale":                        # a norm's gain
        x = jax.random.uniform(key, leaf.shape, jnp.float32, 0.8, 1.2)
    elif name == "bias":
        x = 0.02 * jax.random.normal(key, leaf.shape, jnp.float32)
    else:                                      # embeddings
        x = 0.02 * jax.random.normal(key, leaf.shape, jnp.float32)
    return x.astype(leaf.dtype)


def make_params(struct, seed: int):
    """The seeded tree of ``struct``, built on the device in one call."""
    from repro.core.bipolar import BipolarTensor
    is_bt = lambda x: isinstance(x, BipolarTensor)
    flat, treedef = jax.tree_util.tree_flatten_with_path(struct,
                                                         is_leaf=is_bt)

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            if is_bt(leaf):
                stacked = getattr(path[0], "key", None) in ("blocks",
                                                            "cross")
                out.append(_packed(k, leaf, stacked))
            else:
                out.append(_dense(k, path, leaf))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(build)(key_for(seed, 0)))


def plain(params) -> dict:
    """``{path: array}`` of a params tree, with ``.packed``/``.scale``
    leaves named by their path: what the reference reads."""
    return {jax.tree_util.keystr(p): a
            for p, a in jax.tree_util.tree_flatten_with_path(params)[0]}
