"""Plain float32 reference forward of the served configurations.

Straight ``jax.numpy`` in float32 at the highest matmul precision, from
the published configuration (``configs/<name>.json``): no kernel, no
cache, no batching, no quantized activations or KV.  It imports nothing
of the program.  Weights arrive as packed bipolar-INT bit planes with
per-row scales, and are decoded here: element ``k`` of a row is bit
``k % 32`` of word ``k // 32`` of every plane, plane ``i`` weighs
``2**i``, and a bit reads -1 (0) or +1 (1) (the paper's Eq. 1).

Covers a decoder of pre-norm blocks: LayerNorm (with bias) or RMSNorm,
partial rotary embedding on the leading ``partial_rotary_factor`` of
each head (the two halves of the rotated part form the pairs), causal
grouped-query attention, and a SwiGLU MLP; untied LM head.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def decode_planes(packed, scale, k_dim: int):
    """``packed (n_bits, N, Kw)`` uint32 and ``scale (N, 1)`` -> the
    ``(N, K)`` float32 weight."""
    n_bits, n, kw = packed.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(n_bits, n, kw * 32)[:, :, :k_dim]
    signed = 2.0 * bits.astype(jnp.float32) - 1.0
    weight = (2.0 ** jnp.arange(n_bits, dtype=jnp.float32))[:, None, None]
    return jnp.sum(signed * weight, axis=0) * scale.astype(jnp.float32)


def _linear(x, w):
    """``x (T, K) @ W (N, K)^T`` for a packed ``w = (packed, scale)``."""
    packed, scale = w
    return jnp.dot(x, decode_planes(packed, scale, x.shape[-1]).T,
                   precision=HIGHEST)


def _lm_head(x, w):
    """The LM head over row blocks of the vocabulary: decoded whole, a
    151,552-row head would take gigabytes of temporaries."""
    packed, scale = w
    n_bits, vp, kw = packed.shape
    c = math.gcd(vp, 4096)
    blocks = (packed.reshape(n_bits, vp // c, c, kw).transpose(1, 0, 2, 3),
              scale.reshape(vp // c, c, 1))
    out = jax.lax.map(lambda b: _linear(x, b), blocks)     # (vp/c, M, c)
    return out.transpose(1, 0, 2).reshape(x.shape[0], vp)


def _norm(x, p, cfg):
    eps = cfg["norm_eps"]
    if cfg["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _rope(x, pos, cfg):
    """Rotary embedding on the leading part of each head; ``x (T, H, D)``."""
    d = x.shape[-1]
    rot = int(d * cfg["partial_rotary_factor"])
    rot -= rot % 2
    half = rot // 2
    inv = 1.0 / (cfg["rope_theta"] ** (np.arange(0, rot, 2) / rot))
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _block(x, lp, pos, cfg):
    t = x.shape[0]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    a = _norm(x, lp["norm1"], cfg)
    q = _rope(_linear(a, lp["wq"]).reshape(t, h, dh), pos, cfg)
    k = _rope(_linear(a, lp["wk"]).reshape(t, hk, dh), pos, cfg)
    v = _linear(a, lp["wv"]).reshape(t, hk, dh)
    k = jnp.repeat(k, h // hk, axis=1)          # query head j: kv head j // g
    v = jnp.repeat(v, h // hk, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(dh)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(t, h * dh)
    x = x + _linear(o, lp["wo"])
    m = _norm(x, lp["norm2"], cfg)
    f = jax.nn.silu(_linear(m, lp["w_gate"])) * _linear(m, lp["w_up"])
    return x + _linear(f, lp["w_down"])


def logits_at(weights, tokens, idx, cfg: dict):
    """Logits ``(M, vocab)`` at positions ``idx (M,)`` of ``tokens (T,)``."""
    pos = jnp.arange(tokens.shape[0])
    x = weights["embed"][tokens].astype(jnp.float32)

    def body(x, lp):
        return _block(x, lp, pos, cfg), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    x = _norm(x[idx], weights["final_norm"], cfg)
    return _lm_head(x, weights["lm_head"])[:, :cfg["vocab_size"]]


@partial(jax.jit, static_argnames=("cfg",))
def _served_gaps(weights, tokens, idx, served, cfg):
    """Logit gaps of served tokens.  ``tokens (T,)`` is a prompt with its
    served tokens (padded at the end); ``idx (M,)`` the positions whose
    logits chose ``served (M,)``.  Returns ``(M,)`` gaps: the reference's
    best logit minus its logit of the served token."""
    logits = logits_at(weights, tokens, idx, dict(cfg))
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return best - got


KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "norm",
        "norm_eps", "partial_rotary_factor", "rope_theta", "vocab_size")


def static(cfg: dict) -> tuple:
    """The sizes the forward reads, hashable for ``jax.jit``."""
    return tuple(sorted((k, cfg[k]) for k in KEYS))


def served_gaps(weights: dict, cfg: dict, prompt: np.ndarray,
                served: np.ndarray, t_max: int, m_max: int) -> np.ndarray:
    """Gap of each served token below the reference's best logit.

    ``weights``: ``embed (V, d)``, ``final_norm``, ``lm_head`` and
    ``layers`` (each leaf stacked over layers), packed linears as
    ``(packed, scale)``.  Sequences are padded to ``t_max`` tokens and
    ``m_max`` served tokens, so one program serves every request."""
    p, m = len(prompt), len(served)
    tokens = np.zeros(t_max, np.int32)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    tokens[:len(seq)] = seq
    idx = np.full(m_max, p - 1, np.int32)
    idx[:m] = np.arange(p - 1, p - 1 + m)
    srv = np.zeros(m_max, np.int32)
    srv[:m] = served
    gaps = _served_gaps(weights, jnp.asarray(tokens), jnp.asarray(idx),
                        jnp.asarray(srv), static(cfg))
    return np.asarray(gaps)[:m]
