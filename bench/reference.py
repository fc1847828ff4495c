"""Plain float32 building blocks of the reference forwards, and the gap of
served tokens below the reference's best logit.

Straight ``jax.numpy`` in float32 at the highest matmul precision: no
kernel, no cache, no batching, no quantized activations or KV.  It
imports nothing of the program.  Weights arrive as packed bipolar-INT
bit planes with per-row scales, and are decoded here: element ``k`` of a
row is bit ``k % 32`` of word ``k // 32`` of every plane, plane ``i``
weighs ``2**i``, and a bit reads -1 (0) or +1 (1) (the paper's Eq. 1).

Each architecture module (``arch/``) builds its forward, ``logits_at``,
from these blocks; ``served_gaps`` runs any of them.
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


def linear(x, w):
    """``x (T, K) @ W (N, K)^T`` for a packed ``w = (packed, scale)``."""
    packed, scale = w
    return jnp.dot(x, decode_planes(packed, scale, x.shape[-1]).T,
                   precision=HIGHEST)


def lm_head(x, w):
    """A packed LM head over row blocks of the vocabulary: decoded whole,
    a 151,552-row head would take gigabytes of temporaries."""
    packed, scale = w
    n_bits, vp, kw = packed.shape
    c = math.gcd(vp, 4096)
    blocks = (packed.reshape(n_bits, vp // c, c, kw).transpose(1, 0, 2, 3),
              scale.reshape(vp // c, c, 1))
    out = jax.lax.map(lambda b: linear(x, b), blocks)      # (vp/c, M, c)
    return out.transpose(1, 0, 2).reshape(x.shape[0], vp)


def norm(x, p, cfg):
    """LayerNorm with bias (``cfg["norm"] == "layernorm"``) or RMSNorm."""
    eps = cfg["norm_eps"]
    if cfg["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def rope(x, pos, cfg):
    """Rotary embedding on the leading ``partial_rotary_factor`` of each
    head, the two halves of the rotated part forming the pairs;
    ``x (T, H, D)``."""
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


@partial(jax.jit, static_argnames=("cfg", "logits_at"))
def _served_gaps(weights, tokens, idx, served, cfg, logits_at):
    """Logit gaps of served tokens.  ``tokens (T,)`` is a prompt with its
    served tokens (padded at the end); ``idx (M,)`` the positions whose
    logits chose ``served (M,)``.  Returns ``(M,)`` gaps: the reference's
    best logit minus its logit of the served token."""
    logits = logits_at(weights, tokens, idx, dict(cfg))
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return best - got


def served_gaps(arch, weights: dict, cfg: dict, prompt: np.ndarray,
                served: np.ndarray, t_max: int, m_max: int) -> np.ndarray:
    """Gap of each served token below the best logit of the reference of
    ``arch`` (an architecture module) on ``weights`` (its
    ``reference_weights``).  Sequences are padded to ``t_max`` tokens and
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
                        jnp.asarray(srv), arch.static(cfg), arch.logits_at)
    return np.asarray(gaps)[:m]
