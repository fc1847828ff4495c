"""Dense decoder: pre-norm blocks of causal grouped-query attention and a
SwiGLU MLP, LayerNorm (with bias) or RMSNorm, partial rotary embedding
(``reference.rope``), an untied packed LM head.  A configuration file
without an ``"arch"`` key means this architecture.

Its published keys, reference weights, reference forward and the work of
its kernels; see ``arch/__init__.py`` for what each name gives.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R

FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "d_head",
          "intermediate_size": "d_ff", "vocab_size": "vocab",
          "partial_rotary_factor": "rope_pct", "rope_theta": "rope_theta",
          "norm": "norm_type", "norm_eps": "norm_eps"}

# each layer's linears: the reference's name and the program's sub-tree
LINEARS = {"wq": "mixer", "wk": "mixer", "wv": "mixer", "wo": "mixer",
           "w_gate": "ffn", "w_up": "ffn", "w_down": "ffn"}

STATIC_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
               "norm", "norm_eps", "partial_rotary_factor", "rope_theta",
               "vocab_size")


def model_fields(config: dict) -> dict:
    return {f: config[k] for k, f in FIELDS.items()}


# -- reference weights -------------------------------------------------------

def norm_weights(flat: dict, prefix: str) -> dict:
    out = {"scale": flat[f"{prefix}['scale']"]}
    if f"{prefix}['bias']" in flat:
        out["bias"] = flat[f"{prefix}['bias']"]
    return out


def packed_weights(flat: dict, prefix: str) -> tuple:
    return (flat[f"{prefix}['w'].packed"], flat[f"{prefix}['w'].scale"])


def layer_weights(flat: dict) -> dict:
    """The stacked layers' linears and norms (each leaf stacked over
    layers)."""
    blk = "['blocks'][0]"
    layers = {name: packed_weights(flat, f"{blk}['{part}']['{name}']")
              for name, part in LINEARS.items()}
    layers["norm1"] = norm_weights(flat, f"{blk}['norm1']")
    layers["norm2"] = norm_weights(flat, f"{blk}['norm2']")
    return layers


def reference_weights(flat: dict) -> dict:
    return {"embed": flat["['embed']['w']"],
            "final_norm": norm_weights(flat, "['final_norm']"),
            "lm_head": packed_weights(flat, "['lm_head']"),
            "layers": layer_weights(flat)}


# -- reference forward -------------------------------------------------------

def attention(a, lp, pos, cfg):
    """Causal grouped-query attention of the normed ``a (T, d)``, through
    the output projection."""
    t = a.shape[0]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    q = R.rope(R.linear(a, lp["wq"]).reshape(t, h, dh), pos, cfg)
    k = R.rope(R.linear(a, lp["wk"]).reshape(t, hk, dh), pos, cfg)
    v = R.linear(a, lp["wv"]).reshape(t, hk, dh)
    k = jnp.repeat(k, h // hk, axis=1)          # query head j: kv head j // g
    v = jnp.repeat(v, h // hk, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=R.HIGHEST) / np.sqrt(dh)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=R.HIGHEST).reshape(t,
                                                                      h * dh)
    return R.linear(o, lp["wo"])


def mlp(m, lp):
    """SwiGLU MLP of the normed ``m``."""
    f = jax.nn.silu(R.linear(m, lp["w_gate"])) * R.linear(m, lp["w_up"])
    return R.linear(f, lp["w_down"])


def block(x, lp, pos, cfg):
    x = x + attention(R.norm(x, lp["norm1"], cfg), lp, pos, cfg)
    return x + mlp(R.norm(x, lp["norm2"], cfg), lp)


def logits_at(weights, tokens, idx, cfg: dict):
    pos = jnp.arange(tokens.shape[0])
    x = weights["embed"][tokens].astype(jnp.float32)

    def body(x, lp):
        return block(x, lp, pos, cfg), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    x = R.norm(x[idx], weights["final_norm"], cfg)
    return R.lm_head(x, weights["lm_head"])[:, :cfg["vocab_size"]]


def static(config: dict) -> tuple:
    return tuple(sorted((k, config[k]) for k in STATIC_KEYS))


# -- work of the kernels -----------------------------------------------------

def linears(cfg: dict) -> list:
    """``(N, K)`` of one layer's linears."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    dh = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh
    return [(q, d), (kv, d), (kv, d), (d, q), (f, d), (f, d), (d, f)]


def act_bytes_per_token(cfg: dict) -> int:
    """bf16 activations one token moves through a layer's linears, each
    distinct tensor once: the attention input, q/k/v, the attention
    output, o, its residual, the MLP input, silu(gate) * up, down and
    its residual."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    dh = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh
    return 2 * (d + (q + 2 * kv) + q + 2 * d + d + f + f + 2 * d)


def layer_linear_calls(cfg: dict, lanes) -> list:
    """One fused-linear call a layer, every linear of the layer on every
    live token."""
    bits = cfg["quant"]["w_bits"]
    tokens = sum(n for _, n in lanes)
    mats = linears(cfg)
    ops = 2 * tokens * sum(n * k for n, k in mats)
    nbytes = (sum(bits * n * k // 8 + 4 * n for n, k in mats)
              + tokens * act_bytes_per_token(cfg))
    return [(ops, nbytes)] * cfg["num_hidden_layers"]


def apmm_calls(cfg: dict, lanes) -> list:
    """Fused quantized linear (``kernels/apmm.py``): every linear of every
    layer, and the packed LM head on one row per lane."""
    if not lanes:
        return []
    bits = cfg["quant"]["w_bits"]
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    rows = len(lanes)
    head_ops = 2 * rows * v * d
    head_bytes = bits * v * d // 8 + 4 * v + 2 * rows * (d + v)
    return layer_linear_calls(cfg, lanes) + [(head_ops, head_bytes)]


def paged_attn_calls(cfg: dict, lanes) -> list:
    """Paged attention over the quantized KV pool
    (``kernels/flash_attention.py``), one call a layer: each new query
    attends every resident token up to its own position; each lane's KV
    is read once, at ``kv_bits`` with one f32 scale per token and head."""
    if not lanes:
        return []
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, kv_bits = cfg["head_dim"], cfg["quant"]["kv_bits"]
    ops = sum(4 * h * dh * (n * s + n * (n + 1) // 2) for s, n in lanes)
    ctx = sum(s + n for s, n in lanes)
    new = sum(n for _, n in lanes)
    nbytes = (ctx * 2 * hk * (dh * kv_bits // 8 + 4)     # K, V and scales
              + new * 2 * h * dh * 2)                     # q in, o out
    return [(ops, nbytes)] * cfg["num_hidden_layers"]


WORK = {"apmm": apmm_calls, "paged_attn": paged_attn_calls}
