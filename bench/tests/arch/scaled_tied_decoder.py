"""Dense decoder with MiniCPM's scaling and a tied head: the blocks of
``arch/dense_decoder.py``, with the embedding scaled by ``scale_emb``,
both residual branches of each block by ``scale_depth /
sqrt(num_hidden_layers)``, the final hidden state by ``dim_model_base /
hidden_size``, and the embedding itself, unquantized, as the LM head.

It lives with the tests, which serve it at toy widths: a second
architecture that arrives as new files alone and is found by the lookup
every configuration goes through (``spec.architecture``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import reference as R
from bench.arch import dense_decoder as D

STATIC_KEYS = D.STATIC_KEYS + ("hidden_size", "num_hidden_layers",
                               "scale_emb", "scale_depth", "dim_model_base")


def scales(config: dict) -> dict:
    return {"emb_scale": float(config["scale_emb"]),
            "residual_scale": (config["scale_depth"]
                               / math.sqrt(config["num_hidden_layers"])),
            "logit_scale": config["dim_model_base"] / config["hidden_size"]}


def model_fields(config: dict) -> dict:
    return dict(D.model_fields(config),
                tie_embeddings=config["tie_word_embeddings"],
                **scales(config))


def reference_weights(flat: dict) -> dict:
    return {"embed": flat["['embed']['w']"],
            "final_norm": D.norm_weights(flat, "['final_norm']"),
            "layers": D.layer_weights(flat)}


def block(x, lp, pos, cfg, rs):
    x = x + rs * D.attention(R.norm(x, lp["norm1"], cfg), lp, pos, cfg)
    return x + rs * D.mlp(R.norm(x, lp["norm2"], cfg), lp)


def logits_at(weights, tokens, idx, cfg: dict):
    s = scales(cfg)
    pos = jnp.arange(tokens.shape[0])
    embed = weights["embed"].astype(jnp.float32)
    x = embed[tokens] * s["emb_scale"]

    def body(x, lp):
        return block(x, lp, pos, cfg, s["residual_scale"]), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    x = R.norm(x[idx], weights["final_norm"], cfg) * s["logit_scale"]
    return jnp.dot(x, embed.T, precision=R.HIGHEST)[:, :cfg["vocab_size"]]


def static(config: dict) -> tuple:
    return tuple(sorted((k, config[k]) for k in STATIC_KEYS))


def tied_head_calls(cfg: dict, lanes) -> list:
    """The tied head, an XLA matmul of bf16 operands on one row per
    lane."""
    if not lanes:
        return []
    d, v, rows = cfg["hidden_size"], cfg["vocab_size"], len(lanes)
    return [(2 * rows * v * d, 2 * v * d + 2 * rows * (d + v))]


def apmm_calls(cfg: dict, lanes) -> list:
    return D.layer_linear_calls(cfg, lanes) if lanes else []


WORK = {"apmm": apmm_calls, "lm_head": tied_head_calls,
        "paged_attn": D.paged_attn_calls}
