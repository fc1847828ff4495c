"""Decoder with leading dense layers and fine-grained routed experts
(DeepSeekMoE): pre-norm blocks of causal attention (``dense_decoder``'s,
full rotary here); the first ``first_k_dense_replace`` layers end in
``dense_decoder``'s SwiGLU MLP, every later one in a softmax router over
``n_routed_experts`` SwiGLU experts of width ``moe_intermediate_size``.
A token takes its ``num_experts_per_tok`` most probable experts, each
weighted by its probability (renormalized to sum to 1 only where
``norm_topk_prob``), plus ``n_shared_experts`` shared experts taken as
one SwiGLU MLP of their summed width; an untied packed LM head.

Its published keys, reference weights, reference forward and the work of
its kernels; see ``arch/__init__.py`` for what each name gives.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference as R
from bench.arch import dense_decoder as D

FIELDS = dict(D.FIELDS, n_routed_experts="n_experts",
              n_shared_experts="n_shared_experts",
              num_experts_per_tok="top_k",
              moe_intermediate_size="expert_d_ff",
              first_k_dense_replace="first_dense",
              moe_layer_freq="moe_every", norm_topk_prob="norm_topk_prob")

ATTENTION = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")

STATIC_KEYS = D.STATIC_KEYS + ("n_routed_experts", "num_experts_per_tok",
                               "norm_topk_prob")


def model_fields(config: dict) -> dict:
    return {f: config[k] for k, f in FIELDS.items()}


# -- reference weights -------------------------------------------------------

def _attention_and_norms(flat: dict, blk: str) -> dict:
    out = {n: D.packed_weights(flat, f"{blk}['mixer']['{n}']")
           for n in ATTENTION}
    out["norm1"] = D.norm_weights(flat, f"{blk}['norm1']")
    out["norm2"] = D.norm_weights(flat, f"{blk}['norm2']")
    return out


def dense_layer_weights(flat: dict, blk: str) -> dict:
    return dict(_attention_and_norms(flat, blk),
                **{n: D.packed_weights(flat, f"{blk}['ffn']['{n}']")
                   for n in MLP})


def moe_layer_weights(flat: dict, blk: str) -> dict:
    """A layer with experts: its router ``(E, d)``, each routed expert
    linear as ``(planes (n_bits, E, N, Kw), scales (E, N, 1))`` and the
    shared experts' MLP (leaves stacked over layers)."""
    ffn = f"{blk}['ffn']"
    return dict(_attention_and_norms(flat, blk),
                router=flat[f"{ffn}['router']['w']"],
                experts={n: (flat[f"{ffn}['{n}'].packed"],
                             flat[f"{ffn}['{n}'].scale"]) for n in MLP},
                shared={n: D.packed_weights(flat, f"{ffn}['shared']['{n}']")
                        for n in MLP})


def reference_weights(flat: dict) -> dict:
    n_dense = len({k.split("]")[1] for k in flat
                   if k.startswith("['prelude']")})
    return {"embed": flat["['embed']['w']"],
            "final_norm": D.norm_weights(flat, "['final_norm']"),
            "lm_head": D.packed_weights(flat, "['lm_head']"),
            "dense": [dense_layer_weights(flat, f"['prelude'][{i}]")
                      for i in range(n_dense)],
            "layers": moe_layer_weights(flat, "['blocks'][0]")}


# -- reference forward -------------------------------------------------------

def gates(m, router, cfg):
    """``(T, E)`` weight of every expert for each row of the normed
    ``m``: its softmax probability where it is among the row's top
    ``num_experts_per_tok``, else 0."""
    probs = jax.nn.softmax(jnp.dot(m, router.T, precision=R.HIGHEST), -1)
    top_p, top_e = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    e = cfg["n_routed_experts"]
    return (jax.nn.one_hot(top_e, e) * top_p[..., None]).sum(1)


def experts(m, lp, cfg):
    """Routed plus shared experts of the normed ``m``: every routed
    expert runs on every row, one expert at a time (its planes decoded
    alone), and adds its output times its gate."""
    g = gates(m, lp["router"], cfg)
    ex = lp["experts"]
    per_expert = ({n: (jnp.moveaxis(p, 1, 0), s) for n, (p, s) in ex.items()},
                  g.T)

    def one(acc, inp):
        w, g_e = inp
        return acc + g_e[:, None] * D.mlp(m, w), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), per_expert)
    return y + D.mlp(m, lp["shared"])


def moe_block(x, lp, pos, cfg):
    x = x + D.attention(R.norm(x, lp["norm1"], cfg), lp, pos, cfg)
    return x + experts(R.norm(x, lp["norm2"], cfg), lp, cfg)


def logits_at(weights, tokens, idx, cfg: dict):
    pos = jnp.arange(tokens.shape[0])
    x = weights["embed"][tokens].astype(jnp.float32)
    for lp in weights["dense"]:
        x = D.block(x, lp, pos, cfg)

    def body(x, lp):
        return moe_block(x, lp, pos, cfg), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    x = R.norm(x[idx], weights["final_norm"], cfg)
    return R.lm_head(x, weights["lm_head"])[:, :cfg["vocab_size"]]


def static(config: dict) -> tuple:
    return tuple(sorted((k, config[k]) for k in STATIC_KEYS))


# -- work of the kernels -----------------------------------------------------

def _n_dense(cfg: dict) -> int:
    return cfg["first_k_dense_replace"]


def apmm_calls(cfg: dict, lanes) -> list:
    """Fused quantized linear: one call a layer for its attention and
    MLP linears -- the dense MLP in the leading layers, the shared
    experts' MLP in the others -- on every live token, and the packed LM
    head on one row per lane."""
    if not lanes:
        return []
    n_moe = cfg["num_hidden_layers"] - _n_dense(cfg)
    dense = dict(cfg, num_hidden_layers=_n_dense(cfg))
    shared = dict(cfg, num_hidden_layers=n_moe,
                  intermediate_size=(cfg["n_shared_experts"]
                                     * cfg["moe_intermediate_size"]))
    return D.layer_linear_calls(dense, lanes) + D.apmm_calls(shared, lanes)


def moe_calls(cfg: dict, lanes) -> list:
    """Grouped expert kernel (``kernels/moe.py``): one entry a layer with
    experts, for its two launches (gate and up, then down), over the
    live tokens' routed assignments: int8 operations, and the bytes of
    the bf16 activations in and out of each launch.  The experts'
    weights are not here: which experts a step reads depends on the
    routing (``expert_bytes`` times the program's count of experts
    touched)."""
    if not lanes:
        return []
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = sum(n for _, n in lanes) * cfg["num_experts_per_tok"]
    ops = 2 * rows * 3 * d * f
    nbytes = rows * 2 * (2 * d + 2 * f)
    return [(ops, nbytes)] * (cfg["num_hidden_layers"] - _n_dense(cfg))


def expert_bytes(cfg: dict) -> int:
    """Packed planes and scales of one routed expert's three linears at
    ``w_bits``: what the kernel must read once for each expert a step
    routes a token to."""
    bits = cfg["quant"]["w_bits"]
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    words = lambda k: -(-k // 32)
    return (bits * 4 * (2 * f * words(d) + d * words(f))
            + 4 * (2 * f + d))


WORK = {"apmm": apmm_calls, "paged_attn": D.paged_attn_calls,
        "moe": moe_calls}
