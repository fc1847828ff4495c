"""Fused quantized linear (``kernels/apmm.py``): every linear of every
layer, and the LM head on one row per lane."""


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


def calls(cfg: dict, lanes) -> list:
    if not lanes:
        return []
    bits = cfg["quant"]["w_bits"]
    n_layers, d, v = (cfg["num_hidden_layers"], cfg["hidden_size"],
                      cfg["vocab_size"])
    tokens, rows = sum(n for _, n in lanes), len(lanes)
    mats = linears(cfg)
    body_ops = 2 * tokens * sum(n * k for n, k in mats)
    body_bytes = (sum(bits * n * k // 8 + 4 * n for n, k in mats)
                  + tokens * act_bytes_per_token(cfg))
    head_ops = 2 * rows * v * d
    head_bytes = bits * v * d // 8 + 4 * v + 2 * rows * (d + v)
    return [(body_ops, body_bytes)] * n_layers + [(head_ops, head_bytes)]
