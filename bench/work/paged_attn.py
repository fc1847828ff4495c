"""Paged attention over the quantized KV pool
(``kernels/flash_attention.py``): each new query attends every resident
token up to its own position; each lane's KV is read once, at
``kv_bits`` with one f32 scale per token and head."""


def calls(cfg: dict, lanes) -> list:
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
