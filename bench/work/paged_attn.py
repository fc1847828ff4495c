"""Paged attention over the quantized KV pool
(``kernels/flash_attention.py``): the calls of one step, as the
configuration's architecture module counts them."""

from bench.work import kernel_calls


def calls(cfg: dict, lanes) -> list:
    return kernel_calls("paged_attn", cfg, lanes)
