"""Fused quantized linear (``kernels/apmm.py``): the calls of one step,
as the configuration's architecture module counts them."""

from bench.work import kernel_calls


def calls(cfg: dict, lanes) -> list:
    return kernel_calls("apmm", cfg, lanes)
