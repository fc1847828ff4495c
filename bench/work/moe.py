"""Grouped MoE expert kernel (``kernels/moe.py``): the calls of one step,
as the configuration's architecture module counts them, and the bytes of
one expert's weights, which a step reads once for each expert it routes
a live token to."""

from bench import spec
from bench.work import kernel_calls


def calls(cfg: dict, lanes) -> list:
    return kernel_calls("moe", cfg, lanes)


def expert_bytes(cfg: dict) -> int:
    return spec.architecture(cfg).expert_bytes(cfg)
