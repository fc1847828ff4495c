"""The whole step: the linears and the attention the tokens need."""

from bench.work import apmm, paged_attn


def calls(cfg: dict, lanes) -> list:
    return apmm.calls(cfg, lanes) + paged_attn.calls(cfg, lanes)
