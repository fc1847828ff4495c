import pytest

from bench.arch import dense_decoder
from bench.tests import tiny
from bench.work import apmm, model_step, paged_attn


def _cfg(name, **quant):
    cfg = tiny.load_config(name)
    cfg["quant"] = dict(cfg["quant"], **quant)
    return cfg


def _weight_bytes(cfg, lanes):
    """Bytes of one decode step's APMM calls minus the activations."""
    per_tok = dense_decoder.act_bytes_per_token(cfg)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    calls = apmm.calls(cfg, lanes)
    body = calls[0][1] - len(lanes) * per_tok
    head = calls[-1][1] - 2 * len(lanes) * (d + v)
    return body, head


@pytest.mark.parametrize("bits,layer_bytes", [(3, 29_843_456),
                                              (2, 19_931_136)])
def test_stablelm_decode_weight_bytes(bits, layer_bytes):
    cfg = _cfg("stablelm-3b.w3a8kv8", w_bits=bits)
    # one layer: q k v o are 2560 x 2560, gate up are 6912 x 2560, down
    # 2560 x 6912; an f32 scale per output row (5 x 2560 + 2 x 6912)
    n_weights = 4 * 2560 * 2560 + 3 * 6912 * 2560
    assert layer_bytes == n_weights * bits // 8 + 4 * (5 * 2560 + 2 * 6912)
    body, head = _weight_bytes(cfg, [(100, 1)] * 16)
    assert body == layer_bytes
    assert head == 50304 * 2560 * bits // 8 + 4 * 50304
    # the whole decode step streams about 1.0 GB at 3 bits
    total = 32 * body + head
    assert total == pytest.approx({3: 1.003e9, 2: 0.670e9}[bits], rel=2e-3)


def test_glm_kv_bytes_per_token():
    cfg = _cfg("glm4-9b.w2a8kv8")
    # K and V: 2 kv heads x 128 dims x 8 bits + one f32 scale each
    per_layer = 2 * 2 * (128 + 4)
    one = paged_attn.calls(cfg, [(999, 1)])
    two = paged_attn.calls(cfg, [(1999, 1)])
    assert len(one) == 40
    assert two[0][1] - one[0][1] == 1000 * per_layer
    assert 40 * per_layer == 21120          # about 21 KB a token


def test_attention_ops_count_every_causal_key():
    cfg = _cfg("stablelm-3b.w3a8kv8")
    ops, _ = paged_attn.calls(cfg, [(10, 3)])[0]
    keys = 11 + 12 + 13
    assert ops == 4 * 32 * 80 * keys


def test_linear_ops_and_head_rows():
    cfg = _cfg("glm4-9b.w2a8kv8")
    lanes = [(0, 256), (500, 1), (600, 1)]
    calls = apmm.calls(cfg, lanes)
    assert len(calls) == 41
    d, f = 4096, 13696
    per_tok = 2 * (4096 * d + 2 * 256 * d + d * 4096 + 3 * f * d)
    assert calls[0][0] == 258 * per_tok
    assert calls[-1][0] == 3 * 2 * 151552 * d
    assert model_step.calls(cfg, lanes) == calls + paged_attn.calls(cfg,
                                                                    lanes)
    assert apmm.calls(cfg, []) == [] and paged_attn.calls(cfg, []) == []


def test_a_tied_head_is_no_fused_linear_call():
    cfg = _cfg("minicpm-2b.w2a8kv8")
    lanes = [(0, 32), (300, 1)]
    calls = apmm.calls(cfg, lanes)
    # the layers' linears only: the tied head is an XLA matmul
    assert calls == dense_decoder.layer_linear_calls(cfg, lanes)
    assert len(calls) == 40
    d, v = 2304, 122753
    head = (2 * 2 * v * d, 2 * v * d + 2 * 2 * (d + v))
    assert model_step.calls(cfg, lanes) == calls + [head] + \
        paged_attn.calls(cfg, lanes)
