"""StableLM-3B-4E1T [hf:stabilityai/stablelm-3b-4e1t, config.json] -- dense.

32L d_model=2560 32H (kv=32, MHA, d_head=80) d_ff=6912 vocab=50304,
4096-token context.  Partial rotary (25%, theta 1e4), LayerNorm
(eps 1e-5), SwiGLU, untied embeddings, no QKV bias.
"""

from repro.models.config import ModelConfig, QuantConfig

CONFIG = ModelConfig(
    name="stablelm-3b",
    family="dense",
    n_layers=32,
    d_model=2560,
    n_heads=32,
    n_kv_heads=32,
    d_ff=6912,
    vocab=50304,
    norm_type="layernorm",
    rope_pct=0.25,
    rope_theta=10000.0,
    quant=QuantConfig(w_bits=3, a_bits=8),
    max_seq_len=4096,
)
