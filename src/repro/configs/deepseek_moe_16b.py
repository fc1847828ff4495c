"""DeepSeekMoE-16B [arXiv:2401.06066] -- fine-grained MoE.

Published config: https://huggingface.co/deepseek-ai/deepseek-moe-16b-base
(config.json).  28L d_model=2048 16H (kv=16, MHA, head 128, full rotary,
theta 10000) vocab=102400, RMSNorm eps 1e-6, untied head.  MoE: 2 shared
+ 64 routed experts top-6 (softmax scores, ``norm_topk_prob: false``:
the top-6 probabilities are not renormalized), fine-grained expert
d_ff=1408.  Layer 0 keeps a dense FFN (d_ff 10944,
``first_k_dense_replace: 1``) -- modeled as the unrolled prelude; layers
1-27 are MoE.
"""

from repro.models.config import ModelConfig, QuantConfig

CONFIG = ModelConfig(
    name="deepseek-moe-16b",
    family="moe",
    n_layers=28,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab=102400,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    norm_topk_prob=False,
    expert_d_ff=1408,
    first_dense=1,
    norm_eps=1e-6,
    quant=QuantConfig(w_bits=3, a_bits=8),
    max_seq_len=524288,
)
