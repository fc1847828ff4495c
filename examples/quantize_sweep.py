"""Accuracy-vs-bits sweep (the paper's §5.2 setting, scaled to CPU).

Trains a small LM briefly so its weights are meaningful, then measures
held-out cross-entropy under the paper's W{n}A8 bipolar quantization for
n in {1..8} plus the bf16 ceiling -- the quality/bits trade-off curve an
arbitrary-precision *scheme* exists to exploit (W3/W5/W6 are exactly the
points fixed-format kernels cannot serve).

Run:  PYTHONPATH=src python examples/quantize_sweep.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import DataSpec, batch_at
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.models.config import QuantConfig
from repro.train.trainer import TrainConfig, Trainer


def main():
    enable_compile_cache()
    cfg = get_config("llama3-8b").reduced(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_head=32,
        d_ff=256, vocab=512)
    spec = DataSpec(vocab=cfg.vocab, seq_len=64, global_batch=8, seed=3)
    tcfg = TrainConfig(num_steps=120, peak_lr=1e-3, warmup_steps=10,
                       ckpt_every=0, ckpt_dir="/tmp/repro_sweep")
    print("— pretraining a toy model (120 steps) …")
    state, hist = Trainer(cfg, tcfg, spec).run(resume=False)
    params = state["params"]

    held_out = [jax.tree.map(jnp.asarray, batch_at(spec, 10_000 + i))
                for i in range(4)]

    def ce(p, quant):
        return float(np.mean([
            float(M.loss_fn(p, b, cfg, quant=quant, remat=False))
            for b in held_out]))

    base = ce(params, None)
    print(f"bf16 ceiling: CE {base:.3f}")
    print(" bits |   CE   | ΔCE vs bf16")
    for bits in (8, 6, 5, 4, 3, 2, 1):
        q = QuantConfig(w_bits=bits, a_bits=8)
        qp = M.quantize_params(params, q)
        c = ce(qp, q)
        print(f"  W{bits}  | {c:6.3f} | +{c - base:.3f}")
    print("done. (W5/W6/W3 are the arbitrary-precision points the paper's "
          "scheme unlocks on hardware with int-only format catalogues)")


if __name__ == "__main__":
    main()
