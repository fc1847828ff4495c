"""Train a small LM with the WSD schedule + fault-tolerance demo.

Trains a ~6M-param llama-family model for a few hundred steps on the
deterministic synthetic pipeline, simulates a preemption mid-run, resumes
from the latest atomic checkpoint, and verifies the loss curve continues
seamlessly.  Uses int8-quantized optimizer state (the bit-level storage
idea applied beyond the paper).

Run:  PYTHONPATH=src python examples/train_wsd.py [--steps 300]
"""

import argparse
import shutil

import numpy as np

from repro.configs import get_config
from repro.data.pipeline import DataSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.optimizer import AdamWConfig
from repro.train.trainer import (SimulatedPreemption, TrainConfig, Trainer)

CKPT = "/tmp/repro_example_wsd"


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    args = ap.parse_args()
    shutil.rmtree(CKPT, ignore_errors=True)

    cfg = get_config("llama3-8b").reduced(
        n_layers=4, d_model=128, n_heads=8, n_kv_heads=2, d_head=16,
        d_ff=256, vocab=512)
    spec = DataSpec(vocab=cfg.vocab, seq_len=64, global_batch=8, seed=7)
    print(f"model ~{cfg.param_count() / 1e6:.1f}M params, "
          f"WSD schedule, int8 AdamW state")

    tcfg = TrainConfig(
        num_steps=args.steps, peak_lr=1e-3, warmup_steps=20,
        schedule="wsd", adamw=AdamWConfig(state_bits=8),
        ckpt_dir=CKPT, ckpt_every=50, log_every=20,
        preempt_at=args.steps // 2)

    losses = []

    def log(step, loss):
        losses.append(loss)
        if step % tcfg.log_every == 0:
            print(f"  step {step:4d}  loss {loss:.3f}")

    t = Trainer(cfg, tcfg, spec)
    try:
        t.run(resume=False, on_step=log)
    except SimulatedPreemption as e:
        print(f"!! {e} -- restarting from checkpoint")

    tcfg2 = TrainConfig(**{**tcfg.__dict__, "preempt_at": None})
    t2 = Trainer(cfg, tcfg2, spec)
    state, _ = t2.run(resume=True, on_step=log)

    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"loss: {first:.3f} -> {last:.3f} over {len(losses)} steps "
          f"(preempted + resumed at step {args.steps // 2})")
    assert last < first - 0.5, "training did not converge"
    if t2.straggler_events:
        print(f"straggler watchdog flagged {len(t2.straggler_events)} "
              f"slow steps")
    print("done.")


if __name__ == "__main__":
    main()
