"""Tiny cells for the CPU tests: the served configurations' structure
(norms, rotary share, head grouping, bit widths, architecture) at toy
widths.  Configurations that no cell serves are test data in
``tests/configs/``."""

import copy
from pathlib import Path

from bench import spec

TEST_CONFIGS = Path(__file__).resolve().parent / "configs"

TINY_SIZES = {
    "stablelm-3b.w3a8kv8": dict(num_hidden_layers=2, hidden_size=64,
                                num_attention_heads=4,
                                num_key_value_heads=4, head_dim=16,
                                intermediate_size=128, vocab_size=256),
    "glm4-9b.w2a8kv8": dict(num_hidden_layers=2, hidden_size=64,
                            num_attention_heads=4, num_key_value_heads=2,
                            head_dim=32, intermediate_size=128,
                            vocab_size=256),
    # eight layers: a residual scale of 1.4 / sqrt(8) = 0.49, far enough
    # from 1 that leaving it out shows
    "minicpm-2b.w2a8kv8": dict(num_hidden_layers=8, hidden_size=64,
                               num_attention_heads=4,
                               num_key_value_heads=4, head_dim=16,
                               intermediate_size=128, vocab_size=256),
}
TINY_TRAFFIC = {"clients": 4,
                "prompt_tokens": {"dist": "lognormal", "median": 24,
                                  "sigma": 0.6, "min": 8, "max": 48},
                "output_tokens": {"dist": "uniform", "min": 8, "max": 24},
                "pool_blocks": 4 * 5 + 1}
# widest gap at these sizes: sound runs read 0.02-0.08, the A4/KV4
# control 2-3.3 (CPU, reference kernels)
TINY_LIMIT = 0.5


def load_config(name: str) -> dict:
    """A configuration file at its published sizes, from the test data or
    the benchmark's own."""
    path = TEST_CONFIGS / f"{name}.json"
    if not path.is_file():
        path = spec.BENCH_DIR / "configs" / f"{name}.json"
    return spec.load_json(path)


def config(name: str, **quant) -> dict:
    cfg = copy.deepcopy(load_config(name))
    cfg.update(TINY_SIZES[name])
    cfg["engine"]["chunk_tokens"] = 32
    cfg["quant"].update(quant)
    cfg["correct"]["max_logit_gap"] = TINY_LIMIT
    return cfg


def cell(name: str = "stablelm-3b.w3a8kv8", **quant) -> spec.Cell:
    full = spec.load_json(spec.ROOT / "BENCHMARK.json")
    return spec.Cell(name="tiny", chips=1, config=config(name, **quant),
                     traffic=TINY_TRAFFIC,
                     end_to_end=[m for m in full["end_to_end"]
                                 if "workloads" not in m],
                     per_layer=full["per_layer"])
