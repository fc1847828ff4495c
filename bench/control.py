#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, taken on the chip in one
process (set-up is paid once; later runs reuse the compiled programs).

    python3 bench/control.py --workload <name> --seconds <s> \
        --seeds 1,2,...,12 --control-seeds 101,102,103 [--out FILE]

For each ``--seeds`` entry the cell runs as configured (the lower
reading: the widest logit gap of sound runs).  For each
``--control-seeds`` entry it runs with the program's next lower
precision switched on -- 4-bit activations and a 4-bit KV cache in place
of the configuration's 8 -- and the same comparison (the upper reading).
The benchmark's own runs never run this.  Each reading is printed as
one JSON line.
"""

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROL_QUANT = {"a_bits": 4, "kv_bits": 4}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args()
    from bench import harness, spec
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    control = copy.deepcopy(cell)
    control.config["quant"].update(CONTROL_QUANT)
    runs = [("program", s, cell) for s in args.seeds.split(",") if s] + \
        [("control", s, control) for s in args.control_seeds.split(",") if s]
    out = open(args.out, "a") if args.out else None
    warmed = set()
    for kind, seed, c in runs:
        t = time.time()
        res = harness.run_cell(c, int(seed), args.seconds, False, t_start=t,
                               warm=kind not in warmed)
        warmed.add(kind)
        line = json.dumps({"workload": args.workload, "kind": kind,
                           "seed": int(seed), "correct": res["correct"],
                           "checks": res["checks"],
                           "metrics": res["metrics"],
                           "window": res["window"],
                           "wall_s": time.time() - t})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
