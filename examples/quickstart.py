"""Quickstart: the paper's arbitrary-precision MatMul in five minutes.

Demonstrates (on CPU, reference/interpret impls):
 1. bipolar-INT quantization + §4.1 bit-plane packing (exact n bits/elt),
 2. the §3.2 bit-serial MatMul == the fused operand-recovery MatMul ==
    the exact integer product (bit-for-bit),
 3. the Pallas kernel (interpret mode) matching the oracle,
 4. quantized-GEMM accuracy vs the float GEMM across bit-widths.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bipolar
from repro.kernels import ops, ref
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()
rng = np.random.default_rng(0)
M, N, K = 64, 96, 300   # deliberately unaligned: pad correction in action

x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)   # activations
w = jnp.asarray(rng.standard_normal((N, K)), jnp.float32)   # weights

print("== 1. quantize + pack (paper §3.1 + §4.1) ==")
for bits in (1, 2, 3, 4):
    t = ops.pack_weight(w, bits, impl="reference")
    print(f"  W{bits}: packed {t.nbytes_packed:8d} B   "
          f"bf16 {t.nbytes_dense_bf16:8d} B   "
          f"({t.nbytes_dense_bf16 / t.nbytes_packed:.1f}x smaller)")

print("== 2. bit-serial == fused == exact (paper §3.2 / Fig. 2) ==")
at = ops.quantize_rows(x, 2, pad_bit=0, impl="reference")
bt = ops.quantize_rows(w, 3, pad_bit=1, impl="reference")
y_bs = ops.ap_matmul(at, bt, variant="bitserial", impl="reference", raw=True)
y_fu = ops.ap_matmul(at, bt, variant="fused", impl="reference", raw=True)
assert np.array_equal(np.asarray(y_bs), np.asarray(y_fu))
print(f"  W3A2 {M}x{N}x{K}: bit-serial and fused agree bit-for-bit "
      f"(checksum {int(np.asarray(y_fu).sum())})")

print("== 3. Pallas kernel (interpret mode) vs oracle ==")
y_k = ops.ap_matmul(at, bt, impl="interpret", raw=True)
assert np.array_equal(np.asarray(y_k), np.asarray(y_fu))
print("  pallas_call(interpret=True) matches the jnp oracle exactly")

print("== 4. accuracy vs float across bit-widths ==")
y_f = np.asarray(x) @ np.asarray(w).T
for wb, ab in ((1, 2), (2, 2), (3, 4), (4, 8), (8, 8)):
    wt = ops.pack_weight(w, wb, impl="reference")
    y_q = np.asarray(ops.ap_linear(x, wt, a_bits=ab, impl="reference"))
    rel = np.abs(y_q - y_f).mean() / np.abs(y_f).mean()
    print(f"  W{wb}A{ab}: mean relative error {rel * 100:6.2f}%")

print("done.")
