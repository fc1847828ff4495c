"""Fused quantize -> bit-plane decompose -> uint32 pack Pallas kernel.

The paper preprocesses matrices ahead of time (§4.1).  Weights can always
be preprocessed offline, but LLM *activations* appear on the fly; this
kernel performs the whole §4.1 pipeline (quantize to bipolar-INT, 1-bit
decompose, pack into uint32 words, concatenate planes) in one VMEM pass so
the activation matrix is read once from HBM and only ``n_bits/16`` of its
bf16 volume is written back.

Layout produced: ``(n_bits, R, K/32)`` uint32 for a row-major matrix
``X (R, K)`` packed along the trailing reduction axis K (element k = 32w+b
-> bit b of word w), matching :func:`repro.kernels.apmm.apmm_packed` --
the same function packs activations (R = tokens) and weights (R = d_out).

Scales are computed *outside* (a cheap jnp absmax) and passed in; the
kernel is the bandwidth-heavy part.  K must be a multiple of 32 and tiled
exactly; the ops wrapper pads rows with ``-scale*(2^n-1)`` / ``+scale*
(2^n-1)`` values, which quantize to all-zero / all-one bits = the pad-bit
conventions of the closed-form K-pad correction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bipolar

DEFAULT_BR = 256
DEFAULT_BK = 1024


def _kernel(x_ref, scale_ref, out_ref, *, n_bits: int, br: int, bk: int):
    x = x_ref[...].astype(jnp.float32)             # (br, bk)
    s = scale_ref[...]                             # (br, 1)
    maxv = bipolar.max_value(n_bits)
    q = 2.0 * jnp.round((x / s - 1.0) * 0.5) + 1.0   # round to odd
    q = jnp.clip(q, -maxv, maxv)
    u = ((q.astype(jnp.int32) + maxv) >> 1).astype(jnp.uint32)  # bit field
    u = u.reshape(br, bk // 32, 32)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 32), 2)
    for i in range(n_bits):                        # plane i -> word sum
        bits = (u >> jnp.uint32(i)) & jnp.uint32(1)
        out_ref[i] = jnp.sum(bits << shifts, axis=2, dtype=jnp.uint32)


@functools.partial(
    jax.jit, static_argnames=("n_bits", "block", "interpret"))
def quantize_pack_rows(x: jax.Array, scale: jax.Array, *, n_bits: int,
                       block: tuple = (DEFAULT_BR, DEFAULT_BK),
                       interpret: bool = False) -> jax.Array:
    """Quantize + pack a row-major matrix ``X (R, K)`` along K.

    ``scale``: ``(R, 1)`` f32 per-row symmetric scales.
    Returns ``(n_bits, R, K/32)`` uint32.  Requires ``K % 32 == 0`` and
    exact tiling (the ops wrapper pads).
    """
    r, k = x.shape
    br, bk = block
    br, bk = min(br, r), min(bk, k)
    if k % 32 or r % br or k % bk or bk % 32:
        raise ValueError(f"shape ({r},{k}) not tiled by ({br},{bk})")
    kernel = functools.partial(_kernel, n_bits=n_bits, br=br, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=(r // br, k // bk),
        in_specs=[
            pl.BlockSpec((br, bk), lambda i, j: (i, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((n_bits, br, bk // 32),
                               lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_bits, r, k // 32), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, scale)
