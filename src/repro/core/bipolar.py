"""Bipolar-INT data format (paper §3.1) and bit-plane pack/reassembly (§4.1).

An ``n``-bit bipolar-INT value ``x = x^(n-1) ... x^(1) x^(0)`` has decimal
value

    (x)_D = sum_i (2 * x^(i) - 1) * 2^i            (paper Eq. 1)

i.e. every bit is interpreted as -1 (bit=0) or +1 (bit=1).  The representable
set is the 2^n *odd* integers in ``[-(2^n - 1), 2^n - 1]`` -- perfectly
symmetric, no sign bit, no zero-point.  Every bit-plane is handled
identically, which is what makes the bit-serial MatMul decomposition a
uniform parallel loop (no two's-complement MSB special case).

This module is pure jnp and serves as both the public quantization API and
the oracle for the Pallas kernels (kernels/ref.py re-exports from here).

Conventions
-----------
* "value"  -- odd-integer bipolar value, int32.
* "ubits"  -- the unsigned bit field ``u = (value + (2^n - 1)) / 2`` in
  ``[0, 2^n)``; bit ``i`` of ``u`` is the bipolar bit ``x^(i)``.
* "planes" -- bit-plane tensor, leading axis = bit index, entries in {0, 1}
  (uint8), *interpreted* as {-1, +1}.
* "packed" -- planes packed along the reduction axis into uint32 words,
  planes concatenated on the leading axis (paper Fig. 3 steps 1-3).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

PACK_WIDTH = 32  # bits per packed word (uint32), paper §4.1 step 2


# ---------------------------------------------------------------------------
# Value-level encode / decode
# ---------------------------------------------------------------------------

def max_value(n_bits: int) -> int:
    """Largest representable bipolar-INT magnitude: 2^n - 1."""
    return (1 << n_bits) - 1


def encode(values: jax.Array, n_bits: int) -> jax.Array:
    """Odd-integer bipolar values -> unsigned bit field ``u`` (int32).

    ``u = (v + (2^n - 1)) / 2``; bit i of u is the bipolar bit x^(i).
    """
    v = values.astype(jnp.int32)
    return (v + max_value(n_bits)) >> 1


def decode(ubits: jax.Array, n_bits: int) -> jax.Array:
    """Unsigned bit field -> odd-integer bipolar value (int32)."""
    return (ubits.astype(jnp.int32) << 1) - max_value(n_bits)


def round_to_odd(x: jax.Array) -> jax.Array:
    """Round to the nearest odd integer (ties away from the even side)."""
    # nearest odd = 2 * round((x - 1) / 2) + 1;  jnp.round is
    # round-half-to-even on .5 ties which keeps the result unbiased.
    return 2.0 * jnp.round((x - 1.0) * 0.5) + 1.0


def quantize_values(x: jax.Array, n_bits: int, scale: jax.Array) -> jax.Array:
    """Real tensor -> odd-integer bipolar values (int32), symmetric scaling.

    ``q = clip(round_to_odd(x / scale), -(2^n-1), 2^n-1)``.
    """
    m = max_value(n_bits)
    q = round_to_odd(x / scale)
    return jnp.clip(q, -m, m).astype(jnp.int32)


def absmax_scale(x: jax.Array, n_bits: int, axis=None, keepdims=True,
                 eps: float = 1e-8) -> jax.Array:
    """Symmetric absmax scale so that absmax maps to +-(2^n - 1).

    Written as a reciprocal multiply, not a divide: XLA folds a
    constant-divisor divide into exactly this multiply when compiling,
    while eager mode executes a true division -- the explicit multiply
    is the one form that produces identical bits in every compilation
    context, which the bit-exact parity contracts between kernel impls
    rely on."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=keepdims)
    return jnp.maximum(amax, eps) * (1.0 / max_value(n_bits))


def mse_scale(x: jax.Array, n_bits: int, axis=-1, *,
              candidates: int = 15, lo: float = 0.65) -> jax.Array:
    """Per-group clip-searched scale minimizing quantization MSE.

    Sweeps ``candidates`` shrink factors in ``[lo, 1.0]`` of the absmax
    scale and keeps, per reduction group, the one with the smallest
    ``||q * s - x||^2``.  At low bit widths (<= 4) absmax wastes most of
    the grid on outliers; a mild clip roughly halves weight MSE and is
    what keeps greedy decode faithful at W4 (calibration-free analogue of
    the ABQ-LLM/AWQ clip search).  Offline-cost only -- used for weight
    preprocessing, never on the activation path.
    """
    xf = x.astype(jnp.float32)
    base = absmax_scale(xf, n_bits, axis=axis, keepdims=True)
    best_s, best_e = base, jnp.full_like(base, jnp.inf)
    for c in np.linspace(lo, 1.0, candidates):
        s = base * float(c)
        q = quantize_values(xf, n_bits, s)
        err = jnp.sum(jnp.square(q.astype(jnp.float32) * s - xf),
                      axis=axis, keepdims=True)
        take = err < best_e
        best_s = jnp.where(take, s, best_s)
        best_e = jnp.where(take, err, best_e)
    return best_s


def truncate_values(values: jax.Array, n_bits: int, k: int) -> jax.Array:
    """Top-``k`` plane prefix of ``n_bits``-bit bipolar values, as k-bit
    bipolar values (int32).

    Dropping the ``n_bits - k`` least-significant planes of the unsigned
    bit field is *round-to-nearest* onto the coarse k-bit grid scaled by
    ``2^{n_bits-k}``: the discarded low bits form an odd remainder of
    magnitude ``<= 2^{n_bits-k} - 1``, strictly under half the coarse
    spacing ``2^{n_bits-k+1}`` and never a tie -- which is why a plane
    slice of a packed tensor matches a direct k-bit quantization at the
    natural scale ``s * 2^{n_bits-k}`` (the nested-precision parity
    contract in tests/kernels/test_parity.py)."""
    if k == n_bits:
        return values.astype(jnp.int32)
    return decode(encode(values, n_bits) >> (n_bits - k), k)


def nested_width_scales(x: jax.Array, values: jax.Array, n_bits: int,
                        scale: jax.Array, axis=-1, *,
                        candidates: int = 15, lo: float = 0.8,
                        hi: float = 1.2) -> jax.Array:
    """Per-width dequant scales for a nested (prefix-truncatable) tensor.

    Row ``k-1`` is the scale to dequantize the top-``k`` plane slice of
    ``values`` (the integers are FIXED by the max-bit grid -- truncation
    only, no requantization), chosen by a clip search around the natural
    slice scale ``scale * 2^{n_bits-k}``: sweep ``candidates`` factors in
    ``[lo, hi]`` and keep, per reduction group, the one minimizing
    ``||v_k * s - x||^2`` (the fixed-integer analogue of
    :func:`mse_scale`'s clip search; offline cost only).  Row
    ``n_bits-1`` is ``scale`` itself, unconditionally -- a full-width
    slice must be the identity.  Returns ``(n_bits, *scale.shape)``.
    """
    xf = x.astype(jnp.float32)
    base = scale.astype(jnp.float32)
    rows = []
    for k in range(1, n_bits + 1):
        if k == n_bits:
            rows.append(base)
            continue
        vk = truncate_values(values, n_bits, k).astype(jnp.float32)
        natural = base * float(1 << (n_bits - k))
        best_s = natural
        best_e = jnp.full_like(natural, jnp.inf)
        for c in np.linspace(lo, hi, candidates):
            s = natural * float(c)
            err = jnp.sum(jnp.square(vk * s - xf), axis=axis,
                          keepdims=True)
            take = err < best_e
            best_s = jnp.where(take, s, best_s)
            best_e = jnp.where(take, err, best_e)
        rows.append(best_s)
    return jnp.stack(rows, axis=0)


# ---------------------------------------------------------------------------
# Bit-plane decomposition / recovery (paper §3.2 data decomposition step)
# ---------------------------------------------------------------------------

def decompose(values: jax.Array, n_bits: int) -> jax.Array:
    """Bipolar values -> bit planes ``(n_bits, *shape)`` uint8 in {0,1}."""
    u = encode(values, n_bits)
    shifts = jnp.arange(n_bits, dtype=jnp.int32)
    shifts = shifts.reshape((n_bits,) + (1,) * values.ndim)
    return ((u[None] >> shifts) & 1).astype(jnp.uint8)


def recover(planes: jax.Array, n_bits: int) -> jax.Array:
    """Bit planes -> bipolar values (int32).  Inverse of :func:`decompose`."""
    weights = (1 << jnp.arange(n_bits, dtype=jnp.int32))
    weights = weights.reshape((n_bits,) + (1,) * (planes.ndim - 1))
    signed = 2 * planes.astype(jnp.int32) - 1          # {0,1} -> {-1,+1}
    return jnp.sum(signed * weights, axis=0)


# ---------------------------------------------------------------------------
# uint32 packing / reassembly (paper §4.1, Fig. 3)
# ---------------------------------------------------------------------------

def packed_words(k: int) -> int:
    """Number of uint32 words covering ``k`` reduction elements."""
    return (k + PACK_WIDTH - 1) // PACK_WIDTH


def pack_planes(planes: jax.Array, axis: int) -> jax.Array:
    """Pack {0,1} planes into uint32 words along ``axis`` (step 2 of Fig. 3).

    ``axis`` indexes the *underlying tensor* dims (excluding the leading
    plane axis).  The packed axis shrinks by 32x; ``axis`` length must be a
    multiple of 32 (callers pad with :func:`pad_for_packing` first).

    Bit layout: element ``k`` lives in word ``k // 32`` at bit ``k % 32``.
    """
    axis = axis + 1 if axis >= 0 else axis  # account for leading plane axis
    k = planes.shape[axis]
    if k % PACK_WIDTH != 0:
        raise ValueError(f"pack axis length {k} not a multiple of {PACK_WIDTH}")
    x = jnp.moveaxis(planes, axis, -1).astype(jnp.uint32)
    x = x.reshape(x.shape[:-1] + (k // PACK_WIDTH, PACK_WIDTH))
    shifts = jnp.arange(PACK_WIDTH, dtype=jnp.uint32)
    words = jnp.sum(x << shifts, axis=-1, dtype=jnp.uint32)
    return jnp.moveaxis(words, -1, axis)


def unpack_planes(packed: jax.Array, axis: int, k: int) -> jax.Array:
    """uint32 words -> {0,1} planes (uint8); inverse of :func:`pack_planes`."""
    axis = axis + 1 if axis >= 0 else axis
    x = jnp.moveaxis(packed, axis, -1)
    shifts = jnp.arange(PACK_WIDTH, dtype=jnp.uint32)
    bits = (x[..., None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(x.shape[:-1] + (x.shape[-1] * PACK_WIDTH,))
    bits = bits[..., :k]
    return jnp.moveaxis(bits, -1, axis).astype(jnp.uint8)


def pad_for_packing(planes: jax.Array, axis: int, pad_bit: int) -> jax.Array:
    """Pad the pack axis to a multiple of 32 with a constant bit.

    Padding a bipolar plane is never free (bit 0 *means* -1), so matmul
    callers pad W with bit 1 (+1) and X with bit 0 (-1) and subtract the
    closed-form correction ``n_pad * (2^{n_w}-1) * (2^{n_x}-1) * (-1)``
    (see :func:`pad_correction`).
    """
    axis = axis + 1 if axis >= 0 else axis
    k = planes.shape[axis]
    pad = (-k) % PACK_WIDTH
    if pad == 0:
        return planes
    cfg = [(0, 0)] * planes.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(planes, cfg, constant_values=pad_bit)


@partial(jax.jit, static_argnames=("n_bits", "axis", "pad_bit"))
def pack_values(values: jax.Array, n_bits: int, axis: int,
                pad_bit: int) -> jax.Array:
    """Bipolar values -> packed words: :func:`decompose`,
    :func:`pad_for_packing` and :func:`pack_planes` as one program.

    Integer-only, so compiling it changes no bit.  Run op by op, the
    pack's ``(..., Kw, 32)`` intermediates are materialized, and a TPU
    stores them in (8, 128) tiles at 4x their size; for a 50304 x 2560
    lm_head on a v5e this program needs 0.48 GiB of temporaries."""
    planes = pad_for_packing(decompose(values, n_bits), axis, pad_bit)
    return pack_planes(planes, axis)


def pad_correction(k: int, n_w: int, n_x: int) -> int:
    """Additive correction for W-pad-bit=1 / X-pad-bit=0 K padding.

    Each padded k contributes ``(sum_i 2^i * (+1)) * (sum_j 2^j * (-1))
    = -(2^{n_w}-1)(2^{n_x}-1)`` to every output element; the true product
    is ``Y_raw + n_pad * (2^{n_w}-1)(2^{n_x}-1)``.
    """
    n_pad = (-k) % PACK_WIDTH
    return n_pad * max_value(n_w) * max_value(n_x)


# ---------------------------------------------------------------------------
# Quantized tensor container
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BipolarTensor:
    """A bipolar-INT quantized tensor in packed §4.1 layout.

    ``packed`` has shape ``(n_bits, *shape_with_K_packed)`` -- the n planes
    are concatenated on the leading axis (Fig. 3 step 3) with the reduction
    axis packed 32x into uint32 (step 2).  ``scale`` broadcasts against the
    dequantized tensor.

    ``width_scales`` (optional, ``(n_bits, *scale.shape)``) makes the
    tensor *nested*: row ``k-1`` is the clip-searched dequant scale for
    the top-``k`` plane prefix (:func:`nested_width_scales`), so one
    max-bit checkpoint serves every k <= n_bits via :func:`nested_slice`
    with no requantization.  Row ``n_bits-1`` equals ``scale``.
    """
    packed: jax.Array
    scale: jax.Array
    n_bits: int = dataclasses.field(metadata=dict(static=True))
    shape: tuple = dataclasses.field(metadata=dict(static=True))
    pack_axis: int = dataclasses.field(metadata=dict(static=True))
    width_scales: Optional[jax.Array] = None

    @property
    def nbytes_packed(self) -> int:
        return int(np.prod(self.packed.shape)) * 4 + int(np.prod(self.scale.shape)) * self.scale.dtype.itemsize

    @property
    def nbytes_dense_bf16(self) -> int:
        return int(np.prod(self.shape)) * 2


def quantize_pack(x: jax.Array, n_bits: int, pack_axis: int,
                  scale_axis=None, pad_bit: int = 1) -> BipolarTensor:
    """Real tensor -> packed bipolar-INT (quantize + decompose + pack).

    ``scale_axis``: axes reduced for the absmax scale (None = per-tensor).
    ``pad_bit``: 1 for weights (LHS), 0 for activations (RHS) -- see
    :func:`pad_correction`.
    """
    if scale_axis is None:
        scale = absmax_scale(x, n_bits)
    else:
        scale = absmax_scale(x, n_bits, axis=scale_axis, keepdims=True)
    q = quantize_values(x, n_bits, scale)
    packed = pack_values(q, n_bits, pack_axis, pad_bit)
    return BipolarTensor(packed=packed, scale=scale.astype(jnp.float32),
                         n_bits=n_bits, shape=tuple(x.shape),
                         pack_axis=pack_axis if pack_axis >= 0 else x.ndim + pack_axis)


def take_experts(t: BipolarTensor, idx: jax.Array) -> BipolarTensor:
    """Experts ``idx`` of a packed expert stack ``(E, N, K)`` (packed
    ``(n_bits, E, N, Kw)``), in that order, as a ``(len(idx), N, K)``
    stack: each dispatch tile's own copy of its expert's weights."""
    ws = t.width_scales
    return dataclasses.replace(
        t, packed=t.packed[:, idx], scale=t.scale[idx],
        width_scales=None if ws is None else ws[:, idx],
        shape=(idx.shape[0],) + tuple(t.shape[1:]))


def nested_slice(t: BipolarTensor, k: int) -> BipolarTensor:
    """Top-``k`` plane prefix of a packed tensor as a k-bit tensor.

    :func:`decompose` puts bit ``i`` (LSB first) at plane index ``i``,
    so the k most-significant planes are the TRAILING k entries of the
    leading plane axis -- the slice ``packed[n_bits-k:]`` reinterpreted
    with ``n_bits=k`` is exactly the truncated integers of
    :func:`truncate_values`.  K-pad columns stay valid: a weight packed
    with pad bit 1 keeps bit 1 in every remaining plane, decoding to
    ``+max_value(k)``, which is what :func:`pad_correction` at the
    sliced widths assumes.  The dequant scale comes from
    ``width_scales`` when present (clip-searched per width), else the
    natural ``scale * 2^{n_bits-k}``; the sliced tensor keeps the first
    k width-scale rows (top-j of top-k == top-j of the original), so
    slicing composes.  Expects the plane axis leading (``packed`` as
    stored by :func:`quantize_pack` / ``ops.quantize_rows``; stacked
    per-layer weights are sliced after the scan peels their unit axis).
    """
    m = t.n_bits
    if k == m:
        return t
    if not 1 <= k < m:
        raise ValueError(f"nested slice width {k} outside [1, {m}]")
    drop = m - k
    if t.width_scales is not None:
        scale = t.width_scales[k - 1]
        ws = t.width_scales[:k]
    else:
        scale = t.scale * float(1 << drop)
        ws = None
    return dataclasses.replace(t, packed=t.packed[drop:], scale=scale,
                               width_scales=ws, n_bits=k)


def dequantize(t: BipolarTensor) -> jax.Array:
    """Packed bipolar-INT -> real tensor (float32)."""
    k = t.shape[t.pack_axis]
    planes = unpack_planes(t.packed, t.pack_axis, k)
    values = recover(planes, t.n_bits)
    return values.astype(jnp.float32) * t.scale
