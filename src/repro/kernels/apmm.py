"""Arbitrary-precision MatMul (APMM) Pallas TPU kernels.

Implements the paper's bit-wise MatMul reconstitution (§3.2) with the
recovery-oriented memory scheduling of §4.2, adapted from GPU shared
memory / tensor-core fragments to the TPU memory hierarchy:

* HBM holds only the §4.1 *packed* layout: both operands are row-major and
  packed along their last (reduction) axis K into uint32 words --
  ``A (n_a, M, K/32)`` for activations (tokens x features, pad bit 0) and
  ``B (n_b, N, K/32)`` for weights (d_out x d_in, pad bit 1).  The GEMM is
  "NT" (``Y = A @ B^T``), so no operand transpose ever materializes and an
  n-bit matrix costs exactly n bits/element of HBM traffic.
* Each Pallas grid cell owns one ``(bm, bn)`` output tile (the paper's
  "one SM computes all bit-pair products of one block", §4.2 ①); all
  ``n_a * n_b`` bit-plane combinations for that tile are produced from
  VMEM-resident packed tiles, so *recovery never touches HBM* (§4.2 ②).
* Pallas grid pipelining double-buffers the HBM->VMEM tile streams --
  the TPU analogue of the paper's two alternating shared-memory buffers
  (§4.2 ③).
* Two variants:

  - ``variant="bitserial"`` (paper-faithful): unpack each plane to a
    {-1,+1} int8 tile, run one MXU GEMM per (i, j) bit pair, keep
    ``n_a * n_b`` int32 accumulators in VMEM scratch, and shift-add them
    into the output after the K loop -- the literal §3.2 dataflow with the
    §4.2 ④ loop order (one A plane reused against all B planes).  On GPU
    each per-pair GEMM is a 1-bit XOR-popcount MMA; the TPU has no 1-bit
    MXU mode, so plane GEMMs execute as int8 MXU ops (DESIGN.md §2,
    "what does not transfer").

  - ``variant="fused"`` (beyond-paper, TPU-native): because the MXU's
    atomic precision is already int8, the recovery sum can be folded into
    the *operands* -- ``(sum_i 2^i A^(i)) (sum_j 2^j B^(j))^T =
    sum_ij 2^{i+j} A^(i) B^(j)T`` exactly -- turning ``n_a * n_b`` GEMMs
    into one int8 GEMM per tile (valid for bit-widths <= 7).

K padding to the 32-bit word boundary is corrected in closed form by
pre-loading the accumulator with ``n_pad * (2^{n_a}-1)(2^{n_b}-1)``, so
arbitrary K is exact.

One-kernel quantized linear (:func:`apmm_fused_linear`): the activation
operand arrives as *float* tiles plus per-row scales, and the §4.1
preprocessing (quantize to bipolar-INT, bit-decompose) runs inside the
GEMM kernel's VMEM prologue -- packed activation planes never exist in
HBM, so one ``ops.ap_linear`` costs one kernel launch instead of two and
skips the ``n_a * M * K / 8``-byte packed round trip.  A fused epilogue
(bias add, none|silu|gelu activation, residual add, and a dual-GEMM
gate/up mode that streams one A tile against two weight operands and
writes ``act(gate) * up``) keeps the whole linear in the kernel; every
epilogue stage round-trips through ``out_dtype`` exactly where the
unfused composition casts, so fused and unfused outputs are
*bit-identical* (greedy decode is token-identical by construction).

Nested-precision serving needs no kernel changes: the kernels are
width-agnostic (``n_a``/``n_b`` are static parameters and the packed
plane axis is BlockSpec'd whole), so when ``ops`` plane-prefix slices a
nested weight (``bipolar.nested_slice``) the operand physically shipped
to the kernel holds only the served ``k`` planes -- HBM weight traffic
scales with the served width, not the stored one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bipolar
from repro.kernels import ref

# Default tile sizes: MXU-aligned (multiples of 128 on the GEMM dims).
# The reduction axis is never tiled: every kernel takes the whole packed
# row of ``Kw`` words as one block (the block's last dim then equals the
# array's, which Mosaic accepts for any ``Kw``), and walks K inside the
# kernel one bit position at a time (:func:`_bits`).
DEFAULT_BM = 256
DEFAULT_BN = 256

# Scoped-VMEM limit for the APMM kernels.  v5e has 128 MiB of VMEM per
# core; Mosaic's default scoped limit (16 MiB on v5e) is below what a
# whole-K block needs at prefill.  StableLM-3B's largest case, bm = bn =
# 256, K = 6912 (Kw = 216, padded to 256 lanes), 8-bit weights, dual
# gate/up, all blocks double-buffered:
#   x row block      2 * 32 * 256 * 256 * 2 B (bf16)     =  8 MiB
#   weight planes    2 * 2 * 8 * 256 * 256 * 4 B         =  8 MiB
#   out + residual   2 * 2 * 256 * 256 * 2 B             = 0.5 MiB
#   accumulators     2 * 256 * 256 * 4 B (fused)         = 0.5 MiB
#                    (bitserial: n_a * n_b = 64x that = 32 MiB)
#   per-bit temps    ~4 * 8 * 256 * 256 * 4 B            =  8 MiB
# K = 14336 (llama3-8b's down projection) doubles the first, second and
# last lines.  100 MiB covers both with the bitserial accumulators and
# keeps 28 MiB of VMEM for the surrounding XLA program.
VMEM_LIMIT_BYTES = 100 * 2**20

_NT = (((1,), (1,)), ((), ()))  # contract last dims (A @ B^T)


def _bits(words, b):
    """Bit ``b`` of every packed word: ``(n, r, Kw)`` uint32 -> ``(n, r,
    Kw)`` int32 planes in {0,1}.

    Word ``w`` holds K elements ``32*w .. 32*w + 31`` (bit ``i`` is
    element ``32*w + i``), so bit ``b`` of every word is the K subset
    ``{32*w + b}``.  The kernels contract K one such subset at a time
    (a ``fori_loop`` over ``b``): integer products summed in any order
    are the same sum, the planes keep the packed layout (no lane
    relayout), and the loop body -- hence Mosaic's compile time -- stays
    small whatever K is.
    """
    return ((words >> b.astype(jnp.uint32)) & jnp.uint32(1)).astype(jnp.int32)


def _bit_major(x):
    """``(..., R, Kp)`` -> ``(..., 32, R, Kp/32)`` with ``[..., b, r, w] =
    x[..., r, 32*w + b]``: the float activation columns each
    :func:`_bits` step contracts, as one contiguous slab per bit."""
    *lead, r, kp = x.shape
    x = x.reshape(*lead, r, kp // bipolar.PACK_WIDTH, bipolar.PACK_WIDTH)
    nd = x.ndim
    return jnp.moveaxis(x, nd - 1, nd - 3)


def _recover_int8(planes, lo: int, size: int):
    """{0,1} planes (n, r, k) -> recombined bipolar int8 (r, k) for the
    plane group ``[lo, lo+size)``.

    ``sum_{i in group} 2^{i-lo} (2 b_i - 1)
        = (sum b_i << (i-lo+1)) - (2^size - 1)``.
    """
    acc = planes[lo] << 1
    for i in range(lo + 1, lo + size):
        acc = acc + (planes[i] << (i - lo + 1))
    return (acc - bipolar.max_value(size)).astype(jnp.int8)


def _accumulate(a_u, bpl, acc_ref, *, n_a: int, n_b: int, variant: str):
    """Add one bit position's products to the accumulator(s).

    ``a_u`` is the activations' unsigned bit field ``(bm, Kw)`` (one
    integer per K element of the subset), ``bpl`` the weight planes
    ``(n_b, bn, Kw)`` in {0,1}.  ``fused``: operand-level recovery, one
    int8 MXU GEMM per <=7-bit plane-group pair.  ``bitserial``: one GEMM
    per bit pair into its own accumulator (paper §3.2/§4.2)."""
    if variant == "fused":
        for lo_a, sz_a in ref.plane_groups(n_a):
            mask = (1 << sz_a) - 1
            va = ((((a_u >> lo_a) & mask) << 1)
                  - bipolar.max_value(sz_a)).astype(jnp.int8)
            for lo_b, sz_b in ref.plane_groups(n_b):
                b8 = _recover_int8(bpl, lo_b, sz_b)
                y = jax.lax.dot_general(
                    va, b8, _NT, preferred_element_type=jnp.int32)
                acc_ref[...] += y << (lo_a + lo_b)
    else:
        for i in range(n_a):                        # §4.2 ④ loop order:
            a8 = (((a_u >> i) & 1) * 2 - 1).astype(jnp.int8)   # one A plane
            for j in range(n_b):                    # ... x all B planes
                b8 = (2 * bpl[j] - 1).astype(jnp.int8)
                acc_ref[i * n_b + j] += jax.lax.dot_general(
                    a8, b8, _NT, preferred_element_type=jnp.int32)


def _init_acc(n_a: int, n_b: int, n_pad: int, variant: str, bm: int,
              bn: int):
    """Accumulator start value: the closed-form K-pad correction."""
    if variant == "fused":
        return jnp.full(
            (bm, bn), n_pad * bipolar.max_value(n_a) * bipolar.max_value(n_b),
            jnp.int32)
    return jnp.full((n_a * n_b, bm, bn), n_pad, jnp.int32)


def _recover_acc(acc_ref, n_a: int, n_b: int, variant: str):
    """Accumulator(s) -> the int32 product ``Y``.  Bitserial: shift-add
    recovery ``Y = sum_ij 2^{i+j} Y^(ij)`` (paper Fig. 2)."""
    if variant == "fused":
        return acc_ref[...]
    y = acc_ref[0]
    for i in range(n_a):
        for j in range(n_b):
            if i or j:
                y = y + (acc_ref[i * n_b + j] << (i + j))
    return y


def _kernel(ap_ref, bp_ref, as_ref, bs_ref, out_ref, acc_ref, *,
            n_a: int, n_b: int, bm: int, bn: int, n_pad: int,
            variant: str, dequant: bool):
    acc_ref[...] = _init_acc(n_a, n_b, n_pad, variant, bm, bn)

    def bit_step(b, carry):
        apl = _bits(ap_ref[...], b)
        a_u = apl[0]                    # planes -> unsigned bit field
        for i in range(1, n_a):
            a_u = a_u + (apl[i] << i)
        _accumulate(a_u, _bits(bp_ref[...], b), acc_ref, n_a=n_a, n_b=n_b,
                    variant=variant)
        return carry

    jax.lax.fori_loop(0, bipolar.PACK_WIDTH, bit_step, 0)
    y = _recover_acc(acc_ref, n_a, n_b, variant)
    if dequant:
        yf = y.astype(jnp.float32) * as_ref[...] * bs_ref[...]
        out_ref[...] = yf.astype(out_ref.dtype)
    else:
        out_ref[...] = y


# ---------------------------------------------------------------------------
# One-kernel quantized linear: quantize-pack prologue + epilogue in VMEM
# ---------------------------------------------------------------------------

def _quantize_tile(x, s, n_a: int, col, k_orig: int):
    """Float tile + per-row scale ``(rows, 1)`` -> unsigned bipolar bit
    field (int32) -- the §4.1 quantize + encode performed in VMEM (same
    math as :mod:`repro.kernels.pack`), dividing in f32.  ``col`` holds
    each column's absolute K index; columns at ``>= k_orig`` (K padding)
    are forced to the all-zero-bit value ``-maxv``, matching the
    activation pad-bit-0 convention of the closed-form pad correction."""
    q = bipolar.quantize_values(x.astype(jnp.float32), n_a, s)
    q = jnp.where(col < k_orig, q, -bipolar.max_value(n_a))
    return bipolar.encode(q, n_a)


def _word_base(rows: int, kw: int):
    """``(rows, Kw)`` int32: absolute K index of bit 0 of each word."""
    return bipolar.PACK_WIDTH * jax.lax.broadcasted_iota(
        jnp.int32, (rows, kw), 1)


_apply_act = ref.apply_act


def _fused_linear_kernel(*refs, n_a: int, n_b: int, bm: int, bn: int,
                         kw: int, k_orig: int, n_pad: int, variant: str,
                         act: str, dual: bool, has_bias: bool,
                         has_res: bool):
    it = iter(refs)
    x_ref, as_ref = next(it), next(it)
    bp_ref, bs_ref = next(it), next(it)
    bp2_ref = next(it) if dual else None
    b2s_ref = next(it) if dual else None
    bias_ref = next(it) if has_bias else None
    res_ref = next(it) if has_res else None
    out_ref = next(it)
    accs = list(it)                       # 1 or 2 scratch accumulators

    init = _init_acc(n_a, n_b, n_pad, variant, bm, bn)
    for aref in accs:
        aref[...] = init
    streams = [(bp_ref, accs[0])] + ([(bp2_ref, accs[1])] if dual else [])
    base = _word_base(bm, kw)

    def bit_step(b, carry):
        # prologue: quantize + bit-decompose this bit position's float
        # activation columns in VMEM.  x_ref holds the whole-K row block
        # (index map depends only on i), so the float activations stream
        # from HBM ONCE per M tile -- not once per N tile -- and the
        # quantize recompute is VPU-only
        a_u = _quantize_tile(x_ref[b], as_ref[...], n_a, base + b, k_orig)
        for bref, aref in streams:
            _accumulate(a_u, _bits(bref[...], b), aref, n_a=n_a, n_b=n_b,
                        variant=variant)
        return carry

    jax.lax.fori_loop(0, bipolar.PACK_WIDTH, bit_step, 0)

    # epilogue stages round-trip through out_dtype exactly where the
    # unfused composition casts, so fused == unfused bitwise
    od = out_ref.dtype
    yf = _recover_acc(accs[0], n_a, n_b, variant).astype(jnp.float32) \
        * as_ref[...] * bs_ref[...]
    if has_bias:
        yf = yf + bias_ref[...]
    yo = yf.astype(od)
    if dual:
        y2 = _recover_acc(accs[1], n_a, n_b, variant).astype(jnp.float32) \
            * as_ref[...] * b2s_ref[...]
        h = _apply_act(yo.astype(jnp.float32), act) \
            * y2.astype(od).astype(jnp.float32)
        yo = h.astype(od)
    elif act != "none":
        yo = _apply_act(yo.astype(jnp.float32), act).astype(od)
    if has_res:
        yo = yo + res_ref[...]
    out_ref[...] = yo


def _check_tiles(m: int, n: int, bm: int, bn: int) -> None:
    if m % bm or n % bn:
        raise ValueError(f"({m},{n}) not tiled by ({bm},{bn})")


@functools.partial(
    jax.jit,
    static_argnames=("n_a", "n_b", "k_orig", "variant", "act", "block",
                     "out_dtype", "interpret"))
def apmm_fused_linear(x: jax.Array, a_scale: jax.Array, bp: jax.Array,
                      b_scale, *, bp2=None, b2_scale=None, bias=None,
                      residual=None, n_a: int, n_b: int, k_orig: int,
                      variant: str = "fused", act: str = "none",
                      block: tuple = (DEFAULT_BM, DEFAULT_BN),
                      out_dtype=jnp.float32,
                      interpret: bool = False) -> jax.Array:
    """One-kernel quantized linear ``Y = epilogue(Q(X) @ B^T)``.

    Args:
      x: ``(M, Kw*32)`` float activations (K padded to the packed word
        width; pad columns are masked in-kernel).
      a_scale: ``(M, 1)`` f32 per-row activation scales.
      bp: ``(n_b, N, Kw)`` uint32 packed weight planes (pad bit 1).
      b_scale: ``(N, 1)`` f32 per-output-channel weight scales.
      bp2/b2_scale: optional second weight operand (dual-GEMM gate/up
        mode): the quantized A tile streams against both weights and the
        epilogue writes ``act(Y1) * Y2`` (SwiGLU: Y1 = gate, Y2 = up).
      bias: optional ``(N,)``-broadcastable f32 bias, added to Y1 before
        the out-dtype cast.
      residual: optional ``(M, N)`` tensor (out_dtype) added after the
        activation, in out_dtype arithmetic.
      act: "none" | "silu" | "gelu" epilogue activation.
      k_orig: unpadded reduction length.
      block: ``(bm, bn)`` output tile; K is never tiled (see
        :func:`_bits`).

    Shapes must tile exactly (:mod:`repro.kernels.ops` pads and unpads).
    """
    m, kp = x.shape
    n_b_, n, kw = bp.shape
    assert n_b_ == n_b and kp == kw * bipolar.PACK_WIDTH, (x.shape, bp.shape)
    bm, bn = min(block[0], m), min(block[1], n)
    _check_tiles(m, n, bm, bn)
    dual = bp2 is not None
    if dual:
        assert b2_scale is not None and bp2.shape == bp.shape, \
            (bp.shape, None if bp2 is None else bp2.shape)
    a_scale = a_scale.reshape(m, 1).astype(jnp.float32)
    b_scale = b_scale.reshape(1, n).astype(jnp.float32)

    operands = [_bit_major(x), a_scale, bp, b_scale]
    in_specs = [
        # whole-K row block, re-fetched only when i changes: activations
        # cost M*K*itemsize of HBM traffic total, independent of N/bn
        pl.BlockSpec((bipolar.PACK_WIDTH, bm, kw), lambda i, j: (0, i, 0)),
        pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((n_b, bn, kw), lambda i, j: (0, j, 0)),
        pl.BlockSpec((1, bn), lambda i, j: (0, j)),
    ]
    if dual:
        operands += [bp2, b2_scale.reshape(1, n).astype(jnp.float32)]
        in_specs += [
            pl.BlockSpec((n_b, bn, kw), lambda i, j: (0, j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ]
    if bias is not None:
        operands.append(bias.reshape(1, n).astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
    if residual is not None:
        operands.append(residual.reshape(m, n).astype(out_dtype))
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j: (i, j)))

    acc_shape = ((bm, bn) if variant == "fused" else (n_a * n_b, bm, bn))
    scratch = [pltpu.VMEM(acc_shape, jnp.int32) for _ in range(1 + dual)]
    kernel = functools.partial(
        _fused_linear_kernel, n_a=n_a, n_b=n_b, bm=bm, bn=bn, kw=kw,
        k_orig=k_orig, n_pad=kp - k_orig, variant=variant, act=act,
        dual=dual, has_bias=bias is not None, has_res=residual is not None)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit,
    static_argnames=("n_a", "n_b", "k_orig", "variant", "block",
                     "out_dtype", "interpret"))
def apmm_packed(ap: jax.Array, bp: jax.Array, a_scale, b_scale, *,
                n_a: int, n_b: int, k_orig: int,
                variant: str = "fused",
                block: tuple = (DEFAULT_BM, DEFAULT_BN),
                out_dtype=jnp.float32,
                interpret: bool = False) -> jax.Array:
    """Packed-layout arbitrary-precision NT GEMM: ``Y = A @ B^T``.

    Args:
      ap: ``(n_a, M, Kw)`` uint32 packed A planes (pad bit 0).
      bp: ``(n_b, N, Kw)`` uint32 packed B planes (pad bit 1).
      a_scale: ``(M, 1)`` f32 per-row scales, or None (with b_scale=None)
        for a raw int32 output.
      b_scale: ``(N, 1)`` f32 per-row (output-channel) scales.
      k_orig: unpadded reduction length (pad columns are corrected in
        closed form).
      variant: "fused" | "bitserial" (see module docstring).
      block: ``(bm, bn)`` output tile; K is never tiled (see
        :func:`_bits`).

    Shapes must tile exactly: ``M % bm == N % bn == 0`` (the
    :mod:`repro.kernels.ops` wrapper pads and unpads).
    """
    n_a_, m, kw = ap.shape
    n_b_, n, kw2 = bp.shape
    assert (n_a_, n_b_) == (n_a, n_b) and kw == kw2, (ap.shape, bp.shape)
    bm, bn = min(block[0], m), min(block[1], n)
    _check_tiles(m, n, bm, bn)
    kp = kw * bipolar.PACK_WIDTH
    dequant = a_scale is not None
    if dequant:
        assert b_scale is not None
        a_scale = a_scale.reshape(m, 1).astype(jnp.float32)
        b_scale = b_scale.reshape(1, n).astype(jnp.float32)
    else:
        out_dtype = jnp.int32
        # dummy 1-element scale operands keep a single kernel signature
        a_scale = jnp.ones((m, 1), jnp.float32)
        b_scale = jnp.ones((1, n), jnp.float32)

    acc_shape = ((bm, bn) if variant == "fused" else (n_a * n_b, bm, bn))
    kernel = functools.partial(
        _kernel, n_a=n_a, n_b=n_b, bm=bm, bn=bn,
        n_pad=kp - k_orig, variant=variant, dequant=dequant)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((n_a, bm, kw), lambda i, j: (0, i, 0)),
            pl.BlockSpec((n_b, bn, kw), lambda i, j: (0, j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM(acc_shape, jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(ap, bp, a_scale, b_scale)
