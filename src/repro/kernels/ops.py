"""Dispatch layer for the APMM kernels.

Every quantized op runs under one of three interchangeable implementations:

* ``"pallas"``    -- the real Pallas TPU kernels (Mosaic), for TPU targets.
* ``"interpret"`` -- the same Pallas kernels under ``interpret=True``
  (kernel body executed in Python on CPU) -- used by the correctness suite.
* ``"reference"`` -- pure-jnp dataflow (:mod:`repro.kernels.ref`) operating
  on the *same packed buffers*; used inside jitted model graphs on CPU and
  in the 512-device dry-run, where a Mosaic kernel cannot lower.

The default comes from ``$REPRO_KERNEL_IMPL`` or the JAX backend
(``pallas`` on TPU, ``reference`` elsewhere).

Dispatch contract: all three impls consume the *same packed buffers* and
compute the same function -- bit-exactly for the integer GEMM cores,
to float tolerance for dequantizing ops (kv attention) -- enforced by
tests/kernels/test_parity.py.  Ops covered:

* ``quantize_rows`` / ``pack_weight`` -- §4.1 quantize + bit-plane pack;
* ``ap_matmul`` -- packed-x-packed NT GEMM (operands packed to different
  K word-widths are padded to the common width, pad bit 0/1);
* ``ap_linear`` -- unfused quantized linear: a standalone quantize-pack
  launch writes the activation planes to HBM, then ``ap_matmul`` reads
  them back (kept as the fused path's bit-exactness oracle/baseline);
* ``ap_linear_fused`` -- ONE-kernel quantized linear: activation
  quantize + decompose run in the GEMM kernel's VMEM prologue (packed
  activation planes never exist in HBM) and a fused epilogue applies
  ``bias``, ``act in {none, silu, gelu}``, an optional residual add and
  a dual-GEMM gate/up mode (``w2``: SwiGLU's two projections share one
  A-tile stream, ``act(x@w1^T) * (x@w2^T)``).  Bit-identical to the
  composed unfused pipeline (tests/kernels/test_fused_linear.py);
* ``ap_moe_tiles_linear`` -- grouped MoE expert linear over expert
  tiles: ONE launch for all experts over a grid of ``bc``-row tiles,
  each tile's live-row count and expert riding scalar prefetch,
  ``pl.when`` skipping tiles that hold no routed token (and fetching
  nothing for them), and the fused quantize prologue / dequant epilogue
  of ``ap_linear_fused`` batched per expert (dual gate/up mode
  included).  ``ap_moe_expert_linear`` runs the capacity dispatch
  ``(E, C, K) x (E, N, K) -> (E, C, N)`` through it.  Live rows are
  bit-identical to ``layers._expert_matmul``; dead rows are exact
  zeros.  ``with_stats=True`` additionally returns the kernel's
  live-tile map (the interpret-mode skip proof);
* the bipolar KV-cache path ``quantize_kv`` / ``dequantize_kv`` /
  ``kv_cache_attention`` (dequant-on-read flash attention) /
  ``paged_kv_cache_attention`` (same, reading K/V through a serving
  block table; with ``window`` set the kernel masks by absolute
  position and skips blocks no query may see -- null-padded table
  entries and fully-out-of-window blocks -- matching the scheduler's
  rolling-table out-of-window reclaim.
  tests/kernels/test_paged_attention.py covers the window boundaries).
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bipolar
from repro.core.bipolar import BipolarTensor
from repro.kernels import apmm as apmm_kernel
from repro.kernels import flash_attention as flash_kernel
from repro.kernels import moe as moe_kernel
from repro.kernels import pack as pack_kernel
from repro.kernels import ref

_IMPLS = ("pallas", "interpret", "reference")
_impl_override = None


def default_impl() -> str:
    if _impl_override is not None:
        return _impl_override
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env:
        if env not in _IMPLS:
            raise ValueError(
                f"REPRO_KERNEL_IMPL={env!r}: expected one of {_IMPLS}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def set_impl(impl) -> None:
    """Override the global kernel implementation (None = auto)."""
    global _impl_override
    assert impl is None or impl in _IMPLS, impl
    _impl_override = impl


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_dim(arr: jax.Array, axis: int, target: int, value=0) -> jax.Array:
    pad = target - arr.shape[axis]
    if pad <= 0:
        return arr
    cfg = [(0, 0)] * arr.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(arr, cfg, constant_values=np.asarray(value, arr.dtype))


# ---------------------------------------------------------------------------
# Quantize + pack
# ---------------------------------------------------------------------------

def quantize_rows(x: jax.Array, n_bits: int, *, pad_bit: int,
                  impl: str | None = None,
                  scale: jax.Array | None = None,
                  scale_search: bool = False) -> BipolarTensor:
    """Quantize a row-major ``(R, K)`` matrix to packed bipolar planes.

    Per-row absmax scales (``scale_search=True``: per-row MSE clip search,
    :func:`bipolar.mse_scale` -- weight preprocessing only); K padded to
    the 32-bit word boundary with the given pad bit (0 for
    activations/LHS, 1 for weights/RHS).

    ``scale_search=True`` additionally fits the per-width nested scales
    (:func:`bipolar.nested_width_scales`) so every plane prefix of the
    result is directly servable via :func:`bipolar.nested_slice` -- the
    any-precision checkpoint contract (offline cost only, like the clip
    search itself).
    """
    impl = impl or default_impl()
    r, k = x.shape
    if scale is None and scale_search:
        scale = bipolar.mse_scale(x, n_bits, axis=-1)
    if scale is None:
        scale = bipolar.absmax_scale(x, n_bits, axis=-1, keepdims=True)
    scale = scale.astype(jnp.float32).reshape(r, 1)
    width_scales = None
    if scale_search and n_bits > 1:
        qv = bipolar.quantize_values(x, n_bits, scale)
        width_scales = bipolar.nested_width_scales(x, qv, n_bits, scale)
    if impl == "reference":
        q = bipolar.quantize_values(x, n_bits, scale)
        packed = bipolar.pack_values(q, n_bits, -1, pad_bit)
    else:
        kp = _round_up(k, bipolar.PACK_WIDTH)
        maxv = bipolar.max_value(n_bits)
        pad_val = scale * (maxv if pad_bit else -maxv)   # all-1/all-0 bits
        xp = _pad_dim(x.astype(jnp.float32), 1, kp)
        if kp > k:
            xp = xp.at[:, k:].set(jnp.broadcast_to(pad_val, (r, kp - k)))
        # row tiling: pad rows to the block multiple, slice planes after
        br = min(pack_kernel.DEFAULT_BR, _round_up(r, 8))
        rp = _round_up(r, br)
        xp = _pad_dim(xp, 0, rp, 1.0)
        sp = _pad_dim(scale, 0, rp, 1.0)
        bk = next(b for b in (1024, 512, 256, 128, 64, 32) if kp % b == 0)
        packed = pack_kernel.quantize_pack_rows(
            xp, sp, n_bits=n_bits, block=(br, bk),
            interpret=(impl == "interpret"))[:, :r, :]
    return BipolarTensor(packed=packed, scale=scale, n_bits=n_bits,
                         shape=(r, k), pack_axis=1,
                         width_scales=width_scales)


# ---------------------------------------------------------------------------
# Arbitrary-precision GEMM
# ---------------------------------------------------------------------------

def _normalize_packed_kw(a: BipolarTensor,
                         b: BipolarTensor) -> tuple:
    """Pad operands packed to different K word-widths to the common one.

    Both describe the same logical K; a weight preprocessed offline may
    carry extra alignment words.  A pads with all-zero bits (-1s), B
    with all-one bits (+1s) -- the pad conventions the closed-form
    K-pad correction already accounts for, so the product is unchanged.
    """
    assert a.shape[-1] == b.shape[-1], \
        f"reduction dims differ: {a.shape} vs {b.shape}"
    kw = max(a.packed.shape[-1], b.packed.shape[-1])
    if a.packed.shape[-1] < kw:
        a = dataclasses.replace(
            a, packed=_pad_dim(a.packed, a.packed.ndim - 1, kw, 0))
    if b.packed.shape[-1] < kw:
        b = dataclasses.replace(
            b, packed=_pad_dim(b.packed, b.packed.ndim - 1, kw, 0xFFFFFFFF))
    return a, b


def ap_matmul(a: BipolarTensor, b: BipolarTensor, *,
              variant: str = "fused", impl: str | None = None,
              out_dtype=jnp.float32, raw: bool = False,
              b_bits: int | None = None) -> jax.Array:
    """NT GEMM of packed tensors: ``Y (M,N) = A (M,K) @ B (N,K)^T``.

    ``raw=True`` returns the exact int32 product of the bipolar integer
    values (no scale dequant).  ``b_bits`` serves a nested B operand at
    a lower width: only the leading ``b_bits`` plane rows of the packed
    buffer are shipped to the kernel (:func:`bipolar.nested_slice` --
    HBM weight traffic scales with the served width, and the reference
    impl slices the same buffers inside the jitted graph).
    """
    impl = impl or default_impl()
    if b_bits is not None:
        b = bipolar.nested_slice(b, b_bits)
    a, b = _normalize_packed_kw(a, b)
    if impl == "reference":
        if raw:
            return ref.apmm_packed_ref(a, b, fused=(variant == "fused"))
        return ref.apmm_dequant_ref(a, b, fused=(variant == "fused"),
                                    out_dtype=out_dtype)
    (m, k), (n, _) = a.shape, b.shape
    # --- pad M/N to tile multiples (K is never tiled) --------------------
    bm = min(apmm_kernel.DEFAULT_BM, _round_up(m, 8))
    bn = min(apmm_kernel.DEFAULT_BN, _round_up(n, 128))
    mp, np_ = _round_up(m, bm), _round_up(n, bn)
    ap = _pad_dim(a.packed, 1, mp)
    bp = _pad_dim(b.packed, 1, np_)
    a_scale = None if raw else _pad_dim(a.scale.reshape(m, 1), 0, mp, 1.0)
    b_scale = None if raw else _pad_dim(b.scale.reshape(n, 1), 0, np_, 1.0)
    y = apmm_kernel.apmm_packed(
        ap, bp, a_scale, b_scale, n_a=a.n_bits, n_b=b.n_bits, k_orig=k,
        variant=variant, block=(bm, bn), out_dtype=out_dtype,
        interpret=(impl == "interpret"))
    return y[:m, :n]


def ap_linear(x: jax.Array, w: BipolarTensor, *, a_bits: int,
              variant: str = "fused", impl: str | None = None,
              out_dtype=None, w_bits: int | None = None) -> jax.Array:
    """Quantized linear: ``y (..., N) = x (..., K) @ W (N, K)^T``.

    Activations are quantized on the fly (per-token absmax, the paper's
    runtime preprocessing path); weights arrive pre-packed.  ``w_bits``
    serves a nested weight at a lower width (plane-prefix slice, see
    :func:`ap_matmul`).
    """
    impl = impl or default_impl()
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    k = x.shape[-1]
    xq = quantize_rows(x.reshape(-1, k), a_bits, pad_bit=0, impl=impl)
    y = ap_matmul(xq, w, variant=variant, impl=impl, out_dtype=out_dtype,
                  b_bits=w_bits)
    return y.reshape(*lead, w.shape[0])


def ap_linear_fused(x: jax.Array, w: BipolarTensor, *, a_bits: int,
                    w2: BipolarTensor | None = None,
                    bias: jax.Array | None = None,
                    act: str = "none",
                    residual: jax.Array | None = None,
                    variant: str = "fused", impl: str | None = None,
                    out_dtype=None, w_bits: int | None = None) -> jax.Array:
    """One-kernel quantized linear with a fused epilogue (paper §4.2
    taken to its conclusion: preprocessing AND recovery in fast memory).

    ``y (..., N) = epi(x (..., K) @ W (N, K)^T)`` where the epilogue is
    ``act(y + bias) [* (x @ W2^T) if w2] [+ residual]``:

    * activation quantize + bit-decompose run inside the GEMM kernel's
      VMEM prologue -- packed activation planes never round-trip HBM and
      one linear is ONE kernel launch instead of two;
    * ``w2`` (dual-GEMM gate/up mode) streams the quantized A tile
      against a second weight and the epilogue computes
      ``act(y1) * y2`` -- SwiGLU's two projections share one A stream;
    * ``bias`` adds in f32 before the out-dtype cast; ``act`` and the
      out-dtype cast points mirror the unfused composition exactly, and
      ``residual`` adds in out_dtype -- so the fused path is
      *bit-identical* to ``ap_linear`` + jnp epilogue (greedy decode is
      token-identical by construction).

    Dispatch: pallas | interpret run
    :func:`repro.kernels.apmm.apmm_fused_linear`; reference runs
    :func:`repro.kernels.ref.ap_linear_fused_ref` (quantize to values,
    integer GEMM, same epilogue -- no packed activation buffer in the
    graph at all).

    ``w_bits`` serves nested weights at a lower width: both GEMM
    operands (``w`` and ``w2``) are plane-prefix sliced up front
    (:func:`bipolar.nested_slice`), so the pallas/interpret kernel
    physically streams only ``w_bits`` planes from HBM and the
    reference impl slices the same packed buffers in-graph.
    """
    impl = impl or default_impl()
    out_dtype = out_dtype or x.dtype
    if w_bits is not None:
        w = bipolar.nested_slice(w, w_bits)
        if w2 is not None:
            w2 = bipolar.nested_slice(w2, w_bits)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[0]
    assert w.shape[-1] == k, (x.shape, w.shape)
    if w2 is not None:
        assert w2.shape == w.shape and w2.n_bits == w.n_bits, \
            (w.shape, w2.shape)
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    res2 = residual.reshape(m, n) if residual is not None else None
    # scale computed exactly as quantize_rows does (absmax in the INPUT
    # dtype, then cast) -- a f32-side absmax would differ in the last
    # bit for bf16 activations and break fused==unfused bit-identity
    scale = bipolar.absmax_scale(x2, a_bits, axis=-1, keepdims=True)
    scale = scale.astype(jnp.float32)
    if impl == "reference":
        # the residual adds AFTER the reshape, at the exact graph
        # position the unfused model-level add occupies: XLA-CPU elides
        # bf16 rounding differently across fusion boundaries, so a
        # structurally different add site can flip near-tie argmax even
        # though the arithmetic is identical (the pallas/interpret
        # kernels add in-kernel, where the rounding is explicit)
        y = ref.ap_linear_fused_ref(
            x2, scale, w, w2=w2, bias=bias, residual=None, a_bits=a_bits,
            variant=variant, act=act, out_dtype=out_dtype)
        y = y.reshape(*lead, n)
        if residual is not None:
            y = y + residual.astype(out_dtype)
        return y
    # --- pad to tile multiples (kernel masks the K pad in-prologue) -----
    wp = w.packed
    w2p = w2.packed if w2 is not None else None
    kw = max(bipolar.packed_words(k), wp.shape[-1],
             w2p.shape[-1] if w2p is not None else 0)
    bm = min(apmm_kernel.DEFAULT_BM, _round_up(m, 8))
    bn = min(apmm_kernel.DEFAULT_BN, _round_up(n, 128))
    mp, np_ = _round_up(m, bm), _round_up(n, bn)
    xp = _pad_dim(_pad_dim(x2, 1, kw * bipolar.PACK_WIDTH), 0, mp)
    sp = _pad_dim(scale, 0, mp, 1.0)
    wp = _pad_dim(_pad_dim(wp, 1, np_), 2, kw, 0xFFFFFFFF)
    ws = _pad_dim(w.scale.reshape(n, 1), 0, np_, 1.0)
    kw_args: dict = {}
    if w2p is not None:
        kw_args["bp2"] = _pad_dim(_pad_dim(w2p, 1, np_), 2, kw, 0xFFFFFFFF)
        kw_args["b2_scale"] = _pad_dim(w2.scale.reshape(n, 1), 0, np_, 1.0)
    if bias is not None:
        kw_args["bias"] = _pad_dim(
            bias.reshape(n, 1).astype(jnp.float32), 0, np_)
    if res2 is not None:
        kw_args["residual"] = _pad_dim(
            _pad_dim(res2.astype(out_dtype), 1, np_), 0, mp)
    y = apmm_kernel.apmm_fused_linear(
        xp, sp, wp, ws, n_a=a_bits, n_b=w.n_bits, k_orig=k,
        variant=variant, act=act, block=(bm, bn), out_dtype=out_dtype,
        interpret=(impl == "interpret"), **kw_args)
    return y[:m, :n].reshape(*lead, n)


def ap_moe_expert_linear(x: jax.Array, w: BipolarTensor, *,
                         counts: jax.Array, a_bits: int,
                         w2: BipolarTensor | None = None,
                         act: str = "none", variant: str = "fused",
                         impl: str | None = None, out_dtype=None,
                         with_stats: bool = False,
                         w_bits: int | None = None):
    """Grouped quantized MoE expert linear over a capacity dispatch.

    ``y (E, C, N) = epi(Q(x) (E, C, K) @ W (E, N, K)^T)`` where ``C =
    G * seg`` capacity rows per expert hold ``G`` dispatch-group
    segments whose live tokens form a prefix of length ``counts[e, g]``
    (``counts (E, G)`` int32, the one-hot-cumsum keep counts of
    ``moe_apply``'s capacity dispatch).  Per segment:

    * rows ``< counts[e, g]`` are **bit-identical** to the legacy
      batched ``layers._expert_matmul`` path -- activations are
      quantized per row in f32 from the materialized input (the
      single-rounding chain of ``_expert_quantize``) and the epilogue
      composes in f32 with one cast at the output write, matching the
      legacy composition's cast point.  The op pins its operand and
      result materialization (``lax.optimization_barrier`` on the
      reference dataflow; the pallas call boundary pins physically), so
      the bit pattern cannot drift with the surrounding jit graph;
    * rows ``>= counts[e, g]`` are **exact zeros** in every impl (the
      legacy path leaves tiny eps-scale values in dead capacity rows;
      the combine gather reads neither, so rewiring is token-identical).

    ``w2`` enables the dual gate/up mode: one quantized A-tile stream
    against both expert weights, ``act(Y1) * Y2`` fused before the
    output write (SwiGLU: w = gate, w2 = up -- the convention of
    ``mlp_apply``).  The pallas/interpret impls cut each segment into
    row tiles and run :func:`ap_moe_tiles_linear`: tiles holding no live
    token are skipped.  ``with_stats=True`` additionally returns the
    ``(E*G, n_row_tiles)`` int32 live-tile map (kernel-reported for
    pallas/interpret, analytic for reference -- the interpret parity
    test asserting they agree is the skip-path proof).

    ``w_bits`` serves nested expert weights at a lower width (leading
    plane-prefix slice of the ``(n_bits, E, N, Kw)`` packed buffers,
    see :func:`ap_linear_fused`).
    """
    impl = impl or default_impl()
    out_dtype = out_dtype or x.dtype
    e, c, k = x.shape
    g = counts.shape[1]
    assert c % g == 0, (c, g)
    seg = c // g
    counts = counts.astype(jnp.int32)
    # tile geometry shared by all impls so the live map is comparable
    bc = moe_segment_tile_rows(seg)
    n_ci = _round_up(seg, bc) // bc
    if impl == "reference":
        if w_bits is not None:
            w = bipolar.nested_slice(w, w_bits)
            if w2 is not None:
                w2 = bipolar.nested_slice(w2, w_bits)
        assert w.shape == (e, w.shape[1], k), (x.shape, w.shape)
        x, a_scale = _moe_operand(x, a_bits)
        # result barrier = the kernel's HBM write: downstream consumers
        # (the next GEMM's quantizer, the combine) see materialized
        # out_dtype bits, never the fused f32 intermediate
        y = jax.lax.optimization_barrier(ref.ap_moe_expert_linear_ref(
            x, a_scale, counts, w, w2=w2, a_bits=a_bits, variant=variant,
            act=act, out_dtype=out_dtype))
        if with_stats:
            live = (counts.reshape(e * g, 1)
                    > jnp.arange(n_ci, dtype=jnp.int32)[None, :] * bc)
            return y, live.astype(jnp.int32)
        return y
    # each (expert, group) segment padded to whole tiles of bc rows
    cp = n_ci * bc
    xt = _pad_dim(x.reshape(e * g, seg, k), 1, cp).reshape(e * g * cp, k)
    rows = jnp.clip(counts.reshape(e * g, 1)
                    - jnp.arange(n_ci, dtype=jnp.int32)[None, :] * bc,
                    0, bc).reshape(-1)
    experts = jnp.repeat(jnp.arange(e, dtype=jnp.int32), g * n_ci)
    out = ap_moe_tiles_linear(
        xt, w, tile_rows=rows, tile_expert=experts, a_bits=a_bits, w2=w2,
        act=act, variant=variant, impl=impl, out_dtype=out_dtype,
        with_stats=with_stats, w_bits=w_bits)
    y, live = out if with_stats else (out, None)
    y = y.reshape(e * g, cp, -1)[:, :seg].reshape(e, c, -1)
    return (y, live.reshape(e * g, n_ci)) if with_stats else y


def moe_segment_tile_rows(seg: int) -> int:
    """Rows of one kernel tile of a capacity segment of ``seg`` rows."""
    return min(apmm_kernel.DEFAULT_BM, _round_up(seg, 8))


def _moe_operand(x, a_bits: int):
    """Pin the dispatched rows' materialization and take their per-row
    absmax scales in f32 -- exactly ``_expert_quantize``'s chain.  The
    kernel reads ``x`` from HBM in its storage dtype, so the reference
    dataflow must quantize the SAME rounded values: the barrier stops
    XLA from feeding it the pre-cast excess-precision f32 upstream
    value instead."""
    x = jax.lax.optimization_barrier(x)
    return x, bipolar.absmax_scale(x.astype(jnp.float32), a_bits, axis=-1,
                                   keepdims=True)


def ap_moe_tiles_linear(x: jax.Array, w: BipolarTensor, *,
                        tile_rows: jax.Array, tile_expert: jax.Array,
                        a_bits: int, w2: BipolarTensor | None = None,
                        act: str = "none", variant: str = "fused",
                        impl: str | None = None, out_dtype=None,
                        with_stats: bool = False,
                        w_bits: int | None = None):
    """Grouped quantized MoE expert linear over expert tiles.

    ``x (R, K)`` holds ``T = len(tile_rows)`` tiles of ``R / T`` rows;
    tile ``i`` belongs to expert ``tile_expert[i]`` and its live rows
    are its first ``tile_rows[i]``.  Returns ``y (R, N)``: live rows
    ``epi(Q(x_row) @ W_e^T)`` with the numerics of
    :func:`ap_moe_expert_linear`, every other row exactly zero.  The
    pallas/interpret impls run :func:`repro.kernels.moe.moe_expert_linear`,
    which skips dead tiles and fetches no block for them;
    ``with_stats=True`` also returns the ``(T,)`` int32 map of tiles
    that did work (analytic for reference).
    """
    impl = impl or default_impl()
    out_dtype = out_dtype or x.dtype
    if w_bits is not None:
        w = bipolar.nested_slice(w, w_bits)
        if w2 is not None:
            w2 = bipolar.nested_slice(w2, w_bits)
    r, k = x.shape
    n_t = tile_rows.shape[0]
    assert r % n_t == 0, (r, n_t)
    bc = r // n_t
    e, n = w.shape[0], w.shape[1]
    assert w.shape == (e, n, k), (x.shape, w.shape)
    if w2 is not None:
        assert w2.shape == w.shape and w2.n_bits == w.n_bits, \
            (w.shape, w2.shape)
    tile_rows = tile_rows.astype(jnp.int32)
    tile_expert = tile_expert.astype(jnp.int32)
    x, a_scale = _moe_operand(x, a_bits)
    if impl == "reference":
        # each tile as a one-segment "expert" holding its own weights
        y = jax.lax.optimization_barrier(ref.ap_moe_expert_linear_ref(
            x.reshape(n_t, bc, k), a_scale.reshape(n_t, bc, 1),
            tile_rows[:, None], bipolar.take_experts(w, tile_expert),
            w2=None if w2 is None else bipolar.take_experts(w2,
                                                            tile_expert),
            a_bits=a_bits, variant=variant, act=act, out_dtype=out_dtype))
        y = y.reshape(r, n)
        return (y, (tile_rows > 0).astype(jnp.int32)) if with_stats else y
    # --- pad to tile multiples (kernel masks the K pad in-prologue) -----
    wp = w.packed
    w2p = w2.packed if w2 is not None else None
    kw = max(bipolar.packed_words(k), wp.shape[-1],
             w2p.shape[-1] if w2p is not None else 0)
    np_ = _round_up(n, 128)
    bn = moe_kernel.col_block(np_, kw, w.n_bits, w2 is not None)
    xp = _pad_dim(x, 1, kw * bipolar.PACK_WIDTH)
    wp = _pad_dim(_pad_dim(wp, 2, np_), 3, kw, 0xFFFFFFFF)
    ws = _pad_dim(w.scale.reshape(e, 1, n).astype(jnp.float32), 2, np_, 1.0)
    kw_args: dict = {}
    if w2p is not None:
        kw_args["wp2"] = _pad_dim(_pad_dim(w2p, 2, np_), 3, kw, 0xFFFFFFFF)
        kw_args["w2_scale"] = _pad_dim(
            w2.scale.reshape(e, 1, n).astype(jnp.float32), 2, np_, 1.0)
    out = moe_kernel.moe_expert_linear(
        xp, a_scale.astype(jnp.float32), tile_rows, tile_expert, wp, ws,
        n_a=a_bits, n_b=w.n_bits, k_orig=k, variant=variant, act=act,
        block=(bc, bn), out_dtype=out_dtype, stats=with_stats,
        interpret=(impl == "interpret"), **kw_args)
    if with_stats:
        return out[0][:, :n], out[1]
    return out[:, :n]


def pack_weight(w: jax.Array, n_bits: int, *,
                impl: str | None = None) -> BipolarTensor:
    """Offline weight preprocessing (§4.1): ``W (d_out, d_in)`` -> packed,
    with the per-row MSE clip search (cheap: happens once at load)."""
    return quantize_rows(w, n_bits, pad_bit=1, impl=impl, scale_search=True)


# ---------------------------------------------------------------------------
# Bipolar-quantized KV cache (pack on write, dequant on read)
# ---------------------------------------------------------------------------

def fold_kv_heads(a: jax.Array) -> jax.Array:
    """``(B, T, H, ...) -> (BH, T, ...)``: fold the KV-head axis into
    batch, the layout every KV-cache attention kernel consumes (one
    shared definition so the packed-plane layout can change in one
    place)."""
    b, t, h = a.shape[:3]
    return a.transpose((0, 2, 1) + tuple(range(3, a.ndim))).reshape(
        (b * h, t) + a.shape[3:])


def quantize_kv(x: jax.Array, kv_bits: int):
    """K/V tensor ``(..., D)`` -> packed bipolar planes + per-head scales.

    Quantizes along the head dim with a per-(token, head) absmax scale
    (axis -1), decomposes into ``kv_bits`` bit planes and packs D into
    uint32 words (paper §4.1 applied to the KV stream).  Returns
    ``(packed (..., kv_bits, ceil(D/32)) uint32, scale (..., 1) f32)``.
    Pure jnp: the pack is elementwise-cheap next to the projections that
    produce K/V, and runs identically under every impl.
    """
    xf = x.astype(jnp.float32)
    scale = bipolar.absmax_scale(xf, kv_bits, axis=-1, keepdims=True)
    q = bipolar.quantize_values(xf, kv_bits, scale)
    packed = bipolar.pack_values(q, kv_bits, -1, 0)   # (kv_bits, ..., Dw)
    return jnp.moveaxis(packed, 0, -2), scale


def dequantize_kv(packed: jax.Array, scale: jax.Array, d: int,
                  dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_kv`: planes ``(..., n_bits, Dw)`` +
    scale ``(..., 1)`` -> ``(..., D)`` (the ``reference``-impl read path
    and the oracle for the in-kernel recovery)."""
    n_bits = packed.shape[-2]
    planes = jnp.moveaxis(packed, -2, 0)
    vals = bipolar.recover(bipolar.unpack_planes(planes, -1, d), n_bits)
    return (vals.astype(jnp.float32) * scale).astype(dtype)


def kv_cache_attention(q: jax.Array,
                       k_packed: jax.Array, k_scale: jax.Array,
                       v_packed: jax.Array, v_scale: jax.Array,
                       q_pos: jax.Array, kv_pos: jax.Array, *,
                       d: int, causal: bool = True, window=None,
                       impl: str | None = None) -> jax.Array:
    """Attention over a packed bipolar KV cache, folded (BH, ...) layout.

    ``q (BH, Sq, D)``; ``k_packed/v_packed (BH, T, n_bits, Dw)`` uint32;
    ``k_scale/v_scale (BH, T, 1)`` f32; positions int32 with negative
    kv_pos marking invalid slots.  Dispatches pallas | interpret (the
    dequant-on-read flash kernel) | reference (jnp dequant + direct
    softmax) -- all three agree to float tolerance.
    """
    impl = impl or default_impl()
    bh, sq, _ = q.shape
    t = k_packed.shape[1]
    n_bits = k_packed.shape[-2]
    if impl == "reference":
        k = dequantize_kv(k_packed, k_scale, d)
        v = dequantize_kv(v_packed, v_scale, d)
        return flash_kernel.attention_reference(
            q, k, v, q_pos, kv_pos, causal=causal, window=window)
    dp = k_packed.shape[-1] * bipolar.PACK_WIDTH
    # pad q's head dim with zeros to the packed word boundary (pad cols of
    # the recovered K decode to garbage but meet only zeros in q . k)
    qp_arr = _pad_dim(q, 2, dp)
    sqp = _round_up(sq, 8)
    bq = min(flash_kernel.DEFAULT_BQ, sqp)
    sqp = _round_up(sqp, bq)
    bk = min(flash_kernel.DEFAULT_BK, _round_up(t, 32))
    tp = _round_up(t, bk)
    qp_arr = _pad_dim(qp_arr, 1, sqp)
    q_pos_p = _pad_dim(q_pos, 1, sqp)
    kv_pos_p = _pad_dim(kv_pos, 1, tp, -1)      # pad slots are masked out
    kpk = _pad_dim(k_packed, 1, tp)
    vpk = _pad_dim(v_packed, 1, tp)
    ks = _pad_dim(k_scale.reshape(bh, t), 1, tp, 1.0)
    vs = _pad_dim(v_scale.reshape(bh, t), 1, tp, 1.0)
    out = flash_kernel.flash_attention_quantized(
        qp_arr, kpk, ks, vpk, vs, q_pos_p, kv_pos_p,
        d=d, n_bits=n_bits, causal=causal, window=window,
        block=(bq, bk), interpret=(impl == "interpret"))
    return out[:, :sq, :d]


def kv_pool_rows(packed: jax.Array) -> jax.Array:
    """Packed K/V planes ``(..., H, n_bits, Dw)`` -> the paged pool's
    token rows ``(..., n_bits * H * Dw)``, plane-major: word ``(i * H +
    h) * Dw + w`` is word ``w`` of head ``h``'s plane ``i``.

    On a TPU an array's last two dims are stored in (8, 128) tiles, so
    planes shaped ``(n_bits, Dw)`` = (8, 3) would take 43x their size in
    HBM; a pool block ``(bs, n_bits * H * Dw)`` is lane-dense."""
    *lead, h, n_bits, dw = packed.shape
    return jnp.swapaxes(packed, -3, -2).reshape(*lead, n_bits * h * dw)


def kv_pool_planes(rows: jax.Array, h: int, d: int) -> jax.Array:
    """Inverse of :func:`kv_pool_rows` for ``h`` heads of dim ``d``."""
    dw = bipolar.packed_words(d)
    n_bits = rows.shape[-1] // (h * dw)
    return jnp.swapaxes(
        rows.reshape(*rows.shape[:-1], n_bits, h, dw), -3, -2)


def paged_attention_body(n_heads: int, n_kv_heads: int, sq: int,
                         impl: str | None = None) -> str:
    """The body :func:`paged_kv_cache_attention` runs for ``sq`` query
    tokens a lane of a model with ``n_heads`` query heads over
    ``n_kv_heads``: the layer folds a GQA group's ``sq`` tokens into one
    query tile of ``n_heads // n_kv_heads * sq`` rows, and
    :func:`flash_attention.paged_decode_body` picks the kernel body
    (``"decode"`` or ``"tile"``); ``"reference"`` runs no kernel."""
    if (impl or default_impl()) == "reference":
        return "reference"
    rows = n_heads // n_kv_heads * sq
    return "decode" if flash_kernel.paged_decode_body(rows) else "tile"


def paged_kv_cache_attention(q: jax.Array,
                             k_pool: jax.Array, k_scale: jax.Array,
                             v_pool: jax.Array, v_scale: jax.Array,
                             pool_pos: jax.Array, block_tables: jax.Array,
                             q_pos: jax.Array, *,
                             d: int, causal: bool = True, window=None,
                             q_block: int | None = None,
                             impl: str | None = None) -> jax.Array:
    """Attention over a *paged* packed bipolar KV pool via a block table.

    ``q (B, H, Gq, D)`` per-kv-head grouped queries -- ``Gq`` is the
    GQA group size for decode, or ``G * Sq`` with the suffix length
    folded in for block-table suffix prefill (causality is by absolute
    ``q_pos``, so multi-token causal queries need no extra plumbing).
    The pool holds fixed-size token blocks shared by every request:
    ``k_pool/v_pool (n_blocks, bs, n_bits * H * Dw)`` uint32 token rows
    (:func:`kv_pool_rows`), ``k_scale/v_scale (n_blocks, bs, H)`` f32,
    ``pool_pos (n_blocks, bs)`` int32 (-1 = empty slot).
    ``block_tables (B, NB)`` int32 maps each request's logical blocks to
    physical ids; rows pad with 0, the reserved null block whose
    positions stay -1.

    Dispatch: pallas | interpret run the block-table-gathering flash
    kernel (the table is a scalar-prefetch operand indexing the pool
    block specs; decode tiles of at most 8 rows run unpadded through its
    head-batched decode body, wider query axes are tiled by ``q_block``
    rows); reference
    gathers the request's blocks with :func:`repro.kernels.ref.gather_paged_kv`
    and reuses the contiguous :func:`kv_cache_attention` reference path
    on the exact same packed planes.
    """
    impl = impl or default_impl()
    b, h, g, _ = q.shape
    n_bits = k_pool.shape[-1] // (h * bipolar.packed_words(d))
    if impl == "reference":
        gath = partial(ref.gather_paged_kv, block_tables=block_tables)
        kv_pos = gath(pool_pos[:, :, None])[..., 0]
        planes = lambda pool: fold_kv_heads(kv_pool_planes(gath(pool), h, d))
        scales = lambda sc: fold_kv_heads(gath(sc)[..., None])
        o = kv_cache_attention(
            q.reshape(b * h, g, q.shape[-1]),
            planes(k_pool), scales(k_scale), planes(v_pool), scales(v_scale),
            jnp.repeat(q_pos, h, 0), jnp.repeat(kv_pos, h, 0),
            d=d, causal=causal, window=window, impl=impl)
        return o.reshape(b, h, g, d)
    dp = bipolar.packed_words(d) * bipolar.PACK_WIDTH
    if flash_kernel.paged_decode_body(g):
        gp = bq = g                               # decode body: real rows
    else:
        gp = _round_up(g, 8)
        bq = min(q_block or flash_kernel.DEFAULT_PAGED_BQ, gp)
        gp = _round_up(gp, bq)
    qp_arr = _pad_dim(_pad_dim(q, 3, dp), 2, gp)
    q_pos_p = _pad_dim(q_pos, 1, gp, -1)          # pad rows fully masked
    out = flash_kernel.flash_attention_paged_quantized(
        qp_arr, k_pool, k_scale, v_pool, v_scale,
        pool_pos, block_tables, q_pos_p,
        d=d, n_bits=n_bits, causal=causal, window=window, block=bq,
        interpret=(impl == "interpret"))
    return out[:, :, :g, :d]
