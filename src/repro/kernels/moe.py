"""Grouped bipolar-INT MoE expert GEMM Pallas TPU kernel.

Runs the expert linear of a routed step as ONE kernel launch over a grid
of *expert tiles*: the dispatched rows ``(R, K)`` are cut into ``T = R /
bc`` tiles of ``bc`` rows, every tile belongs to one expert, and the
tile's rows are multiplied with that expert's packed weight ``(N, K)``.
Both dispatch layouts of ``layers.moe_apply`` are such tiles:

* the dropless serving dispatch sorts live assignments by expert and
  pads each expert's run to whole tiles (a ragged layout: only tiles
  that hold a live row are computed);
* the capacity dispatch holds ``E * G`` fixed segments, each cut into
  tiles (``ops.ap_moe_expert_linear``).

Three ideas compose:

* **Scalar-prefetch routing** -- each tile's live-row count, the tile
  whose activations and the expert whose weights it reads ride scalar
  prefetch (the mechanism ``flash_attention_paged_quantized`` uses for
  block tables): the index maps pick the expert's weight block per
  tile, and the body decides per tile whether it has work.
* **Dead tiles cost no HBM traffic** -- a tile with no live row skips
  the quantize prologue and every MXU pass (``pl.when``), writes
  zeros, and its index maps repeat the blocks of the last live tile
  before it, so the pipeline fetches nothing for it.  Consecutive
  tiles of one expert read the same weight block, which is therefore
  fetched once per expert when the whole output width fits one block.
* **Fused-APMM prologue/epilogue** -- the dispatched float activations
  are quantized + bit-decomposed in the kernel's VMEM prologue (packed
  activation planes never exist in HBM) and the packed expert weights
  are recovered tile-locally, mirroring
  :func:`repro.kernels.apmm.apmm_fused_linear` -- including its dual
  gate/up mode streaming one quantized A tile against both expert
  weights with the ``act(Y1) * Y2`` epilogue.

Numeric contract (checked bit-for-bit in tests/kernels/test_moe_expert.py):
activation quantization runs in **f32** from the materialized input --
scale and division exactly as ``layers._expert_quantize`` -- and the
epilogue dequantizes and (in dual mode) composes ``act(Y1) * Y2`` in
f32 with ONE cast to the output dtype, so live rows are bit-identical
to the legacy batched path.  Single-rounding f32 chains are the
load-bearing choice: a native-bf16 chain changes bits under XLA's
excess-precision convert elision depending on the surrounding jit
graph.  Rows at or beyond a tile's live count are exactly zero.

Like the dense APMM kernels, this kernel is width-agnostic: nested-
precision serving slices the ``(n_bits, E, N, Kw)`` packed expert
weights to their leading ``k`` planes in ``ops`` (``w_bits=k``), so the
kernel streams only the served planes from HBM.

With ``stats=True`` a second output, the ``(T,)`` int32 live-tile map in
SMEM, records which tiles did work: the interpret-mode proof of the skip
path.  Serving calls leave it out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bipolar
from repro.kernels import ref
from repro.kernels.apmm import (VMEM_LIMIT_BYTES, _accumulate, _bit_major,
                                _bits, _init_acc, _quantize_tile,
                                _recover_acc, _word_base)

# double-buffered weight blocks of one launch (both operands in dual
# mode) stay under this, so a whole expert row block fits at DeepSeek-
# MoE's widths and wider experts split their output columns
WEIGHT_BLOCK_BYTES = 32 * 2**20


def col_block(n: int, kw: int, n_b: int, dual: bool) -> int:
    """Output columns per block for an ``N = n`` expert weight of ``kw``
    packed words a row: the widest multiple of 128 dividing ``n`` whose
    double-buffered planes (lanes padded to 128) fit
    ``WEIGHT_BLOCK_BYTES``; 128 when none does."""
    per_col = n_b * (-(-kw // 128) * 128) * 4 * (1 + dual) * 2
    fit = [b for b in range(128, n + 1, 128)
           if n % b == 0 and b * per_col <= WEIGHT_BLOCK_BYTES]
    return max(fit, default=128)


def _moe_kernel(rows_ref, src_ref, exp_ref, *refs, n_a: int, n_b: int,
                bc: int, bn: int, kw: int, k_orig: int, n_pad: int,
                variant: str, act: str, dual: bool, stats: bool):
    it = iter(refs)
    x_ref, as_ref = next(it), next(it)
    wp_ref, ws_ref = next(it), next(it)
    wp2_ref = next(it) if dual else None
    w2s_ref = next(it) if dual else None
    out_ref = next(it)
    live_ref = next(it) if stats else None
    accs = list(it)                       # 1 or 2 scratch accumulators

    cnt = rows_ref[pl.program_id(0)]      # live rows of this tile
    live = cnt > 0
    if stats:
        live_ref[pl.program_id(0)] = live.astype(jnp.int32)

    @pl.when(live)
    def _compute():
        init = _init_acc(n_a, n_b, n_pad, variant, bc, bn)
        for aref in accs:
            aref[...] = init
        streams = [(wp_ref, accs[0])] \
            + ([(wp2_ref, accs[1])] if dual else [])
        base = _word_base(bc, kw)

        def bit_step(b, carry):
            # prologue: quantize + bit-decompose this bit position's
            # float columns in VMEM.  x_ref holds the tile's whole-K row
            # block, fetched once per tile
            a_u = _quantize_tile(x_ref[b], as_ref[...], n_a, base + b,
                                 k_orig)
            for bref, aref in streams:
                _accumulate(a_u, _bits(bref[:, 0], b), aref, n_a=n_a,
                            n_b=n_b, variant=variant)
            return carry

        jax.lax.fori_loop(0, bipolar.PACK_WIDTH, bit_step, 0)

        # dequant + epilogue in f32 with ONE output-dtype cast -- the
        # same cast point as the legacy f32 composition in moe_apply
        od = out_ref.dtype
        a_s = as_ref[...]                                 # (bc, 1) f32
        yf = _recover_acc(accs[0], n_a, n_b, variant).astype(jnp.float32) \
            * a_s * ws_ref[0]
        if dual:
            y2 = _recover_acc(accs[1], n_a, n_b, variant).astype(
                jnp.float32) * a_s * w2s_ref[0]
            yf = ref.apply_act(yf, act) * y2
        elif act != "none":
            yf = ref.apply_act(yf, act)
        yo = yf.astype(od)
        row = jax.lax.broadcasted_iota(jnp.int32, (bc, bn), 0)
        out_ref[...] = jnp.where(row < cnt, yo, jnp.zeros((), od))

    @pl.when(jnp.logical_not(live))
    def _skip():
        out_ref[...] = jnp.zeros((bc, bn), out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_a", "n_b", "k_orig", "variant", "act", "block",
                     "out_dtype", "stats", "interpret"))
def moe_expert_linear(x: jax.Array, a_scale: jax.Array,
                      tile_rows: jax.Array, tile_expert: jax.Array,
                      wp: jax.Array, w_scale: jax.Array, *, wp2=None,
                      w2_scale=None, n_a: int, n_b: int, k_orig: int,
                      variant: str = "fused", act: str = "none",
                      block: tuple = (8, 128), out_dtype=jnp.bfloat16,
                      stats: bool = False, interpret: bool = False):
    """Grouped quantized expert GEMM over expert tiles, one launch.

    Args:
      x: ``(R, Kw*32)`` float dispatched rows, ``R = T * bc`` (K padded
        to the packed word width; pad columns masked in-kernel).
      a_scale: ``(R, 1)`` f32 per-row activation scales (the f32
        quantize chain -- see :func:`_quantize_tile`).
      tile_rows: ``(T,)`` int32 live rows of each tile (a prefix of its
        ``bc`` rows); rows at or beyond it produce exact zeros, and a
        tile with none is skipped.
      tile_expert: ``(T,)`` int32 expert of each tile.
      wp: ``(n_b, E, Np, Kw)`` uint32 packed expert weight planes.
      w_scale: ``(E, 1, Np)`` f32 per-(expert, out-channel) scales.
      wp2/w2_scale: optional second expert weight (dual gate/up mode);
        the epilogue writes ``act(Y1) * Y2``.
      k_orig: unpadded reduction length (closed-form pad correction).
      block: ``(bc, bn)`` rows of a tile and output columns of a block.

    Returns ``y (R, Np)`` in ``out_dtype``; with ``stats`` also the
    ``(T,)`` int32 map of tiles that did MXU work.
    """
    r, kp = x.shape
    n_b_, _, n, kw = wp.shape
    assert n_b_ == n_b and kp == kw * bipolar.PACK_WIDTH, (x.shape, wp.shape)
    bc, bn = block
    if r % bc or n % bn:
        raise ValueError(f"({r},{n}) not tiled by ({bc},{bn})")
    n_t, n_j = r // bc, n // bn
    assert tile_rows.shape == tile_expert.shape == (n_t,), \
        (tile_rows.shape, tile_expert.shape, n_t)
    dual = wp2 is not None
    if dual:
        assert w2_scale is not None and wp2.shape == wp.shape, \
            (wp.shape, None if wp2 is None else wp2.shape)

    # a dead tile reads the blocks of the last live tile before it (the
    # first tile where none is), so the pipeline sees no new block
    ids = jnp.arange(n_t, dtype=jnp.int32)
    src = jnp.maximum(jax.lax.cummax(jnp.where(tile_rows > 0, ids, -1)), 0)
    fetch_expert = tile_expert[src]

    def col(i, j, rows):
        return jnp.where(rows[i] > 0, j, n_j - 1)

    operands = [_bit_major(x), a_scale, wp, w_scale]
    w_spec = pl.BlockSpec(
        (n_b, 1, bn, kw),
        lambda i, j, rows, src, ex: (0, ex[i], col(i, j, rows), 0))
    s_spec = pl.BlockSpec(
        (1, 1, bn), lambda i, j, rows, src, ex: (ex[i], 0, col(i, j, rows)))
    in_specs = [
        pl.BlockSpec((bipolar.PACK_WIDTH, bc, kw),
                     lambda i, j, rows, src, ex: (0, src[i], 0)),
        pl.BlockSpec((bc, 1), lambda i, j, rows, src, ex: (src[i], 0)),
        w_spec, s_spec,
    ]
    if dual:
        operands += [wp2, w2_scale]
        in_specs += [w_spec, s_spec]

    out_specs = [pl.BlockSpec((bc, bn), lambda i, j, rows, src, ex: (i, j))]
    out_shape = [jax.ShapeDtypeStruct((r, n), out_dtype)]
    if stats:
        out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape.append(jax.ShapeDtypeStruct((n_t,), jnp.int32))

    acc_shape = ((bc, bn) if variant == "fused" else (n_a * n_b, bc, bn))
    kernel = functools.partial(
        _moe_kernel, n_a=n_a, n_b=n_b, bc=bc, bn=bn, kw=kw, k_orig=k_orig,
        n_pad=kp - k_orig, variant=variant, act=act, dual=dual, stats=stats)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_t, n_j),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM(acc_shape, jnp.int32)
                        for _ in range(1 + dual)],
    )
    # the live map is one SMEM array written by every step: keep the
    # grid on one core when it is asked for
    sem = "arbitrary" if stats else "parallel"
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(sem, "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_expert_linear",
    )(tile_rows.astype(jnp.int32), src, fetch_expert, *operands)
    return tuple(out) if stats else out[0]
