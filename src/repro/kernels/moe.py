"""Grouped bipolar-INT MoE expert GEMM Pallas TPU kernel.

Runs the capacity-dispatched expert linear ``(E, C, K) x (E, N, K) ->
(E, C, N)`` as ONE kernel launch over a ``(expert*group, row-tile,
col-tile, k-tile)`` grid, instead of either a per-expert launch loop or
the batched-over-E einsum of ``layers._expert_matmul`` (which unpacks
every expert's bit planes to int32 values in HBM and multiplies all
``E * C`` capacity rows, empty slots included).

Three ideas compose:

* **Scalar-prefetch routing counts** -- the per-(expert, group) live-row
  counts ride scalar prefetch (the same mechanism
  ``flash_attention_paged_quantized`` uses for block tables): they sit in
  SMEM before the grid starts, so the kernel body reads ``counts[eg]``
  and decides per tile whether any of its capacity rows hold a routed
  token.
* **``pl.when`` tile skipping** -- row tiles entirely beyond the live
  prefix skip the quantize prologue and every MXU pass and write zeros.
  Capacity dispatch pads each expert to ``cap`` rows; at decode batch
  sizes almost all of them are empty, so the skipped fraction is the
  decode-path waste the batched einsum silently pays.  (Pallas grid
  skipping elides compute, not the tile DMA.)
* **Fused-APMM prologue/epilogue** -- the dispatched float activations
  are quantized + bit-decomposed in the GEMM kernel's VMEM prologue
  (packed activation planes never exist in HBM) and the packed expert
  weights are recovered tile-locally (unpacked expert weights never
  exist in HBM), mirroring :func:`repro.kernels.apmm.apmm_fused_linear`
  -- including its dual-GEMM gate/up mode streaming one quantized A tile
  against both expert weights with the ``act(Y1) * Y2`` epilogue.

Numeric contract (checked bit-for-bit in tests/kernels/test_moe_expert.py):
activation quantization runs in **f32** from the materialized input --
scale and division exactly as ``layers._expert_quantize`` -- and the
epilogue dequantizes and (in dual mode) composes ``act(Y1) * Y2`` in
f32 with ONE cast to the output dtype, so live rows are bit-identical
to the legacy batched path.  Single-rounding f32 chains are the
load-bearing choice: a native-bf16 chain changes bits under XLA's
excess-precision convert elision depending on the surrounding jit
graph, so "input-dtype division" cannot be made compilation-stable.
Rows at or beyond a group's live count are exactly zero (the legacy
path leaves tiny eps-scale values there; the combine gather never
reads either, which is what keeps the ``moe_apply`` rewire
token-identical).

Like the dense APMM kernels, this kernel is width-agnostic: nested-
precision serving slices the ``(n_bits, E, N, Kw)`` packed expert
weights to their leading ``k`` planes in ``ops.ap_moe_expert_linear``
(``w_bits=k``), so the kernel streams only the served planes from HBM.

A second kernel output, the ``(E*G, n_row_tiles)`` int32 live map,
records which row tiles did work -- the interpret-mode proof of the
skip path and the source of the skipped-tile fraction in
benchmarks/moe_bench.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bipolar
from repro.kernels import ref
from repro.kernels.apmm import (DEFAULT_BM, DEFAULT_BN, VMEM_LIMIT_BYTES,
                                _accumulate, _bit_major, _bits, _init_acc,
                                _quantize_tile, _recover_acc, _word_base)


def _moe_kernel(cnt_ref, *refs, n_a: int, n_b: int, bc: int, bn: int,
                kw: int, k_orig: int, n_pad: int, variant: str, act: str,
                dual: bool):
    it = iter(refs)
    x_ref, as_ref = next(it), next(it)
    wp_ref, ws_ref = next(it), next(it)
    wp2_ref = next(it) if dual else None
    w2s_ref = next(it) if dual else None
    out_ref, live_ref = next(it), next(it)
    accs = list(it)                       # 1 or 2 scratch accumulators

    eg = pl.program_id(0)
    ci = pl.program_id(1)

    # scalar-prefetched live-row count of this (expert, group) segment;
    # the tile is live iff its first capacity row is below the count
    cnt = cnt_ref[eg]
    live = cnt > ci * bc
    live_ref[0, 0] = live.astype(jnp.int32)

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
            # dispatched float columns in VMEM.  x_ref holds the whole-K
            # row block (index map ignores j), so activations stream
            # from HBM once per row tile.
            a_u = _quantize_tile(x_ref[0, b], as_ref[0], n_a, base + b,
                                 k_orig)
            for bref, aref in streams:
                _accumulate(a_u, _bits(bref[:, 0], b), aref, n_a=n_a,
                            n_b=n_b, variant=variant)
            return carry

        jax.lax.fori_loop(0, bipolar.PACK_WIDTH, bit_step, 0)

        # dequant + epilogue in f32 with ONE output-dtype cast -- the
        # same cast point as the legacy f32 composition in moe_apply
        # (bit-identity; intermediate narrowing casts would not be
        # compilation-stable on the jnp side)
        od = out_ref.dtype
        a_s = as_ref[0]                                   # (bc, 1) f32
        yf = _recover_acc(accs[0], n_a, n_b, variant).astype(jnp.float32) \
            * a_s * ws_ref[0]
        if dual:
            y2 = _recover_acc(accs[1], n_a, n_b, variant).astype(
                jnp.float32) * a_s * w2s_ref[0]
            yf = ref.apply_act(yf, act) * y2
        elif act != "none":
            yf = ref.apply_act(yf, act)
        yo = yf.astype(od)
        # rows at/after the live count are exactly zero in every impl
        row = ci * bc + jax.lax.broadcasted_iota(jnp.int32, (bc, bn), 0)
        out_ref[0] = jnp.where(row < cnt, yo, jnp.zeros((), od))

    @pl.when(jnp.logical_not(live))
    def _skip():
        out_ref[0] = jnp.zeros((bc, bn), out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_a", "n_b", "k_orig", "n_groups", "variant", "act",
                     "block", "out_dtype", "interpret"))
def moe_expert_linear(x: jax.Array, a_scale: jax.Array, counts: jax.Array,
                      wp: jax.Array, w_scale: jax.Array, *, wp2=None,
                      w2_scale=None, n_a: int, n_b: int, k_orig: int,
                      n_groups: int, variant: str = "fused",
                      act: str = "none",
                      block: tuple = (DEFAULT_BM, DEFAULT_BN),
                      out_dtype=jnp.bfloat16, interpret: bool = False):
    """Grouped quantized expert GEMM, one launch for all experts.

    Args:
      x: ``(EG, Cp, Kw*32)`` float dispatched activations -- ``EG = E *
        n_groups`` row segments of padded capacity ``Cp``, K padded to
        the packed word width (pad columns masked in-kernel).
      a_scale: ``(EG, Cp, 1)`` f32 per-row activation scales (the f32
        quantize chain -- see :func:`_quantize_tile`).
      counts: ``(EG,)`` int32 live-row counts (scalar-prefetched); rows
        ``>= counts[eg]`` of segment ``eg`` produce exact zeros.
      wp: ``(n_b, E, Np, Kw)`` uint32 packed expert weight planes.
      w_scale: ``(E, 1, Np)`` f32 per-(expert, out-channel) scales.
      wp2/w2_scale: optional second expert weight (dual gate/up mode);
        the epilogue writes ``act(Y1) * Y2``.
      k_orig: unpadded reduction length (closed-form pad correction).

    Returns ``(y, live_map)``: ``y (EG, Cp, Np)`` in ``out_dtype`` and
    ``live_map (EG, Cp // bc)`` int32 marking row tiles that did MXU
    work (0 = skipped by ``pl.when``).

    Shapes must tile exactly (:func:`repro.kernels.ops.ap_moe_expert_linear`
    pads and unpads).
    """
    egs, cp, kp = x.shape
    n_b_, e, n, kw = wp.shape
    assert n_b_ == n_b and kp == kw * bipolar.PACK_WIDTH, (x.shape, wp.shape)
    assert egs == e * n_groups, (egs, e, n_groups)
    bc, bn = min(block[0], cp), min(block[1], n)
    if cp % bc or n % bn:
        raise ValueError(f"({cp},{n}) not tiled by ({bc},{bn})")
    dual = wp2 is not None
    if dual:
        assert w2_scale is not None and wp2.shape == wp.shape, \
            (wp.shape, None if wp2 is None else wp2.shape)
    g = n_groups

    operands = [_bit_major(x), a_scale, wp, w_scale]
    in_specs = [
        # whole-K row block, re-fetched only when (eg, ci) changes
        pl.BlockSpec((1, bipolar.PACK_WIDTH, bc, kw),
                     lambda eg, ci, j, cc: (eg, 0, ci, 0)),
        pl.BlockSpec((1, bc, 1), lambda eg, ci, j, cc: (eg, ci, 0)),
        # expert index = eg // n_groups (groups share their expert's
        # weights; the weight tile is re-fetched only across experts)
        pl.BlockSpec((n_b, 1, bn, kw),
                     lambda eg, ci, j, cc: (0, eg // g, j, 0)),
        pl.BlockSpec((1, 1, bn), lambda eg, ci, j, cc: (eg // g, 0, j)),
    ]
    if dual:
        operands += [wp2, w2_scale]
        in_specs += [
            pl.BlockSpec((n_b, 1, bn, kw),
                         lambda eg, ci, j, cc: (0, eg // g, j, 0)),
            pl.BlockSpec((1, 1, bn),
                         lambda eg, ci, j, cc: (eg // g, 0, j)),
        ]

    acc_shape = ((bc, bn) if variant == "fused" else (n_a * n_b, bc, bn))
    kernel = functools.partial(
        _moe_kernel, n_a=n_a, n_b=n_b, bc=bc, bn=bn, kw=kw, k_orig=k_orig,
        n_pad=kp - k_orig, variant=variant, act=act, dual=dual)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(egs, cp // bc, n // bn),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bc, bn), lambda eg, ci, j, cc: (eg, ci, j)),
            pl.BlockSpec((1, 1), lambda eg, ci, j, cc: (eg, ci)),
        ],
        scratch_shapes=[pltpu.VMEM(acc_shape, jnp.int32)
                        for _ in range(1 + dual)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((egs, cp, n), out_dtype),
            jax.ShapeDtypeStruct((egs, cp // bc), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(counts, *operands)
