"""Flash attention (online softmax) Pallas TPU kernels: float + bipolar KV.

Motivated by the roofline analysis (EXPERIMENTS.md §Perf): prefill cells
of MHA-heavy archs are dominated by materialized (Sq x T) score traffic
-- e.g. minicpm-2b/prefill_32k moves ~26 TiB/chip, ~80% of it score
tensors the jnp dataflow must round-trip through HBM.  This kernel keeps
the score tile in VMEM: HBM traffic drops to Q/K/V/O (+tiny pos masks).

Layout: heads folded into batch -- ``q (BH, Sq, D)``, ``k/v (BH, T, D)``,
``q_pos (BH, Sq)``, ``kv_pos (BH, T)`` int32 (negative kv_pos = invalid
slot, matching the cache convention).  Causal/window masking is by
absolute position, so GQA folding, ring caches and padded prefixes all
work unchanged.

Grid ``(BH, Sq/bq, T/bk)`` with the KV axis innermost ("arbitrary");
scratch: running max/denominator ``(bq, 1)`` and the f32 output
accumulator ``(bq, D)`` -- the classic two-pass-free online softmax.

:func:`flash_attention_quantized` extends this to the bipolar-INT KV
cache (paper §3.1/§4.1 applied to the decode-dominating tensor): K/V
arrive as packed uint32 bit planes ``(BH, T, n_bits, D/32)`` with
per-(token, head) absmax scales, and *recovery happens inside the
kernel* -- HBM moves ``kv_bits`` bits per cache element instead of 16,
and the dequantized tile never round-trips through HBM (the §4.2
"recovery in shared memory" scheduling, on the TPU memory hierarchy).
:func:`attention_reference` is the pure-jnp twin used by the
``reference`` impl of the :mod:`repro.kernels.ops` dispatch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bipolar

DEFAULT_BQ = 512
DEFAULT_BK = 512
# The kernels' products are f32 by design (dequantized K/V tiles, f32
# probabilities); without an explicit precision a TPU's MXU may round f32
# operands to bf16.
_F32 = jax.lax.Precision.HIGHEST


def _online_softmax_update(s, valid, v, m_ref, l_ref, acc_ref):
    """One KV-tile update of the running (max, denom, acc) state.

    Invalid slots are zeroed in ``p`` (not just pushed to -1e30): a row
    whose every slot is masked must end with denominator ~0 so the final
    clamp returns 0, identically across kernel and reference impls.
    """
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32)


def _position_mask(qpos, kpos, causal: bool, window):
    valid = kpos >= 0
    if causal:
        valid &= kpos <= qpos
    if window is not None:
        valid &= kpos > qpos - window
    return valid


def _kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, out_ref,
            m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window, bq: int, bk: int, d: int):
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full((bq, 1), -1e30, jnp.float32)
        l_ref[...] = jnp.zeros((bq, 1), jnp.float32)
        acc_ref[...] = jnp.zeros((bq, d), jnp.float32)

    q = q_ref[0]                                  # (bq, d)
    k = k_ref[0]                                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=_F32,
                            preferred_element_type=jnp.float32) * scale

    qpos = qp_ref[0][:, None]                     # (bq, 1) int32
    kpos = kp_ref[0][None, :]                     # (1, bk)
    valid = _position_mask(qpos, kpos, causal, window)
    s = jnp.where(valid, s, -1e30)
    _online_softmax_update(s, valid, v_ref[0], m_ref, l_ref, acc_ref)

    @pl.when(jk == nk - 1)
    def _done():
        out_ref[0] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-20)).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, kv_pos: jax.Array, *,
                    causal: bool = True, window=None,
                    block: tuple = (DEFAULT_BQ, DEFAULT_BK),
                    interpret: bool = False) -> jax.Array:
    """Online-softmax attention. q (BH,Sq,D), k/v (BH,T,D) -> (BH,Sq,D).

    Shapes must tile exactly (wrapper in ops pads); fully-masked rows
    return 0 (denominator clamp), matching the jnp reference.
    """
    bh, sq, d = q.shape
    t = k.shape[1]
    bq, bk = min(block[0], sq), min(block[1], t)
    if sq % bq or t % bk:
        raise ValueError(f"({sq},{t}) not tiled by ({bq},{bk})")
    kernel = functools.partial(
        _kernel, scale=1.0 / np.sqrt(d), causal=causal, window=window,
        bq=bq, bk=bk, d=d)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),       # q_pos
            pl.BlockSpec((1, bk), lambda b, i, j: (b, j)),       # kv_pos
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),  # v
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_pos, kv_pos, q, k, v)


# ---------------------------------------------------------------------------
# Bipolar-quantized KV cache variant (dequant-on-read in VMEM)
# ---------------------------------------------------------------------------

def _dequant_tile(packed, scale, n_bits: int, bk: int, dp: int):
    """Packed planes (bk, n_bits, dp/32) uint32 + scale (bk, 1) -> f32 tile.

    Bipolar recovery without materializing {-1,+1} planes:
    ``v = (sum_i b_i << (i+1)) - (2^n - 1)`` (see bipolar.recover).
    """
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 1, 32), 3)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(bk, n_bits, dp).astype(jnp.int32)
    acc = bits[:, 0, :] << 1
    for i in range(1, n_bits):
        acc = acc + (bits[:, i, :] << (i + 1))
    vals = acc - bipolar.max_value(n_bits)
    return vals.astype(jnp.float32) * scale


def _kernel_quant(qp_ref, kp_ref, ks_ref, vs_ref, q_ref, kq_ref, vq_ref,
                  out_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window,
                  bq: int, bk: int, dp: int, n_bits: int):
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full((bq, 1), -1e30, jnp.float32)
        l_ref[...] = jnp.zeros((bq, 1), jnp.float32)
        acc_ref[...] = jnp.zeros((bq, dp), jnp.float32)

    # recover K/V tiles from packed bit planes entirely in VMEM; pad
    # columns of D decode to garbage but q is zero-padded there, and pad
    # T slots carry kv_pos=-1 so the position mask removes them.
    k = _dequant_tile(kq_ref[0], ks_ref[0][:, None], n_bits, bk, dp)
    v = _dequant_tile(vq_ref[0], vs_ref[0][:, None], n_bits, bk, dp)

    q = q_ref[0]                                  # (bq, dp), zero pad cols
    s = jax.lax.dot_general(q.astype(jnp.float32), k,
                            (((1,), (1,)), ((), ())), precision=_F32,
                            preferred_element_type=jnp.float32) * scale
    qpos = qp_ref[0][:, None]
    kpos = kp_ref[0][None, :]
    valid = _position_mask(qpos, kpos, causal, window)
    s = jnp.where(valid, s, -1e30)
    _online_softmax_update(s, valid, v, m_ref, l_ref, acc_ref)

    @pl.when(jk == nk - 1)
    def _done():
        out_ref[0] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-20)).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("d", "n_bits", "causal", "window", "block", "interpret"))
def flash_attention_quantized(q: jax.Array,
                              k_packed: jax.Array, k_scale: jax.Array,
                              v_packed: jax.Array, v_scale: jax.Array,
                              q_pos: jax.Array, kv_pos: jax.Array, *,
                              d: int, n_bits: int,
                              causal: bool = True, window=None,
                              block: tuple = (DEFAULT_BQ, DEFAULT_BK),
                              interpret: bool = False) -> jax.Array:
    """Attention over a packed bipolar-INT KV cache, dequant-on-read.

    Args:
      q: ``(BH, Sq, Dp)`` with ``Dp = 32 * ceil(d/32)``; columns past the
        true head dim ``d`` MUST be zero (the ops wrapper pads).
      k_packed/v_packed: ``(BH, T, n_bits, Dp/32)`` uint32 bit planes.
      k_scale/v_scale: ``(BH, T)`` f32 per-(token, head) absmax scales.
      q_pos/kv_pos: ``(BH, Sq)`` / ``(BH, T)`` int32 absolute positions;
        negative kv_pos = invalid slot (also used for T padding).
      d: true head dim (sets the softmax scale).

    Returns ``(BH, Sq, Dp)``; the caller slices ``[..., :d]``.
    """
    bh, sq, dp = q.shape
    t = k_packed.shape[1]
    dw = dp // bipolar.PACK_WIDTH
    assert k_packed.shape == (bh, t, n_bits, dw), (k_packed.shape, q.shape)
    bq, bk = min(block[0], sq), min(block[1], t)
    if sq % bq or t % bk:
        raise ValueError(f"({sq},{t}) not tiled by ({bq},{bk})")
    kernel = functools.partial(
        _kernel_quant, scale=1.0 / np.sqrt(d), causal=causal, window=window,
        bq=bq, bk=bk, dp=dp, n_bits=n_bits)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),        # q_pos
            pl.BlockSpec((1, bk), lambda b, i, j: (b, j)),        # kv_pos
            pl.BlockSpec((1, bk), lambda b, i, j: (b, j)),        # k_scale
            pl.BlockSpec((1, bk), lambda b, i, j: (b, j)),        # v_scale
            pl.BlockSpec((1, bq, dp), lambda b, i, j: (b, i, 0)),  # q
            pl.BlockSpec((1, bk, n_bits, dw),
                         lambda b, i, j: (b, j, 0, 0)),            # k planes
            pl.BlockSpec((1, bk, n_bits, dw),
                         lambda b, i, j: (b, j, 0, 0)),            # v planes
        ],
        out_specs=pl.BlockSpec((1, bq, dp), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_pos, kv_pos, k_scale, v_scale, q, k_packed, v_packed)


# ---------------------------------------------------------------------------
# Paged variant: KV read through a block table (serving block pool)
# ---------------------------------------------------------------------------

DEFAULT_PAGED_BQ = 256   # query rows per tile (suffix prefill can be long)
LANES = 128
# VMEM at StableLM-3B widths (H=32, Dp=96 lane-padded to 128, kv_bits=8,
# bs=16) for a 256-row query tile:
#   q and out blocks   2 * 2 buffers * 32 * 256 * 128 * 2 B  =  8 MiB
#   m, l, acc scratch  3 * 32 * 256 * 128 * 4 B               = 12 MiB
#   unpacked bits      2 * 16 * 8 * 32 * 96 * 4 B             =  3 MiB
# The default scoped limit (16 MiB) is too small; 64 MiB leaves the rest
# of the 128 MiB VMEM to the surrounding XLA program.
PAGED_VMEM_LIMIT_BYTES = 64 * 2**20


def _head_group(h: int, dp: int) -> int:
    """Heads per iteration of the paged kernel's head loop: the fewest
    whose ``Dp`` columns together fill whole 128-lane tiles (4 at Dp=96),
    so every iteration starts at an aligned dynamic lane offset; all
    ``h`` heads in one iteration when they cannot be grouped so."""
    grp = LANES // int(np.gcd(dp, LANES))
    return grp if h % grp == 0 else h


def _head_column(tile, j):
    """Column ``j`` of a ``(rows, H)`` tile as ``(rows, 1)`` -- exact (one
    nonzero term per row), with ``j`` a traced grid index."""
    hit = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) == j
    return jnp.sum(jnp.where(hit, tile, 0.0), axis=1, keepdims=True)


def _dequant_rows(words, n_bits: int, hdp: int):
    """Pool token rows ``(bs, n_bits * H * Dw)`` uint32, plane-major, ->
    every head's recovered integers ``(bs, H * Dp)`` f32 (unscaled).

    Plane ``i`` of all heads is the word range ``[i * H * Dw, (i + 1) *
    H * Dw)``.  The planes are summed while bits still sit on their own
    axis, so one reshape (half the compile time of one per plane) moves
    the values to lanes."""
    bs = words.shape[0]
    hw = hdp // bipolar.PACK_WIDTH
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 32), 2)
    acc = None
    for i in range(n_bits):
        plane = words[:, i * hw:(i + 1) * hw]
        bits = (plane[:, :, None] >> shifts) & jnp.uint32(1)
        term = (bits << (i + 1)).astype(jnp.int32)
        acc = term if acc is None else acc + term
    vals = acc.reshape(bs, hdp) - bipolar.max_value(n_bits)
    return vals.astype(jnp.float32)


def _kernel_paged(bt_ref, qp_ref, kp_ref, ks_ref, vs_ref, q_ref, kq_ref,
                  vq_ref, out_ref, m_ref, l_ref, acc_ref, k_ref, v_ref, *,
                  scale: float, causal: bool, window,
                  h: int, dp: int, n_bits: int):
    del bt_ref  # consumed by the index maps (scalar prefetch)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # position mask first: causal + sliding window by absolute position,
    # invalid slots (pos -1: null block / freshly allocated) excluded
    qpos = qp_ref[0]                              # (bq, 1)
    kpos = kp_ref[0]                              # (1, bs)
    valid = _position_mask(qpos, kpos, causal, window)

    # grid skip: a block none of this tile's queries may see -- the
    # null block behind a padded table entry, a block fully outside
    # every query's attention window, or (Sq>1 suffix prefill) a block
    # entirely in this tile's causal future -- contributes exactly
    # nothing to the online softmax (p = 0, alpha = 1), so the dequant
    # and both MXU passes are skipped outright.  Out-of-window blocks
    # normally never reach the kernel at all (the scheduler reclaims
    # them and the rolling block table bounds the grid itself); this
    # guard covers the in-between steps and the padded table entries.
    @pl.when(jnp.max(valid.astype(jnp.int32)) > 0)
    def _update():
        # one physical block of the pool, routed here by the block table:
        # every head's planes unpack at once into (bs, H * Dp) columns
        k_ref[...] = _dequant_rows(kq_ref[0], n_bits, h * dp)
        v_ref[...] = _dequant_rows(vq_ref[0], n_bits, h * dp)
        ks, vs = ks_ref[0], vs_ref[0]             # (bs, H) scales
        grp = _head_group(h, dp)

        def heads(g, carry):
            # a group of heads starts on a 128-lane boundary; the heads
            # inside it are static lane slices
            cols = pl.ds(pl.multiple_of(g * grp * dp, LANES), grp * dp)
            kg, vg = k_ref[:, cols], v_ref[:, cols]
            for j in range(grp):
                hh = g * grp + j
                kt = kg[:, j * dp:(j + 1) * dp] * _head_column(ks, hh)
                vt = vg[:, j * dp:(j + 1) * dp] * _head_column(vs, hh)
                q = q_ref[0, hh]                  # (bq, dp), zero pad cols
                s = jax.lax.dot_general(
                    q.astype(jnp.float32), kt, (((1,), (1,)), ((), ())),
                    precision=_F32, preferred_element_type=jnp.float32) * scale
                s = jnp.where(valid, s, -1e30)
                _online_softmax_update(s, valid, vt, m_ref.at[hh],
                                       l_ref.at[hh], acc_ref.at[hh])
            return carry

        jax.lax.fori_loop(0, h // grp, heads, 0)

    @pl.when(jk == nk - 1)
    def _done():
        out_ref[0] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-20)).astype(out_ref.dtype)


# A decode tile: at most this many real query rows per kv head take the
# head-batched body below; larger tiles (fused chunk steps, suffix
# prefill, GQA groups wider than 8) keep the per-head tile body.
DECODE_MAX_ROWS = 8


def paged_decode_body(rows: int) -> bool:
    """Whether a paged call whose query tile holds ``rows`` real rows per
    kv head (GQA group x query tokens) runs the head-batched decode body
    (True) or the per-head tile body (False)."""
    return rows <= DECODE_MAX_ROWS


def _row_to_column(row):
    """``(1, n)`` int32 -> ``(n, 1)``, exact for |values| < 2**24."""
    n = row.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    col = jnp.sum(jnp.where(eye, row.astype(jnp.float32), 0.0), axis=1,
                  keepdims=True)
    return col.astype(jnp.int32)


def _kernel_paged_decode(bt_ref, qp_ref, kp_ref, ks_ref, vs_ref, q_ref,
                         e_ref, et_ref, kq_ref, vq_ref, out_ref,
                         m_ref, l_ref, acc_ref, qs_ref, *,
                         scale: float, causal: bool, window, rows: int,
                         unroll: bool):
    """Every head of one pool block in whole-block, lane-dense operations.

    Columns keep the pool's stored order ``(plane i, head, word w)``:
    bit ``j`` of column ``c`` is dim ``32 w + j`` of head ``h(c)``'s
    plane ``i``, and a bipolar value is ``sum_i 2^i (2 b_i - 1)``.  The
    query arrives in that order, one ``(32, W)`` slab per row, already
    weighted by ``2^i``, so for each bit ``j`` one select of ``+q``/``-q``
    on the block's words adds that bit's signed products for every head.
    ``E (H, W)`` marks each column's head: ``@ E^T`` sums a head's
    columns, ``@ E`` spreads a per-head value over its columns.  The
    value accumulator keeps, per bit ``j``, the signed sums over tokens
    in ``sub`` sublane partials; planes fold outside the kernel.  The
    two loops over ``j`` unroll under Mosaic (``unroll``: 28% less
    kernel time at StableLM-3B widths on a v5e); interpret mode runs the
    same body rolled, which keeps the CPU tests' compiles short."""
    del bt_ref  # consumed by the index maps (scalar prefetch)
    b = pl.program_id(0)
    jk = pl.program_id(1)
    nk = pl.num_programs(1)
    bs, w = kq_ref.shape[1], kq_ref.shape[2]
    sub = acc_ref.shape[2]
    pw = bipolar.PACK_WIDTH

    def spread(x):                                # (n, H) -> (n, W)
        return jax.lax.dot_general(
            x, e_ref[...], (((1,), (0,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32)

    def fold(x):                                  # (bs, W) -> (sub, W)
        return functools.reduce(
            jnp.add, [x[t:t + sub] for t in range(0, bs, sub)])

    def each_row(body):
        def run(r, carry):
            body(r)
            return carry
        jax.lax.fori_loop(0, rows, run, 0)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        @each_row
        def _(r):
            for j in range(pw):
                qs_ref[r, j] = jnp.broadcast_to(
                    q_ref[0, r, pl.ds(j, 1), :], (sub, w))

    kpos = _row_to_column(kp_ref[0])              # (bs, 1)

    @each_row
    def _(r):
        valid = _position_mask(qp_ref[b, r], kpos, causal, window)

        # grid skip as in the tile body: a block this row may not see
        # (null block, out of window, causal future) adds nothing
        @pl.when(jnp.max(valid.astype(jnp.int32)) > 0)
        def _update():
            kw, vw = kq_ref[0], vq_ref[0]         # (bs, W) uint32
            ks, vs = ks_ref[0], vs_ref[0]         # (bs, H)

            def k_bit(j, a):
                on = (kw & (jnp.uint32(1) << j.astype(jnp.uint32))) != 0
                qj = qs_ref[r, j]
                qj = jnp.concatenate([qj] * (bs // sub), axis=0)
                return a + jnp.where(on, qj, -qj)

            a = jax.lax.fori_loop(0, pw, k_bit,
                                  jnp.zeros((bs, w), jnp.float32),
                                  unroll=unroll)
            s = jax.lax.dot_general(
                a, et_ref[...], (((1,), (0,)), ((), ())), precision=_F32,
                preferred_element_type=jnp.float32) * (ks * scale)
            s = jnp.where(valid, s, -1e30)        # (bs, H)
            m_prev, l_prev = m_ref[r], l_ref[r]   # (1, H)
            m_new = jnp.maximum(m_prev, jnp.max(s, 0, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[r] = l_prev * alpha + jnp.sum(p, 0, keepdims=True)
            m_ref[r] = m_new
            pv = spread(p * vs)                   # (bs, W)
            alpha_c = jnp.broadcast_to(spread(alpha), (sub, w))

            def v_bit(j, carry):
                on = (vw & (jnp.uint32(1) << j.astype(jnp.uint32))) != 0
                acc_ref[r, j] = (acc_ref[r, j] * alpha_c
                                 + fold(jnp.where(on, pv, -pv)))
                return carry

            jax.lax.fori_loop(0, pw, v_bit, 0, unroll=unroll)

    @pl.when(jk == nk - 1)
    def _done():
        @each_row
        def _(r):
            inv = 1.0 / jnp.maximum(spread(l_ref[r]), 1e-20)   # (1, W)
            for j in range(pw):
                out_ref[0, r, pl.ds(j, 1), :] = jnp.sum(
                    acc_ref[r, j], 0, keepdims=True) * inv


def _decode_vmem_bytes(rows: int, sub: int, words: int) -> int:
    """Scoped VMEM for the decode body: the default 16 MiB for the pool
    blocks, ``E``, ``E^T`` and temporaries, plus what grows with the
    rows -- the query and output blocks, double-buffered, and the query
    and accumulator scratch, 4-byte words of ``(32, W)`` a row, two of
    them ``sub`` sublanes deep.  At StableLM-3B widths (W = 768) one row
    asks for 18 MiB, eight for 32 MiB.  Asking for more than this leaves
    XLA less VMEM to hold the step's other buffers in."""
    per_row = 4 * bipolar.PACK_WIDTH * words * (2 * 2 + 2 * sub)
    return 16 * 2**20 + rows * per_row


def _column_heads(h: int, dw: int, n_bits: int) -> np.ndarray:
    """``E (H, n_bits * H * Dw)``: 1 where pool column ``c`` belongs to
    head ``h`` (word ``(i * H + h) * Dw + w``, :func:`kv_pool_rows`)."""
    col_head = (np.arange(n_bits * h * dw) // dw) % h
    return (col_head[None, :] == np.arange(h)[:, None]).astype(np.float32)


def _paged_decode(q, k_pool, k_scale, v_pool, v_scale, pool_pos,
                  block_tables, q_pos, *, d: int, n_bits: int,
                  causal: bool, window, interpret: bool):
    """The decode body's call: ``q (B, H, R, Dp)`` -> ``(B, H, R, Dp)``.

    The query is laid out in the pool's column order here, once per
    call: ``qk[b, r, j, (i * H + h) * Dw + w] = 2^i q[b, h, r, 32 w +
    j]``.  The kernel returns, per row and bit ``j``, the normalized
    signed value sums of each column; the planes fold here with their
    weights ``2^i`` and the result returns to ``(H, Dp)``."""
    b, h, rows, dp = q.shape
    _, bs, words = k_pool.shape
    nb = block_tables.shape[1]
    dw = dp // bipolar.PACK_WIDTH
    pw = bipolar.PACK_WIDTH
    plane_w = 2.0 ** jnp.arange(n_bits, dtype=jnp.float32)
    qf = q.astype(jnp.float32).reshape(b, h, rows, dw, pw)
    qf = qf.transpose(0, 2, 4, 1, 3)              # (B, R, j, H, Dw)
    qk = (plane_w[:, None, None] * qf[:, :, :, None]).reshape(
        b, rows, pw, words)
    e = _column_heads(h, dw, n_bits)
    sub = int(np.gcd(bs, 8))
    kernel = functools.partial(
        _kernel_paged_decode, scale=1.0 / np.sqrt(d), causal=causal,
        window=window, rows=rows, unroll=not interpret)
    pool_block = lambda i, k, bt, qp: (bt[i, k], 0, 0)
    whole = lambda i, k, bt, qp: (0, 0)
    lane = lambda i, k, bt, qp: (i, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, 1, bs), pool_block),            # pool_pos
            pl.BlockSpec((1, bs, h), pool_block),            # k_scale
            pl.BlockSpec((1, bs, h), pool_block),            # v_scale
            pl.BlockSpec((1, rows, pw, words), lane),        # q, pool order
            pl.BlockSpec((h, words), whole),                 # E
            pl.BlockSpec((words, h), whole),                 # E^T
            pl.BlockSpec((1, bs, words), pool_block),        # k rows
            pl.BlockSpec((1, bs, words), pool_block),        # v rows
        ],
        out_specs=pl.BlockSpec((1, rows, pw, words), lane),
        scratch_shapes=[pltpu.VMEM((rows, 1, h), jnp.float32),
                        pltpu.VMEM((rows, 1, h), jnp.float32),
                        pltpu.VMEM((rows, pw, sub, words), jnp.float32),
                        pltpu.VMEM((rows, pw, sub, words), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, pw, words), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_decode_vmem_bytes(rows, sub, words)),
        interpret=interpret,
        name="flash_attention_paged_quantized",
    )(block_tables, q_pos, pool_pos[:, None, :], k_scale, v_scale, qk,
      jnp.asarray(e), jnp.asarray(e.T), k_pool, v_pool)
    o = out.reshape(b, rows, pw, n_bits, h, dw)
    o = jnp.sum(o * plane_w[:, None, None], axis=3)   # (B, R, j, H, Dw)
    return o.transpose(0, 3, 1, 4, 2).reshape(b, h, rows, dp).astype(q.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("d", "n_bits", "causal", "window", "block", "interpret"))
def flash_attention_paged_quantized(q: jax.Array,
                                    k_pool: jax.Array, k_scale: jax.Array,
                                    v_pool: jax.Array, v_scale: jax.Array,
                                    pool_pos: jax.Array,
                                    block_tables: jax.Array,
                                    q_pos: jax.Array, *,
                                    d: int, n_bits: int,
                                    causal: bool = True, window=None,
                                    block: int = DEFAULT_PAGED_BQ,
                                    interpret: bool = False) -> jax.Array:
    """Dequant-on-read attention over a *paged* bipolar-INT KV pool.

    The pool stores fixed-size token blocks shared by all requests; each
    request addresses its blocks through a block table.  The table is a
    scalar-prefetch operand: the Mosaic grid walks ``(B, Gq/bq,
    n_blocks)`` and the K/V block specs index the pool with
    ``table[b, j]``, so HBM only ever moves the blocks a request
    actually owns -- the gather never materializes a contiguous copy.
    One grid step reads a whole pool block (every head).  Query tiles
    of at most :data:`DECODE_MAX_ROWS` rows (``paged_decode_body``) run
    the head-batched decode body on a ``(B, n_blocks)`` grid, every
    head at once in lane-dense operations on the stored bit order; wider
    tiles run the tile body, which unpacks the block and loops over the
    heads.  Both run as the kernel named ``flash_attention_paged_quantized``.

    Decode calls carry one query row per GQA group; block-table *suffix
    prefill* folds the suffix length into the query axis (``Gq = G *
    Sq``), tiled ``bq`` rows at a time with causal masking by absolute
    position -- the suffix attends through the shared prefix blocks and
    its own freshly written blocks in a single pass.

    Sliding-window attention (``window``) masks ``kv_pos <= q_pos -
    window`` by absolute position, and the kernel *skips* any block
    none of the tile's queries may see (fully out-of-window, the null
    block behind padded table entries, or entirely in the causal
    future): the masked tile's dequant and MXU work never issue.  With
    the serving scheduler's out-of-window reclaim the block table
    itself is a rolling window, so the grid's block axis -- and the HBM
    the step moves -- stays O(window / block_size) per request however
    long the generation runs.

    Args:
      q: ``(B, H, Gq, Dp)`` -- per-kv-head grouped queries (``Gq`` =
        group size x query tokens), zero-padded past the true head dim
        ``d`` (``Dp = 32*ceil(d/32)``); ``Gq`` must tile by ``block``.
      k_pool/v_pool: ``(n_blocks, bs, n_bits * H * Dp/32)`` uint32 token
        rows, plane-major (:func:`repro.kernels.ops.kv_pool_rows`).
      k_scale/v_scale: ``(n_blocks, bs, H)`` f32 absmax scales.
      pool_pos: ``(n_blocks, bs)`` int32 absolute positions, -1 = empty
        slot (freshly allocated or null block 0).
      block_tables: ``(B, NB)`` int32 physical block ids; rows pad with
        0, the reserved null block whose positions stay -1.
      q_pos: ``(B, Gq)`` int32 query positions (-1 rows are masked out).

    Returns ``(B, H, Gq, Dp)``; the caller slices ``[..., :d]``.
    """
    b, h, gq, dp = q.shape
    n_blocks, bs, words = k_pool.shape
    nb = block_tables.shape[1]
    assert words * bipolar.PACK_WIDTH == n_bits * h * dp, (
        k_pool.shape, q.shape, n_bits)
    if paged_decode_body(gq):
        return _paged_decode(
            q, k_pool, k_scale, v_pool, v_scale, pool_pos, block_tables,
            q_pos, d=d, n_bits=n_bits, causal=causal, window=window,
            interpret=interpret)
    bq = min(block, gq)
    if gq % bq:
        raise ValueError(f"query rows {gq} not tiled by {bq}")
    kernel = functools.partial(
        _kernel_paged, scale=1.0 / np.sqrt(d), causal=causal, window=window,
        h=h, dp=dp, n_bits=n_bits)
    # every block's last two dims are whole array dims or (8, 128)
    # multiples, as Mosaic requires: positions carry a unit axis on the
    # side that is not tiled, and K/V/scale blocks are whole pool blocks
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, gq // bq, nb),
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda i, q, k, bt: (i, q, 0)),  # q_pos
            pl.BlockSpec((1, 1, bs),
                         lambda i, q, k, bt: (bt[i, k], 0, 0)),      # pool_pos
            pl.BlockSpec((1, bs, h),
                         lambda i, q, k, bt: (bt[i, k], 0, 0)),      # k_scale
            pl.BlockSpec((1, bs, h),
                         lambda i, q, k, bt: (bt[i, k], 0, 0)),      # v_scale
            pl.BlockSpec((1, h, bq, dp),
                         lambda i, q, k, bt: (i, 0, q, 0)),          # q
            pl.BlockSpec((1, bs, words),
                         lambda i, q, k, bt: (bt[i, k], 0, 0)),      # k rows
            pl.BlockSpec((1, bs, words),
                         lambda i, q, k, bt: (bt[i, k], 0, 0)),      # v rows
        ],
        out_specs=pl.BlockSpec((1, h, bq, dp),
                               lambda i, q, k, bt: (i, 0, q, 0)),
        scratch_shapes=[pltpu.VMEM((h, bq, 1), jnp.float32),
                        pltpu.VMEM((h, bq, 1), jnp.float32),
                        pltpu.VMEM((h, bq, dp), jnp.float32),
                        pltpu.VMEM((bs, h * dp), jnp.float32),
                        pltpu.VMEM((bs, h * dp), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, gq, dp), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=PAGED_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="flash_attention_paged_quantized",
    )(block_tables, q_pos[:, :, None], pool_pos[:, None, :], k_scale,
      v_scale, q, k_pool, v_pool)


def attention_reference(q, k, v, q_pos, kv_pos, *, causal=True, window=None):
    """Pure-jnp oracle in the folded (BH, S, D) kernel layout.

    Direct (non-online) softmax; fully-masked rows return 0, matching the
    kernels' denominator clamp.  Both products run at full f32 precision
    on every backend (a TPU's default f32 matmul rounds its inputs to
    bf16), as an oracle should.
    """
    d = q.shape[-1]
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bqd,btd->bqt", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=hi) / np.sqrt(d)
    valid = _position_mask(q_pos[:, :, None], kv_pos[:, None, :],
                           causal, window)
    s = jnp.where(valid, s, -1e30)
    m = jnp.maximum(jnp.max(s, -1, keepdims=True), -1e30)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    o = jnp.einsum("bqt,btd->bqd", p, v.astype(jnp.float32), precision=hi)
    o = o / jnp.maximum(p.sum(-1, keepdims=True), 1e-20)
    return o.astype(q.dtype)
