"""Grouped MoE expert kernel vs the legacy batched-over-E path.

The contract under test (ops.ap_moe_expert_linear): per dispatch-group
segment, rows below the keep count are BIT-identical to the pre-rewire
oracle ``layers._expert_matmul`` (same f32 quantization chain, same
epilogue cast point), rows at-or-above it are exact zeros, and the
interpret impl's kernel-reported live-tile map equals the reference
impl's analytic one -- the proof that ``pl.when`` actually skipped the
empty capacity tiles rather than computing zeros the hard way.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bipolar
from repro.kernels import ops
from repro.models import layers as L
from repro.models.config import QuantConfig
from repro.models.model import _quantize_leaf

RNG = np.random.default_rng(42)

# deliberately odd: SEG not a multiple of 8, K not a multiple of 32,
# N not a multiple of 128 -- every pad path in the op is exercised
E, G, SEG, K, N = 3, 2, 5, 37, 19
C = G * SEG

# activation/weight bit pairs spanning the full 1..8 arbitrary range
BIT_PAIRS = [(1, 1), (2, 2), (3, 4), (4, 4), (5, 6), (7, 7), (8, 3), (8, 8)]
# interpret runs the real kernel body in python -- keep its matrix small
INTERP_BITS = [(1, 1), (3, 4), (8, 8)]


def _weights(nb, *, seed, n=N, k=K, e=E):
    w = np.asarray(
        np.random.default_rng(seed).standard_normal((e, n, k)) / np.sqrt(k),
        np.float32)
    return _quantize_leaf(jnp.asarray(w), QuantConfig(w_bits=nb),
                          stacked=False)


def _acts(dtype, *, k=K):
    x = RNG.standard_normal((E, C, k)).astype(np.float32)
    return jnp.asarray(x, dtype)


def _counts(fills):
    """counts (E, G) from an explicit per-(e, g) fill list."""
    c = np.asarray(fills, np.int32).reshape(E, G)
    assert c.max() <= SEG
    return jnp.asarray(c)


DEFAULT_COUNTS = _counts([[5, 2], [3, 0], [1, 4]])  # mixed partial fills


def _live_rows(counts):
    """(E, C) bool: which capacity rows hold a kept token."""
    off = np.arange(C) % SEG
    grp = np.arange(C) // SEG
    return np.asarray(counts)[:, grp] > off[None, :]


def _legacy_single(w, x, a_bits):
    return L._expert_matmul(w, x, types.SimpleNamespace(a_bits=a_bits))


def _legacy_dual(wg, wu, x, a_bits):
    """The legacy gate/up composition from moe_apply: one shared
    activation quantization, silu(gate) * up composed in f32 (no
    intermediate narrowing cast), one cast back at the end."""
    q = types.SimpleNamespace(a_bits=a_bits)
    pre = L._expert_quantize(x, a_bits)
    gate = L._expert_matmul(wg, x, q, pre, out_dtype=jnp.float32)
    up = L._expert_matmul(wu, x, q, pre, out_dtype=jnp.float32)
    return (jax.nn.silu(gate) * up).astype(x.dtype)


def _assert_rows(y, oracle, counts):
    """Live rows bit-identical to the oracle; dead rows exact zeros."""
    y, oracle = np.asarray(y), np.asarray(oracle)
    live = _live_rows(counts)
    np.testing.assert_array_equal(y[live], oracle[live])
    assert not y[~live].any(), "dead capacity rows must be exact zeros"


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("bits", BIT_PAIRS,
                         ids=[f"a{a}w{b}" for a, b in BIT_PAIRS])
def test_reference_matches_legacy(bits, dtype):
    a_bits, w_bits = bits
    w = _weights(w_bits, seed=w_bits)
    x = _acts(dtype)
    y = ops.ap_moe_expert_linear(x, w, counts=DEFAULT_COUNTS, a_bits=a_bits,
                                 impl="reference")
    _assert_rows(y, _legacy_single(w, x, a_bits), DEFAULT_COUNTS)


@pytest.mark.parametrize("variant", ["fused", "bitserial"])
@pytest.mark.parametrize("bits", INTERP_BITS,
                         ids=[f"a{a}w{b}" for a, b in INTERP_BITS])
def test_interpret_matches_legacy_and_skips_dead_tiles(bits, variant):
    a_bits, w_bits = bits
    w = _weights(w_bits, seed=10 + w_bits)
    x = _acts(jnp.bfloat16)
    y, live = ops.ap_moe_expert_linear(
        x, w, counts=DEFAULT_COUNTS, a_bits=a_bits, variant=variant,
        impl="interpret", with_stats=True)
    _assert_rows(y, _legacy_single(w, x, a_bits), DEFAULT_COUNTS)
    # skip-path proof: the kernel-reported live-tile map must equal the
    # reference impl's analytic map -- tiles the analytic map calls dead
    # were dead in-kernel too (pl.when really skipped them)
    _, live_ref = ops.ap_moe_expert_linear(
        x, w, counts=DEFAULT_COUNTS, a_bits=a_bits, variant=variant,
        impl="reference", with_stats=True)
    np.testing.assert_array_equal(np.asarray(live), np.asarray(live_ref))
    n_skipped = int(np.asarray(live).size - np.asarray(live).sum())
    assert n_skipped == int((np.asarray(DEFAULT_COUNTS) == 0).sum()), \
        "one whole capacity tile per empty (expert, group) must be skipped"


@pytest.mark.parametrize("impl", ["reference", "interpret"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_dual_gate_up_matches_legacy_composition(impl, dtype):
    a_bits, w_bits = 8, 2
    wg = _weights(w_bits, seed=1)
    wu = _weights(w_bits, seed=2)
    x = _acts(dtype)
    y = ops.ap_moe_expert_linear(x, wg, w2=wu, counts=DEFAULT_COUNTS,
                                 a_bits=a_bits, act="silu", impl=impl)
    _assert_rows(y, _legacy_dual(wg, wu, x, a_bits), DEFAULT_COUNTS)


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_empty_expert_and_all_dropped_group(impl):
    a_bits, w_bits = 6, 3
    w = _weights(w_bits, seed=3)
    x = _acts(jnp.bfloat16)
    # expert 1 receives nothing anywhere; group 1 dropped every token
    counts = _counts([[4, 0], [0, 0], [2, 0]])
    y, live = ops.ap_moe_expert_linear(x, w, counts=counts, a_bits=a_bits,
                                       impl=impl, with_stats=True)
    _assert_rows(y, _legacy_single(w, x, a_bits), counts)
    live = np.asarray(live).reshape(E, G, -1)
    assert not live[1].any(), "empty expert must report zero live tiles"
    assert not live[:, 1].any(), "all-dropped group must report zero tiles"


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_full_capacity_no_dead_rows(impl):
    a_bits, w_bits = 4, 4
    w = _weights(w_bits, seed=4)
    x = _acts(jnp.bfloat16)
    counts = _counts([[SEG] * G] * E)
    y = ops.ap_moe_expert_linear(x, w, counts=counts, a_bits=a_bits,
                                 impl=impl)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(_legacy_single(w, x, a_bits)))


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_two_stage_chain_bit_stable_under_jit(impl):
    """The engine runs both expert GEMM stages inside one jit graph.
    The grouped chain (dual gate/up -> down) and the barrier-pinned
    legacy composition must agree bitwise, compiled or eager -- the
    regression test for XLA's excess-precision convert elision, which
    rounds the f32->bf16->f32 boundary between fused stages differently
    than the materialized HBM round-trip the kernel performs."""
    a_bits = 8
    q = types.SimpleNamespace(a_bits=a_bits)
    wg, wu = _weights(2, seed=6), _weights(2, seed=7)
    wd = _weights(2, seed=8, n=K, k=N)
    x = _acts(jnp.bfloat16)

    def grouped(xx):
        h = ops.ap_moe_expert_linear(xx, wg, w2=wu, counts=DEFAULT_COUNTS,
                                     a_bits=a_bits, act="silu", impl=impl)
        return ops.ap_moe_expert_linear(h, wd, counts=DEFAULT_COUNTS,
                                        a_bits=a_bits, impl=impl)

    def legacy(xx):
        # the quantized fallback branch of moe_apply, barriers included
        xx = jax.lax.optimization_barrier(xx)
        pre = L._expert_quantize(xx, a_bits)
        gate = L._expert_matmul(wg, xx, q, pre, out_dtype=jnp.float32)
        up = L._expert_matmul(wu, xx, q, pre, out_dtype=jnp.float32)
        h = jax.lax.optimization_barrier(
            (jax.nn.silu(gate) * up).astype(xx.dtype))
        return jax.lax.optimization_barrier(L._expert_matmul(wd, h, q))

    yg_e, yg_j = np.asarray(grouped(x)), np.asarray(jax.jit(grouped)(x))
    yl_e, yl_j = np.asarray(legacy(x)), np.asarray(jax.jit(legacy)(x))
    np.testing.assert_array_equal(yg_e, yg_j)
    np.testing.assert_array_equal(yl_e, yl_j)
    live = _live_rows(DEFAULT_COUNTS)
    np.testing.assert_array_equal(yg_j[live], yl_j[live])


def test_single_group_matches_multi_group_live_rows():
    # G=1 (the decode-shape dispatch) against the same tokens split G=2:
    # live rows only, since the dead-row placement differs by grouping
    a_bits, w_bits = 8, 2
    w = _weights(w_bits, seed=5)
    x = _acts(jnp.bfloat16)
    counts1 = jnp.asarray(np.asarray(DEFAULT_COUNTS).sum(1, keepdims=True))
    # rebuild x so each expert's kept tokens form one prefix
    live = _live_rows(DEFAULT_COUNTS)
    xc = np.zeros_like(np.asarray(x, np.float32))
    for e in range(E):
        rows = np.asarray(x, np.float32)[e][live[e]]
        xc[e, :len(rows)] = rows
    xc = jnp.asarray(xc, x.dtype)
    y1 = ops.ap_moe_expert_linear(xc, w, counts=counts1, a_bits=a_bits,
                                  impl="reference")
    y2 = ops.ap_moe_expert_linear(x, w, counts=DEFAULT_COUNTS,
                                  a_bits=a_bits, impl="reference")
    y1, y2 = np.asarray(y1), np.asarray(y2)
    for e in range(E):
        n_live = int(live[e].sum())
        np.testing.assert_array_equal(y1[e, :n_live], y2[e][live[e]])


# expert tiles of the dropless dispatch: 8 rows each; expert 2 holds two
# tiles, expert 1 one partial tile, a dead tile sits between live ones
# and the last two tiles are the layout's unused tail
TILE_ROWS = [8, 3, 0, 5, 1, 0, 0]
TILE_EXPERT = [2, 2, 0, 1, 0, 2, 2]
# the same fills over tiles of 32 rows, as the fused chunk steps cut them
TILE_ROWS_32 = [32, 13, 0, 21, 1, 0, 0]

# tiles over 16 experts, two sublane groups of 8: experts 9 and 7 share
# no group, expert 15 follows a dead tile, the tail is dead
WIDE_TILE_EXPERT = [9, 9, 0, 7, 15, 15, 15]


def _tile_oracle(w, x, a_bits, tile_rows, tile_expert, w2=None):
    """Each row through its tile's expert on the legacy batched path."""
    q = types.SimpleNamespace(a_bits=a_bits)
    bc = x.shape[0] // len(tile_rows)
    rows = []
    for i, ex in enumerate(tile_expert):
        we, xe = bipolar.take_experts(w, jnp.asarray([ex])), \
            x[None, i * bc:(i + 1) * bc]
        y = (_legacy_dual(we, bipolar.take_experts(w2, jnp.asarray([ex])),
                          xe, a_bits) if w2 is not None
             else L._expert_matmul(we, xe, q))
        rows.append(np.asarray(y)[0])
    return np.concatenate(rows)


# (E, K, N, stored bits, served bits, tile rows) of the interpret-only
# cases at published widths.  By ops.expert_plane_layout the kernel reads
# DeepSeek-MoE's gate/up planes (Kw 64) with Kw on the sublanes, its down
# planes (Kw 44, K padded 1400 -> 1408) with E on the sublanes, and Kw 128
# on the lanes as stored
WIDE_TILES = {
    "gate_up-bc8": (16, 2048, 1408, 2, 2, 8),
    "gate_up-bc32": (16, 2048, 1408, 2, 2, 32),
    "gate_up-nested3to2": (16, 2048, 1408, 3, 2, 8),
    "down-bc8": (16, 1400, 2048, 2, 2, 8),
    "down-bc32": (16, 1400, 2048, 2, 2, 32),
    "down-w3": (16, 1400, 2048, 3, 3, 8),
    "down-nested3to2": (16, 1400, 2048, 3, 2, 32),
    "kw128-lanes": (3, 4096, 128, 2, 2, 8),
}
TILE_CASES = (
    [pytest.param(impl, dual, None, id=f"{d}-{impl}")
     for d, dual in (("single", False), ("dual", True))
     for impl in ("reference", "interpret")]
    + [pytest.param("interpret", name.startswith("gate_up"), name,
                    id=f"{name}-interpret") for name in WIDE_TILES])


@pytest.mark.parametrize("impl,dual,wide", TILE_CASES)
def test_expert_tiles_match_legacy_and_skip_dead_tiles(impl, dual, wide):
    a_bits = 8
    e, k, n, stored, served, bc = (wide and WIDE_TILES[wide]) \
        or (E, K, N, 2, 2, 8)
    tile_rows = TILE_ROWS if bc == 8 else TILE_ROWS_32
    tile_expert = (TILE_EXPERT if e < 16 else WIDE_TILE_EXPERT)
    if wide:
        # absmax scales: the clip search is slow at these sizes, and the
        # kernel reads the planes the same way whatever the scales
        w, w2 = (ops.quantize_rows(jnp.asarray(
            np.random.default_rng(seed).standard_normal((e * n, k)),
            jnp.float32), stored, pad_bit=1, impl="reference")
            for seed in (21, 22))
        w, w2 = (dataclasses.replace(
            v, packed=v.packed.reshape(stored, e, n, -1),
            scale=v.scale.reshape(e, n, 1), shape=(e, n, k))
            for v in (w, w2))
    else:
        w = _weights(stored, seed=21, n=n, k=k, e=e)
        w2 = _weights(stored, seed=22, n=n, k=k, e=e)
    x = jnp.asarray(RNG.standard_normal((bc * len(tile_rows), k)),
                    jnp.bfloat16)
    y, live = ops.ap_moe_tiles_linear(
        x, w, w2=w2 if dual else None, act="silu" if dual else "none",
        tile_rows=jnp.asarray(tile_rows), tile_expert=jnp.asarray(
            tile_expert), a_bits=a_bits, impl=impl, with_stats=True,
        w_bits=None if served == stored else served)
    y = np.asarray(y)
    rows = np.concatenate([np.arange(bc) < r for r in tile_rows])
    ws = [bipolar.nested_slice(v, served) for v in (w, w2)]
    oracle = _tile_oracle(ws[0], x, a_bits, tile_rows, tile_expert,
                          ws[1] if dual else None)
    np.testing.assert_array_equal(y[rows], oracle[rows])
    assert not y[~rows].any(), "rows past a tile's count must be zeros"
    np.testing.assert_array_equal(np.asarray(live),
                                  np.asarray(tile_rows) > 0)


def test_col_block_fits_whole_expert_rows_where_it_can():
    from repro.kernels import moe as moe_kernel
    # DeepSeek-MoE widths at 2 bits: one block holds a whole expert row
    assert moe_kernel.col_block(1408, 64, 2, dual=True) == 1408
    assert moe_kernel.col_block(2048, 44, 2, dual=False) == 2048
    # Mixtral's 14336-wide experts at 8 bits split into column blocks
    bn = moe_kernel.col_block(14336, 128, 8, dual=True)
    assert 14336 % bn == 0 and bn % 128 == 0 and bn < 14336
    assert bn * 8 * 128 * 4 * 2 * 2 <= moe_kernel.WEIGHT_BLOCK_BYTES
    # read in their stored order, N on the lanes: DeepSeek-MoE's gate/up
    # (Kw 64 on the sublanes) and down (Kw 44, E on the sublanes, a block
    # holding 8 experts) still fit whole rows
    assert moe_kernel.col_block(1408, 64, 2, True, "EKN") == 1408
    assert moe_kernel.col_block(2048, 44, 2, False, "KEN") == 2048
    # Kw is padded to 8 sublanes there, not 128 lanes: a Kw of 64 takes
    # half the bytes a column, so the block is wider than on the lanes
    bn_s = moe_kernel.col_block(14336, 64, 8, True, "EKN")
    assert bn_s == 3584 > moe_kernel.col_block(14336, 64, 8, True) == 2048
    assert bn_s * (8 * 64 * 4 * 2 + 128) * 2 <= moe_kernel.WEIGHT_BLOCK_BYTES
    # a group of 8 experts a block: 44 words -> 8 x 44 rows, not 48
    assert moe_kernel.col_block(2048, 44, 8, True, "KEN") == 512
