"""Grouped MoE expert kernel vs the legacy batched-over-E path.

The contract under test (ops.ap_moe_expert_linear): per dispatch-group
segment, rows below the keep count are BIT-identical to the pre-rewire
oracle ``layers._expert_matmul`` (same f32 quantization chain, same
epilogue cast point), rows at-or-above it are exact zeros, and the
interpret impl's kernel-reported live-tile map equals the reference
impl's analytic one -- the proof that ``pl.when`` actually skipped the
empty capacity tiles rather than computing zeros the hard way.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


def _weights(nb, *, seed, n=N, k=K):
    w = np.asarray(
        np.random.default_rng(seed).standard_normal((E, n, k)) / np.sqrt(k),
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


def _tile_oracle(w, x, a_bits, w2=None):
    """Each row through its tile's expert on the legacy batched path."""
    q = types.SimpleNamespace(a_bits=a_bits)
    t, bc = len(TILE_ROWS), x.shape[0] // len(TILE_ROWS)
    rows = []
    for i, e in enumerate(TILE_EXPERT):
        xe = jnp.broadcast_to(x[i * bc:(i + 1) * bc][None],
                              (E, bc, x.shape[1]))
        y = (_legacy_dual(w, w2, xe, a_bits) if w2 is not None
             else L._expert_matmul(w, xe, q))
        rows.append(np.asarray(y)[e])
    return np.concatenate(rows)


def _tile_live():
    return np.concatenate([np.arange(8) < n for n in TILE_ROWS])


@pytest.mark.parametrize("impl", ["reference", "interpret"])
@pytest.mark.parametrize("dual", [False, True], ids=["single", "dual"])
def test_expert_tiles_match_legacy_and_skip_dead_tiles(impl, dual):
    a_bits, w_bits = 8, 2
    w, w2 = _weights(w_bits, seed=21), _weights(w_bits, seed=22)
    x = jnp.asarray(RNG.standard_normal((8 * len(TILE_ROWS), K)),
                    jnp.bfloat16)
    y, live = ops.ap_moe_tiles_linear(
        x, w, w2=w2 if dual else None, act="silu" if dual else "none",
        tile_rows=jnp.asarray(TILE_ROWS), tile_expert=jnp.asarray(
            TILE_EXPERT), a_bits=a_bits, impl=impl, with_stats=True)
    y = np.asarray(y)
    rows = _tile_live()
    oracle = _tile_oracle(w, x, a_bits, w2 if dual else None)
    np.testing.assert_array_equal(y[rows], oracle[rows])
    assert not y[~rows].any(), "rows past a tile's count must be zeros"
    np.testing.assert_array_equal(np.asarray(live),
                                  np.asarray(TILE_ROWS) > 0)


def test_col_block_fits_whole_expert_rows_where_it_can():
    from repro.kernels import moe as moe_kernel
    # DeepSeek-MoE widths at 2 bits: one block holds a whole expert row
    assert moe_kernel.col_block(1408, 64, 2, dual=True) == 1408
    assert moe_kernel.col_block(2048, 44, 2, dual=False) == 2048
    # Mixtral's 14336-wide experts at 8 bits split into column blocks
    bn = moe_kernel.col_block(14336, 128, 8, dual=True)
    assert 14336 % bn == 0 and bn % 128 == 0 and bn < 14336
    assert bn * 8 * 128 * 4 * 2 * 2 <= moe_kernel.WEIGHT_BLOCK_BYTES
