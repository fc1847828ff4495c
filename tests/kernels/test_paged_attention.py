"""Three-impl parity for the paged dequantizing flash kernel.

The paged op reads packed bipolar K/V through a per-request block table
(serving block pool).  Contract (same as every op in repro.kernels.ops):
``reference`` (jnp gather + contiguous reference path) and ``interpret``
(the scalar-prefetch Pallas kernel body in Python) agree to float
tolerance on the same packed buffers; the ``pallas`` path runs the
identical kernel body on TPU.  Additionally the paged reference must be
*exactly* the contiguous :func:`ops.kv_cache_attention` on the gathered
layout -- paging is memory management, not math.

Since ISSUE 3 the kernel also serves block-table *suffix prefill*:
``Sq > 1`` causal query tokens folded into the query axis (grid tiled
by ``q_block`` rows), each masked by its own absolute position.  The
same parity matrix covers that path.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bipolar
from repro.kernels import ops

RNG = np.random.default_rng(11)

BITS = [2, 4, 8]


def _paged_inputs(bits, *, B=2, H=3, G=2, sq=1, d=16, bs=8, n_blocks=12,
                  NB=4, lens=(19, 7)):
    """Random per-request K/V quantized and scattered into pool blocks,
    plus the equivalent contiguous (gathered) layout as an oracle.

    ``sq`` > 1 emulates suffix prefill: the query axis carries ``G*sq``
    rows -- ``sq`` causal tokens per GQA group, positioned at the last
    ``sq`` positions of each request."""
    dw = bipolar.packed_words(d)
    k_pool = np.zeros((n_blocks, bs, H, bits, dw), np.uint32)
    v_pool = np.zeros_like(k_pool)
    k_sc = np.zeros((n_blocks, bs, H, 1), np.float32)
    v_sc = np.zeros_like(k_sc)
    pool_pos = np.full((n_blocks, bs), -1, np.int32)
    tables = np.zeros((B, NB), np.int32)    # pad entries -> null block 0
    free = list(range(1, n_blocks))

    T = NB * bs
    k_cat = np.zeros((B, T, H, bits, dw), np.uint32)
    v_cat = np.zeros_like(k_cat)
    ksc_cat = np.ones((B, T, H, 1), np.float32)
    vsc_cat = np.ones_like(ksc_cat)
    pos_cat = np.full((B, T), -1, np.int32)

    for b, ln in enumerate(lens):
        k = jnp.asarray(RNG.standard_normal((1, ln, H, d)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((1, ln, H, d)), jnp.float32)
        kq, ks = ops.quantize_kv(k, bits)
        vq, vs = ops.quantize_kv(v, bits)
        nb = -(-ln // bs)
        ids = [free.pop() for _ in range(nb)]
        tables[b, :nb] = ids
        for j, bid in enumerate(ids):
            lo, hi = j * bs, min((j + 1) * bs, ln)
            k_pool[bid, :hi - lo] = np.asarray(kq[0, lo:hi])
            v_pool[bid, :hi - lo] = np.asarray(vq[0, lo:hi])
            k_sc[bid, :hi - lo] = np.asarray(ks[0, lo:hi])
            v_sc[bid, :hi - lo] = np.asarray(vs[0, lo:hi])
            pool_pos[bid, :hi - lo] = np.arange(lo, hi)
        k_cat[b, :ln] = np.asarray(kq[0])
        v_cat[b, :ln] = np.asarray(vq[0])
        ksc_cat[b, :ln] = np.asarray(ks[0])
        vsc_cat[b, :ln] = np.asarray(vs[0])
        pos_cat[b, :ln] = np.arange(ln)

    q = jnp.asarray(RNG.standard_normal((B, H, G * sq, d)), jnp.float32)
    # row gi*sq + si is group gi's query for the si-th of the last sq
    # positions (the layers.attention_apply fold order)
    q_pos = jnp.asarray([[ln - sq + (r % sq) for r in range(G * sq)]
                         for ln in lens], jnp.int32)
    # the pool's own layout: lane-dense token rows, (.., H) scales
    rows = lambda a: ops.kv_pool_rows(jnp.asarray(a))
    paged = (q, rows(k_pool), jnp.asarray(k_sc[..., 0]), rows(v_pool),
             jnp.asarray(v_sc[..., 0]), jnp.asarray(pool_pos),
             jnp.asarray(tables), q_pos)
    contiguous = (k_cat, ksc_cat, v_cat, vsc_cat, pos_cat)
    return paged, contiguous


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("window", [None, 8])
def test_paged_attention_reference_interpret_parity(bits, window):
    paged, _ = _paged_inputs(bits)
    d = paged[0].shape[-1]
    y_ref = np.asarray(ops.paged_kv_cache_attention(
        *paged, d=d, window=window, impl="reference"))
    y_int = np.asarray(ops.paged_kv_cache_attention(
        *paged, d=d, window=window, impl="interpret"))
    np.testing.assert_allclose(y_int, y_ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bits", BITS)
def test_paged_matches_contiguous_on_gathered_layout(bits):
    """Paging must not change the math: the paged read equals the
    contiguous quantized-KV attention over the same packed planes laid
    out contiguously (exactly, under the shared reference dataflow)."""
    paged, (k_cat, ksc_cat, v_cat, vsc_cat, pos_cat) = _paged_inputs(bits)
    q = paged[0]
    B, H, G, d = q.shape
    T = k_cat.shape[1]
    y_p = np.asarray(ops.paged_kv_cache_attention(
        *paged, d=d, impl="reference"))

    fold = lambda a: a.transpose((0, 2, 1) + tuple(
        range(3, a.ndim))).reshape((B * H, T) + a.shape[3:])
    y_c = np.asarray(ops.kv_cache_attention(
        q.reshape(B * H, G, d),
        fold(jnp.asarray(k_cat)), fold(jnp.asarray(ksc_cat)),
        fold(jnp.asarray(v_cat)), fold(jnp.asarray(vsc_cat)),
        jnp.repeat(paged[-1], H, 0),
        jnp.repeat(jnp.asarray(pos_cat), H, 0),
        d=d, impl="reference")).reshape(B, H, G, d)
    np.testing.assert_array_equal(y_p, y_c)


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("window", [None, 8])
def test_paged_sq_gt1_reference_interpret_parity(bits, window):
    """Suffix-prefill shape: 6 causal query tokens per GQA group, with
    a q_block of 8 so the 12 padded query rows span two kernel tiles
    (exercising the scratch re-init at each new query tile)."""
    paged, _ = _paged_inputs(bits, sq=6, lens=(19, 9))
    d = paged[0].shape[-1]
    y_ref = np.asarray(ops.paged_kv_cache_attention(
        *paged, d=d, window=window, impl="reference"))
    y_int = np.asarray(ops.paged_kv_cache_attention(
        *paged, d=d, window=window, q_block=8, impl="interpret"))
    np.testing.assert_allclose(y_int, y_ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bits", [4, 8])
def test_paged_sq_gt1_matches_contiguous_on_gathered_layout(bits):
    """Multi-token causal queries through the block table equal the
    contiguous quantized-KV attention over the gathered planes exactly
    (shared reference dataflow): the Sq>1 path changes how queries are
    batched, not what they compute."""
    paged, (k_cat, ksc_cat, v_cat, vsc_cat, pos_cat) = _paged_inputs(
        bits, sq=5, lens=(19, 11))
    q = paged[0]
    B, H, GS, d = q.shape
    T = k_cat.shape[1]
    y_p = np.asarray(ops.paged_kv_cache_attention(
        *paged, d=d, impl="reference"))

    fold = lambda a: a.transpose((0, 2, 1) + tuple(
        range(3, a.ndim))).reshape((B * H, T) + a.shape[3:])
    y_c = np.asarray(ops.kv_cache_attention(
        q.reshape(B * H, GS, d),
        fold(jnp.asarray(k_cat)), fold(jnp.asarray(ksc_cat)),
        fold(jnp.asarray(v_cat)), fold(jnp.asarray(vsc_cat)),
        jnp.repeat(paged[-1], H, 0),
        jnp.repeat(jnp.asarray(pos_cat), H, 0),
        d=d, impl="reference")).reshape(B, H, GS, d)
    np.testing.assert_array_equal(y_p, y_c)


def test_paged_sq_causality_within_suffix():
    """Each suffix query must see exactly the prefix plus the suffix
    tokens at positions <= its own: computing the same rows one
    query-position at a time (decode-style Sq=1 calls) must agree."""
    paged, _ = _paged_inputs(8, sq=4, G=2, lens=(17,), B=1)
    q, kp, ks, vp, vs, pos, tables, q_pos = paged
    d = q.shape[-1]
    y_all = np.asarray(ops.paged_kv_cache_attention(
        q, kp, ks, vp, vs, pos, tables, q_pos, d=d, impl="interpret"))
    for si in range(4):
        rows = [g * 4 + si for g in range(2)]
        y_one = np.asarray(ops.paged_kv_cache_attention(
            q[:, :, rows], kp, ks, vp, vs, pos, tables, q_pos[:, rows],
            d=d, impl="interpret"))
        np.testing.assert_allclose(y_one, y_all[:, :, rows],
                                   rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("window", [None, 8])
def test_paged_mixed_sq_lanes_match_single_lane_calls(window):
    """The fused continuous-batching step (ISSUE 6) batches lanes with
    *different* real query counts into one dispatch: chunk-prefill lanes
    carry Sq real rows, decode lanes 1 real row, the rest padded at
    q_pos=-1.  Every real row must equal the same query issued in a
    lane-shaped call of its own -- per-row position masking, not lane
    shape, decides what a query sees."""
    paged, _ = _paged_inputs(8, sq=4, lens=(19, 7))
    q, kp, ks, vp, vs, pos, tables, q_pos = paged
    d = q.shape[-1]
    qp = np.asarray(q_pos).copy()
    qp[1] = -1
    for g in range(2):
        qp[1, g * 4] = 6       # lane 1: one decode row per group (ln-1)
    qp = jnp.asarray(qp)
    rows = np.array([0, 4])    # lane 1's real rows
    for impl in ("reference", "interpret"):
        y = np.asarray(ops.paged_kv_cache_attention(
            q, kp, ks, vp, vs, pos, tables, qp, d=d, window=window,
            impl=impl))
        y0 = np.asarray(ops.paged_kv_cache_attention(
            q[0:1], kp, ks, vp, vs, pos, tables[0:1], qp[0:1], d=d,
            window=window, impl=impl))
        np.testing.assert_allclose(y[0], y0[0], rtol=2e-6, atol=2e-6)
        y1 = np.asarray(ops.paged_kv_cache_attention(
            q[1:2, :, rows], kp, ks, vp, vs, pos, tables[1:2],
            qp[1:2, rows], d=d, window=window, impl=impl))
        np.testing.assert_allclose(y[1][:, rows], y1[0],
                                   rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# Sliding-window boundaries (ISSUE 5): the kernel's window mask + the
# grid's dead-block skip across all impls
# ---------------------------------------------------------------------------

# bs=8 throughout: 8 = window == block_size, 6/13 = window % block_size
# != 0 (smaller and larger than a block), 4 = window < block_size
WINDOWS = [4, 6, 8, 13]


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("sq", [1, 5])
def test_paged_windowed_parity_all_impls(window, sq):
    """Windowed paged attention (Sq=1 decode and Sq>1 suffix prefill)
    agrees across reference | interpret (the pallas path runs the same
    kernel body on TPU) at every window/block alignment."""
    paged, _ = _paged_inputs(8, sq=sq, lens=(19, 9) if sq > 1 else (19, 7))
    d = paged[0].shape[-1]
    y_ref = np.asarray(ops.paged_kv_cache_attention(
        *paged, d=d, window=window, impl="reference"))
    y_int = np.asarray(ops.paged_kv_cache_attention(
        *paged, d=d, window=window, q_block=8 if sq > 1 else None,
        impl="interpret"))
    np.testing.assert_allclose(y_int, y_ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["reference", "interpret"])
@pytest.mark.parametrize("window", [4, 8])
def test_paged_window_edge_is_exclusive(impl, window):
    """q at position p attends exactly kv positions in (p - w, p]: the
    windowed result over the full pool equals the UNwindowed result
    over a pool whose out-of-window slots are invalidated by hand --
    if the kernel's edge were off by one either way, the two would
    differ at the boundary token."""
    paged, _ = _paged_inputs(8, lens=(19,), B=1)
    q, kp, ks, vp, vs, pos, tables, q_pos = paged
    d = q.shape[-1]
    y_w = np.asarray(ops.paged_kv_cache_attention(
        q, kp, ks, vp, vs, pos, tables, q_pos, d=d, window=window,
        impl=impl))
    edge = int(q_pos[0, 0]) - window          # last EXCLUDED position
    pos_masked = jnp.where(pos <= edge, -1, pos)
    y_m = np.asarray(ops.paged_kv_cache_attention(
        q, kp, ks, vp, vs, pos_masked, tables, q_pos, d=d, window=None,
        impl=impl))
    np.testing.assert_allclose(y_w, y_m, rtol=2e-6, atol=2e-6)
    # the boundary matters: including position `edge` changes the result
    pos_off = jnp.where(pos <= edge - 1, -1, pos)
    y_off = np.asarray(ops.paged_kv_cache_attention(
        q, kp, ks, vp, vs, pos_off, tables, q_pos, d=d, window=None,
        impl=impl))
    assert np.abs(y_off - y_w).max() > 1e-6, \
        "edge token contributed nothing -- boundary test is vacuous"


@pytest.mark.parametrize("sq", [1, 4])
def test_paged_q_pos_exactly_at_window_edges(sq):
    """Query positions sitting exactly at window-multiple boundaries
    (q_pos = w, and block-crossing suffixes): reference/interpret agree
    and rows whose window precisely covers one block see it."""
    w = 8
    paged, _ = _paged_inputs(8, sq=sq, lens=(w + sq,), B=1)
    q, kp, ks, vp, vs, pos, tables, q_pos = paged
    d = q.shape[-1]
    assert int(np.asarray(q_pos).min()) == w, np.asarray(q_pos)
    y_ref = np.asarray(ops.paged_kv_cache_attention(
        q, kp, ks, vp, vs, pos, tables, q_pos, d=d, window=w,
        impl="reference"))
    y_int = np.asarray(ops.paged_kv_cache_attention(
        q, kp, ks, vp, vs, pos, tables, q_pos, d=d, window=w,
        q_block=8 if sq > 1 else None, impl="interpret"))
    np.testing.assert_allclose(y_int, y_ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_paged_rolling_table_drops_dead_blocks_identically(impl):
    """The reclaim contract at the kernel level: once a block is fully
    out of every query's window, removing it from the table (the
    scheduler's rolling-window compaction -- pad entries point at the
    null block) must not change the output.  This is what makes
    out-of-window reclaim a pure memory-management change."""
    w, bs = 6, 8
    paged, _ = _paged_inputs(8, lens=(19,), B=1)
    q, kp, ks, vp, vs, pos, tables, q_pos = paged
    d = q.shape[-1]
    L = int(q_pos[0, 0]) + 1                     # 19 resident tokens
    # block j is dead for the (single) query at L-1 iff its last token
    # (j+1)*bs - 1 <= (L-1) - w; here block 0 (pos 0..7): 7 <= 12
    n_dead = max(0, (L - w) // bs)
    assert n_dead >= 1
    y_full = np.asarray(ops.paged_kv_cache_attention(
        q, kp, ks, vp, vs, pos, tables, q_pos, d=d, window=w, impl=impl))
    rolled = np.asarray(tables).copy()
    live = rolled[0, n_dead:].copy()
    rolled[0, :len(live)] = live                 # compact left
    rolled[0, len(live):] = 0                    # pad -> null block
    y_roll = np.asarray(ops.paged_kv_cache_attention(
        q, kp, ks, vp, vs, pos, jnp.asarray(rolled), q_pos, d=d,
        window=w, impl=impl))
    np.testing.assert_allclose(y_roll, y_full, rtol=2e-6, atol=2e-6)


def test_paged_null_block_and_inactive_lanes_return_zero():
    """Padded table entries point at the null block (pos -1) and padded
    batch lanes carry q_pos -1: both must contribute exactly 0 under
    reference AND interpret."""
    paged, _ = _paged_inputs(8)
    q, kp, ks, vp, vs, pos, tables, q_pos = paged
    d = q.shape[-1]
    # lane 1 fully inactive: null table + masked q rows
    tables = tables.at[1].set(0)
    q_pos = q_pos.at[1].set(-1)
    for impl in ("reference", "interpret"):
        y = np.asarray(ops.paged_kv_cache_attention(
            q, kp, ks, vp, vs, pos, tables, q_pos, d=d, impl=impl))
        np.testing.assert_array_equal(y[1], np.zeros_like(y[1]),
                                      err_msg=impl)
        assert np.abs(y[0]).max() > 0      # active lane still attends


def test_paged_block_order_is_table_order():
    """Swapping physical block ids (with the table updated to match)
    must not change the result: position comes from pool_pos, not from
    where a block happens to live in the pool."""
    paged, _ = _paged_inputs(8, lens=(19,), B=1)
    q, kp, ks, vp, vs, pos, tables, q_pos = paged
    d = q.shape[-1]
    y0 = np.asarray(ops.paged_kv_cache_attention(
        q, kp, ks, vp, vs, pos, tables, q_pos, d=d, impl="reference"))

    # swap physical blocks a<->b everywhere and patch the table
    a, b = int(tables[0, 0]), int(tables[0, 2])
    perm = np.arange(kp.shape[0])
    perm[[a, b]] = [b, a]
    swap = lambda arr: jnp.asarray(np.asarray(arr)[perm])
    tbl = np.asarray(tables).copy()
    mask_a, mask_b = tbl == a, tbl == b
    tbl[mask_a], tbl[mask_b] = b, a
    y1 = np.asarray(ops.paged_kv_cache_attention(
        q, swap(kp), swap(ks), swap(vp), swap(vs), swap(pos),
        jnp.asarray(tbl), q_pos, d=d, impl="reference"))
    np.testing.assert_array_equal(y0, y1)
