"""Compile-only rehearsals of the main path's Pallas kernels for a TPU v5e.

The TPU compiler is installed with JAX, and it compiles for a chip that
is described rather than attached, so these tests catch what interpret
mode cannot -- block shapes Mosaic refuses, kernels that overrun the
scoped VMEM limit -- at StableLM-3B's published widths, with no chip.
Nothing runs: they say nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bipolar
from repro.core.bipolar import BipolarTensor
from repro.kernels import ops

D_MODEL, D_FF, VOCAB = 2560, 6912, 50304       # StableLM-3B-4E1T
N_HEADS, D_HEAD = 32, 80
BLOCK_SIZE, KV_BITS = 16, 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


# (d_in, d_out, dual gate/up, fused residual) of each quantized linear
LINEARS = {
    "qkv": (D_MODEL, D_MODEL, False, False),
    "o_proj": (D_MODEL, D_MODEL, False, True),
    "gate_up": (D_MODEL, D_FF, True, False),
    "down": (D_FF, D_MODEL, False, True),
    "lm_head": (D_MODEL, VOCAB, False, False),
}


@pytest.mark.parametrize("m", [8, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("linear", sorted(LINEARS))
def test_fused_linear_compiles_for_v5e(one_chip, linear, m):
    k, n, dual, res = LINEARS[linear]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for w_bits in (3, 8):
        def fn(x, wp, ws, r):
            w = BipolarTensor(packed=wp, scale=ws, n_bits=w_bits,
                              shape=(n, k), pack_axis=1)
            return ops.ap_linear_fused(
                x, w, a_bits=8, w2=w if dual else None,
                act="silu" if dual else "none", residual=r, impl="pallas")

        text = _compile(
            fn, sds((m, k), jnp.bfloat16),
            sds((w_bits, n, bipolar.packed_words(k)), jnp.uint32),
            sds((n, 1), jnp.float32),
            sds((m, n), jnp.bfloat16) if res else None)
        assert "tpu_custom_call" in text, (linear, m, w_bits)


@pytest.mark.parametrize("batch,q_rows", [(8, 1), (1, 256), (16, 1)],
                         ids=["decode", "chunk256", "decode16"])
def test_paged_attention_compiles_for_v5e(one_chip, batch, q_rows):
    n_blocks, table = 513, 64
    dw = bipolar.packed_words(D_HEAD)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, kp, ks, vp, vs, pos, bt, qpos):
        return ops.paged_kv_cache_attention(q, kp, ks, vp, vs, pos, bt, qpos,
                                            d=D_HEAD, impl="pallas")

    planes = sds((n_blocks, BLOCK_SIZE, KV_BITS * N_HEADS * dw), jnp.uint32)
    scales = sds((n_blocks, BLOCK_SIZE, N_HEADS), jnp.float32)
    text = _compile(
        fn, sds((batch, N_HEADS, q_rows, D_HEAD), jnp.bfloat16),
        planes, scales, planes, scales,
        sds((n_blocks, BLOCK_SIZE), jnp.int32),
        sds((batch, table), jnp.int32), sds((batch, q_rows), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [1, 8])
def test_paged_decode_step_fits_beside_its_pool_on_v5e(one_chip, batch):
    """A TPU stores an array's last two dims in (8, 128) tiles.  Pool
    planes shaped ``(..., kv_bits, D/32)`` = (8, 3) took 43x their size
    there, and a one-request decode step asked for 32 GiB of
    temporaries.  With the pool's lane-dense token rows the whole decode
    step (StableLM-3B widths, depth cut to 2 layers) needs less
    temporary memory than the pool itself."""
    import dataclasses
    import numpy as np
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serving import engine as E

    cfg = dataclasses.replace(get_config("stablelm-3b"), n_layers=2)
    quant = dataclasses.replace(cfg.quant, kv_bits=KV_BITS)
    n_blocks, table = 1025, 8

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(lambda k: M.quantize_params(
        M.init_params(cfg, k), quant), jax.random.PRNGKey(0))
    pool = jax.eval_shape(lambda: M.init_caches(
        cfg, n_blocks, BLOCK_SIZE, quant=quant, paged=True))
    pool_bytes = sum(np.prod(x.shape) * x.dtype.itemsize
                     for x in jax.tree.leaves(pool))

    def step_cache(c):
        u = c["k"].shape[0]
        return dict(c, block_tables=jax.ShapeDtypeStruct(
                        (u, batch, table), jnp.int32),
                    length=jax.ShapeDtypeStruct((u, batch), jnp.int32),
                    block_offset=jax.ShapeDtypeStruct((u, batch), jnp.int32))

    caches = {"blocks": [step_cache(c) for c in pool["blocks"]]}
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    ops.set_impl("pallas")
    try:
        compiled = E.serve_program.lower(
            sds(params), sds({"tokens": tok, "positions": tok}),
            sds(caches), cfg, quant).compile()
    finally:
        ops.set_impl(None)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


# DeepSeek-MoE-16B: 64 routed experts of width 1408, top 6, over d 2048
MOE_E, MOE_K, MOE_D, MOE_F = 64, 6, 2048, 1408
MOE_LINEARS = {"gate_up": (MOE_D, MOE_F, True), "down": (MOE_F, MOE_D, False)}


@pytest.mark.parametrize("tokens", [16, 256], ids=["decode", "chunk256"])
@pytest.mark.parametrize("linear", sorted(MOE_LINEARS))
def test_grouped_moe_kernel_compiles_for_v5e(one_chip, linear, tokens):
    """The grouped expert kernel over the dropless dispatch's expert
    tiles of one step, 2-bit experts, as the serving call makes it (no
    live map)."""
    from repro.models.layers import moe_tile_rows
    k, n, dual = MOE_LINEARS[linear]
    bc = moe_tile_rows(tokens, MOE_K, MOE_E)
    n_t = min(tokens * MOE_K, -(-(tokens * MOE_K + MOE_E * (bc - 1)) // bc))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(x, wp, ws, rows, experts):
        w = BipolarTensor(packed=wp, scale=ws, n_bits=2,
                          shape=(MOE_E, n, k), pack_axis=2)
        return ops.ap_moe_tiles_linear(
            x, w, w2=w if dual else None, act="silu" if dual else "none",
            tile_rows=rows, tile_expert=experts, a_bits=8, impl="pallas")

    text = _compile(
        fn, sds((n_t * bc, k), jnp.bfloat16),
        sds((2, MOE_E, n, bipolar.packed_words(k)), jnp.uint32),
        sds((MOE_E, n, 1), jnp.float32), sds((n_t,), jnp.int32),
        sds((n_t,), jnp.int32))
    assert "moe_expert_linear" in text


def test_moe_decode_step_fits_beside_its_pool_on_v5e(one_chip):
    """DeepSeek-MoE-16B's decode step at published widths, depth cut to
    its dense layer and one expert layer, 16 lanes over the decode-heavy
    pool: it compiles with the grouped kernel, and its temporaries --
    one expert layer's working set, whatever the depth -- stay under
    the pool the whole 28-layer model holds."""
    import dataclasses
    import numpy as np
    from repro.configs import get_config
    from repro.models import model as M
    from repro.models.config import QuantConfig
    from repro.serving import engine as E

    cfg = dataclasses.replace(get_config("deepseek-moe-16b"), n_layers=2)
    quant = QuantConfig(w_bits=2, a_bits=8, kv_bits=KV_BITS)
    n_blocks, table, batch = 1281, 64, 16

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(lambda k: M.quantize_params(
        M.init_params(cfg, k), quant), jax.random.PRNGKey(0))
    pool = jax.eval_shape(lambda: M.init_caches(
        cfg, n_blocks, BLOCK_SIZE, quant=quant, paged=True))
    pool_bytes = sum(np.prod(x.shape) * x.dtype.itemsize
                     for x in jax.tree.leaves(pool))

    def step_cache(c, lead):
        return dict(c, block_tables=jax.ShapeDtypeStruct(
                        lead + (batch, table), jnp.int32),
                    length=jax.ShapeDtypeStruct(lead + (batch,), jnp.int32),
                    block_offset=jax.ShapeDtypeStruct(lead + (batch,),
                                                      jnp.int32))

    caches = {"prelude": [step_cache(c, ()) for c in pool["prelude"]],
              "blocks": [step_cache(c, (c["k"].shape[0],))
                         for c in pool["blocks"]]}
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    ops.set_impl("pallas")
    try:
        compiled = E.serve_program.lower(
            sds(params), sds({"tokens": tok, "positions": tok}),
            sds(caches), cfg, quant).compile()
    finally:
        ops.set_impl(None)
    assert "moe_expert_linear" in compiled.as_text()
    full_pool = pool_bytes * 28 // 2
    assert compiled.memory_analysis().temp_size_in_bytes < full_pool
