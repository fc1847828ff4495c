"""Composable pure-JAX layers: norm, RoPE, GQA attention, MLP, MoE.

Design: functional modules -- ``<layer>_init(key, cfg, ...) -> params`` and
``<layer>_apply(params, x, ...) -> y`` over plain dict pytrees.  Linear
weights are stored ``(d_out, d_in)`` ("NT" layout), matching the packed
APMM kernels, so serving-time quantization is a pure param transform:
replace the bf16 weight leaf with a :class:`BipolarTensor` and
``linear_apply`` dispatches to :func:`repro.kernels.ops.ap_linear`.

The decode KV cache has the same bit-level treatment (``kv_bits``):
``make_kv_cache`` allocates packed bipolar-INT bit planes + per-(token,
head) absmax scales, ``attention_apply`` packs new K/V on write and reads
through :func:`repro.kernels.ops.kv_cache_attention`, which dequantizes
inside the flash-attention kernel (pallas/interpret) or via jnp recovery
(reference).  The cache format is self-describing (bit width = plane-axis
length), so apply code needs no extra static config.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bipolar
from repro.core.bipolar import BipolarTensor
from repro.kernels import ops
from repro.kernels.ref import apply_act
from repro.distributed.sharding import constrain
from repro.models.config import ModelConfig

# attention switches to online-softmax KV chunking above this length
ATTN_CHUNK_THRESHOLD = 4096
ATTN_KV_CHUNK = 1024


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Linear / Embedding
# ---------------------------------------------------------------------------

def linear_init(key, d_in: int, d_out: int, dtype) -> dict:
    w = jax.random.normal(key, (d_out, d_in), jnp.float32)
    return {"w": (w / np.sqrt(d_in)).astype(dtype)}


def _epilogue(y: jax.Array, act: str, residual, dtype) -> jax.Array:
    """Post-GEMM epilogue in jnp, with the same ordering/cast points the
    fused kernel uses: activation in f32 on the dtype-cast GEMM output,
    residual added in the output dtype."""
    if act != "none":
        y = apply_act(y.astype(jnp.float32), act).astype(dtype)
    if residual is not None:
        y = y + residual.astype(dtype)
    return y


def _use_fused_linear(w, quant) -> bool:
    return (isinstance(w, BipolarTensor) and quant is not None
            and quant.enabled and quant.fused_linear)


def linear_apply(params: dict, x: jax.Array, *, quant=None,
                 act: str = "none", residual=None) -> jax.Array:
    """``y (..., N) = epi(x (..., K) @ W (N, K)^T)`` -- bf16 or
    arbitrary-precision, with an optional fused epilogue
    (``act in {none, silu, gelu}``, residual add).

    If the weight leaf is a :class:`BipolarTensor` (serving-time quantized
    params) the GEMM runs through the APMM path with on-the-fly activation
    quantization (paper §3.2/§4): the one-kernel fused linear
    (``quant.fused_linear``, activation quantize-pack in the GEMM
    prologue + in-kernel epilogue) or the unfused two-launch baseline.
    Both produce bit-identical outputs; the bf16 path applies the same
    epilogue in jnp.
    """
    w = params["w"]
    if _use_fused_linear(w, quant):
        return ops.ap_linear_fused(x, w, a_bits=quant.a_bits, act=act,
                                   residual=residual,
                                   variant=quant.variant, out_dtype=x.dtype,
                                   w_bits=quant.nested_bits)
    if isinstance(w, BipolarTensor):
        assert quant is not None and quant.enabled
        y = ops.ap_linear(x, w, a_bits=quant.a_bits,
                          variant=quant.variant, out_dtype=x.dtype,
                          w_bits=quant.nested_bits)
    else:
        y = jnp.einsum("...k,nk->...n", x, w.astype(x.dtype))
    return _epilogue(y, act, residual, x.dtype)


def embed_init(key, vocab: int, d_model: int, dtype) -> dict:
    return {"w": (jax.random.normal(key, (vocab, d_model), jnp.float32)
                  * 0.02).astype(dtype)}


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def norm_init(d: int, cfg: ModelConfig) -> dict:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * params["scale"] + params["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * params["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (standard / partial / M-RoPE)
# ---------------------------------------------------------------------------

def _rope_angles(positions: jax.Array, rot_dim: int, theta: float):
    """positions (..., S) -> cos/sin (..., S, rot_dim/2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                           / rot_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig):
    """Rotary embedding on ``x (B, S, H, D)``.

    ``positions``: ``(B, S)`` int32, or ``(3, B, S)`` for M-RoPE
    (temporal/height/width sections, qwen2-vl).  Only the leading
    ``rope_pct`` fraction of D rotates (stablelm/glm partial rotary).
    """
    d = x.shape[-1]
    rot = int(d * cfg.rope_pct)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    if cfg.mrope_sections is not None:
        assert positions.ndim == 3, "M-RoPE needs (3, B, S) positions"
        sec = cfg.mrope_sections
        assert sum(sec) == half, (sec, half)
        cos_parts, sin_parts = [], []
        lo = 0
        for axis, width in enumerate(sec):
            c, s = _rope_angles(positions[axis], rot, cfg.rope_theta)
            cos_parts.append(c[..., lo:lo + width])
            sin_parts.append(s[..., lo:lo + width])
            lo += width
        cos = jnp.concatenate(cos_parts, -1)[:, :, None, :]
        sin = jnp.concatenate(sin_parts, -1)[:, :, None, :]
    else:
        cos, sin = _rope_angles(positions, rot, cfg.rope_theta)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)
    return jnp.concatenate([out, x_pass], axis=-1) if rot < d else out


# ---------------------------------------------------------------------------
# Attention (GQA, causal, sliding-window, cross; direct + online-softmax)
# ---------------------------------------------------------------------------

def _read_quantized_kv(qg, ck, cks, cv, cvs, qp, kv_pos, *,
                       d, causal, window):
    """Attend over a packed bipolar KV cache: fold heads into batch and
    let the ops dispatch pick the dequant-on-read kernel
    (pallas/interpret) or the jnp recovery path (reference).

    ``qg (B, Hk, G, d)`` grouped queries; ``ck/cv (B, T, Hk, bits, Dw)``
    planes with ``cks/cvs (B, T, Hk, 1)`` scales; ``qp (B, G)`` /
    ``kv_pos (B, T)``.  Returns ``(B, Hk, G, d)``.
    """
    b, hk, gs, _ = qg.shape
    return ops.kv_cache_attention(
        qg.reshape(b * hk, gs, d),
        ops.fold_kv_heads(ck), ops.fold_kv_heads(cks),
        ops.fold_kv_heads(cv), ops.fold_kv_heads(cvs),
        jnp.repeat(qp, hk, 0), jnp.repeat(kv_pos, hk, 0),
        d=d, causal=causal, window=window).reshape(b, hk, gs, d)


def attention_init(key, cfg: ModelConfig, cross: bool = False) -> dict:
    d, dh = cfg.d_model, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    dt = _dtype(cfg)
    return {
        "wq": linear_init(kq, d, cfg.n_heads * dh, dt),
        "wk": linear_init(kk, d, cfg.n_kv_heads * dh, dt),
        "wv": linear_init(kv, d, cfg.n_kv_heads * dh, dt),
        "wo": linear_init(ko, cfg.n_heads * dh, d, dt),
    }


def _attn_core(q, k, v, q_pos, kv_pos, *, causal: bool,
               window: Optional[int], chunked: bool,
               score_bf16: bool = False):
    """Online-softmax GQA core.

    q: (B, Hkv, Sq, D) with Sq = groups*S folded; k/v: (B, Hkv, T, D);
    q_pos: (B, Sq) absolute positions; kv_pos: (B, T), negative = invalid.
    """
    b, hk, sq, d = q.shape
    t = k.shape[2]
    scale = 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32) * scale

    def mask_for(kp):  # kp: (B, Tc) -> (B, 1, Sq, Tc) additive mask
        valid = kp[:, None, None, :] >= 0
        if causal:
            valid &= kp[:, None, None, :] <= q_pos[:, None, :, None]
        if window is not None:
            valid &= kp[:, None, None, :] > q_pos[:, None, :, None] - window
        return jnp.where(valid, 0.0, -jnp.inf)

    if not chunked:
        s = jnp.einsum("bhqd,bhtd->bhqt", qf, k.astype(jnp.float32))
        s = s + mask_for(kv_pos)
        m = jnp.max(s, -1, keepdims=True)
        m = jnp.maximum(m, -1e30)  # fully-masked rows stay finite
        p = jnp.exp(s - m)
        o = jnp.einsum("bhqt,bhtd->bhqd", p, v.astype(jnp.float32))
        return o / jnp.maximum(p.sum(-1, keepdims=True), 1e-20)

    nc = -(-t // ATTN_KV_CHUNK)
    tc = nc * ATTN_KV_CHUNK
    pad = tc - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    ks = k.reshape(b, hk, nc, ATTN_KV_CHUNK, d).transpose(2, 0, 1, 3, 4)
    vs = v.reshape(b, hk, nc, ATTN_KV_CHUNK, d).transpose(2, 0, 1, 3, 4)
    ps = kv_pos.reshape(b, nc, ATTN_KV_CHUNK).transpose(1, 0, 2)
    # opt-in: pin the chunk axis unsharded so per-step dynamic-slice does
    # not reshard (see distributed.sharding.default_activation_rules)
    ks = constrain(ks, "attn_chunks")
    vs = constrain(vs, "attn_chunks")

    def step(carry, inp):
        m, l, acc = carry
        kc, vc, pc = inp
        s = jnp.einsum("bhqd,bhtd->bhqt", qf, kc.astype(jnp.float32))
        s = s + mask_for(pc)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        if score_bf16:      # halve probability-tensor traffic; m/l stay f32
            p = p.astype(jnp.bfloat16)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1, keepdims=True).astype(jnp.float32)
        acc = acc * alpha + jnp.einsum(
            "bhqt,bhtd->bhqd", p, vc.astype(p.dtype),
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    init = (jnp.full((b, hk, sq, 1), -1e30, jnp.float32),
            jnp.zeros((b, hk, sq, 1), jnp.float32),
            jnp.zeros((b, hk, sq, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, (ks, vs, ps))
    return acc / jnp.maximum(l, 1e-20)


def attention_apply(params: dict, x: jax.Array, cfg: ModelConfig, *,
                    positions: jax.Array,
                    kv_positions: Optional[jax.Array] = None,
                    kv_override=None,
                    cache: Optional[dict] = None,
                    cross_memory: Optional[jax.Array] = None,
                    causal: Optional[bool] = None,
                    quant=None, residual: Optional[jax.Array] = None):
    """GQA attention over ``x (B, S, d_model)``.

    * training / prefill: self-attention over the full sequence.
    * decode: ``cache`` = dict(k, v, pos, index); x is the new token(s),
      K/V are appended at ``index`` and attention runs over the cache.
    * cross: ``cross_memory (B, T, d)`` supplies K/V (enc-dec decoder).
    ``residual`` (the block input) fuses the residual add into the
    output projection's epilogue.  Returns ``(out, new_cache)``.
    """
    b, s, _ = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // hk
    causal = cfg.causal if causal is None else causal
    rope_pos = positions
    pos2d = positions[positions.ndim - 2] if positions.ndim == 3 else positions

    q = linear_apply(params["wq"], x, quant=quant).reshape(b, s, h, dh)
    kv_src = x if cross_memory is None else cross_memory
    t_src = kv_src.shape[1]
    k = linear_apply(params["wk"], kv_src, quant=quant).reshape(b, t_src, hk, dh)
    v = linear_apply(params["wv"], kv_src, quant=quant).reshape(b, t_src, hk, dh)

    if cross_memory is None:
        q = apply_rope(q, rope_pos, cfg)
        k = apply_rope(k, rope_pos if cache is None else rope_pos, cfg)

    new_cache = None
    quant_kv = None           # (k_packed, k_scale, v_packed, v_scale) folded
    if cache is not None and "block_tables" in cache:
        # paged decode / suffix prefill: the cache is a block pool shared
        # by every request (k/v (n_blocks, bs, kv_bits*H*Dw) token rows +
        # (n_blocks, bs, H) scales), addressed through this batch's block
        # table.  The ``s`` new tokens of row b land at slots
        # ``length[b] + i`` --
        # physically (table[slot // bs], slot % bs) -- then attention
        # runs through the table (ops.paged_kv_cache_attention) with the
        # suffix folded into the query axis; causality is by absolute
        # position, so the suffix sees the shared prefix blocks AND its
        # own just-written tokens in one pass.  Pad tokens (pos -1, from
        # pow2 length bucketing or inactive lanes) are *dropped* at the
        # scatter (routed out of bounds), so they can never touch the
        # null block or a live block's slots.
        n_blocks, blk, words = cache["k"].shape
        kv_bits = words // (hk * bipolar.packed_words(dh))
        bt, ln = cache["block_tables"], cache["length"]
        k_q, k_s = ops.quantize_kv(k, kv_bits)
        v_q, v_s = ops.quantize_kv(v, kv_bits)
        k_q, v_q = ops.kv_pool_rows(k_q), ops.kv_pool_rows(v_q)
        k_s, v_s = k_s[..., 0], v_s[..., 0]
        slot = ln[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # (B,s)
        valid = pos2d >= 0
        logical = jnp.where(valid, slot // blk, 0)
        # sliding-window reclaim: the table is a rolling window whose
        # entry j maps logical block j + block_offset (leading blocks
        # already returned to the pool); writes always target the live
        # suffix, so the index stays in range for every real token
        boff = cache.get("block_offset")
        if boff is not None:
            logical = logical - boff[:, None]
        entry = jnp.clip(logical, 0, bt.shape[1] - 1)
        valid_w = valid & (logical >= 0) & (logical < bt.shape[1])
        phys = jnp.take_along_axis(bt, entry, 1)
        phys = jnp.where(valid_w, phys, n_blocks)   # out of bounds -> drop
        off = slot % blk

        def wr(buf, new):
            return buf.at[phys, off].set(new.astype(buf.dtype), mode="drop")

        ck, cks = wr(cache["k"], k_q), wr(cache["k_scale"], k_s)
        cv, cvs = wr(cache["v"], v_q), wr(cache["v_scale"], v_s)
        cpos = wr(cache["pos"], pos2d.astype(jnp.int32))
        new_cache = dict(cache, k=ck, v=cv, k_scale=cks, v_scale=cvs,
                         pos=cpos)
        qg = q.reshape(b, s, hk, g, dh).transpose(0, 2, 3, 1, 4).reshape(
            b, hk, g * s, dh)
        qp = jnp.repeat(pos2d[:, None, :], g, 1).reshape(b, g * s)
        o = ops.paged_kv_cache_attention(
            qg, ck, cks, cv, cvs, cpos, bt, qp,
            d=dh, causal=causal, window=cfg.window)
        o = o.reshape(b, hk, g, s, dh).transpose(0, 3, 1, 2, 4).reshape(
            b, s, h * dh).astype(x.dtype)
        return linear_apply(params["wo"], o, quant=quant,
                            residual=residual), new_cache
    if cache is not None:
        kv_bits = cache["k"].shape[-2] if "k_scale" in cache else None
        cache_len = cache["k"].shape[1]
        if s > cache_len:
            # SWA prefill longer than the ring: attend over the in-sequence
            # K/V directly, then store only the last `window` entries
            # (slot order is irrelevant -- masking is by absolute position).
            tail_k, tail_v = k[:, -cache_len:], v[:, -cache_len:]
            tail_p = pos2d[:, -cache_len:].astype(jnp.int32)
            new_cache = dict(cache, pos=tail_p,
                             index=jnp.zeros_like(cache["index"]))
            if kv_bits:
                new_cache["k"], new_cache["k_scale"] = \
                    ops.quantize_kv(tail_k, kv_bits)
                new_cache["v"], new_cache["v_scale"] = \
                    ops.quantize_kv(tail_v, kv_bits)
            else:
                new_cache["k"] = tail_k.astype(cache["k"].dtype)
                new_cache["v"] = tail_v.astype(cache["v"].dtype)
            kv_pos = pos2d
        else:
            # write new K/V at per-slot ring positions (continuous batching:
            # each batch row advances independently)
            idx = cache["index"]                       # (B,) int32

            def row_write(buf, new, i):
                start = (i,) + (0,) * (new.ndim - 1)
                return jax.lax.dynamic_update_slice(buf, new, start)

            wr = jax.vmap(row_write)
            if kv_bits:
                k_q, k_s = ops.quantize_kv(k, kv_bits)
                v_q, v_s = ops.quantize_kv(v, kv_bits)
                ck, cks = wr(cache["k"], k_q, idx), wr(cache["k_scale"], k_s, idx)
                cv, cvs = wr(cache["v"], v_q, idx), wr(cache["v_scale"], v_s, idx)
                cpos = wr(cache["pos"], pos2d.astype(jnp.int32), idx)
                new_cache = dict(cache, k=ck, v=cv, k_scale=cks, v_scale=cvs,
                                 pos=cpos, index=(idx + s) % cache_len)
                quant_kv = (ck, cks, cv, cvs)
                kv_pos = cpos
            else:
                ck = wr(cache["k"], k.astype(cache["k"].dtype), idx)
                cv = wr(cache["v"], v.astype(cache["v"].dtype), idx)
                cpos = wr(cache["pos"], pos2d.astype(jnp.int32), idx)
                new_cache = dict(cache, k=ck, v=cv, pos=cpos,
                                 index=(idx + s) % cache_len)
                k, v, kv_pos = ck, cv, cpos
    elif cross_memory is not None:
        kv_pos = (kv_positions if kv_positions is not None
                  else jnp.broadcast_to(jnp.arange(t_src), (b, t_src)))
        causal = False
    else:
        kv_pos = pos2d

    # fold the GQA group into the query-sequence axis: (B, Hkv, G*S, D)
    qg = q.reshape(b, s, hk, g, dh).transpose(0, 2, 3, 1, 4).reshape(
        b, hk, g * s, dh)
    qp = jnp.repeat(pos2d[:, None, :], g, 1).reshape(b, g * s)
    if quant_kv is not None:
        ck, cks, cv, cvs = quant_kv
        o = _read_quantized_kv(qg, ck, cks, cv, cvs, qp, kv_pos,
                               d=dh, causal=causal, window=cfg.window)
    else:
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        # decode (s==1) is a skinny GEMV -- direct; long train/prefill
        # sequences use the online-softmax KV-chunked path to bound the
        # score transient
        chunked = (s > 1) and (k.shape[1] > ATTN_CHUNK_THRESHOLD)
        o = _attn_core(qg, kt, vt, qp, kv_pos, causal=causal,
                       window=cfg.window, chunked=chunked,
                       score_bf16=cfg.attn_score_bf16)
    o = o.reshape(b, hk, g, s, dh).transpose(0, 3, 1, 2, 4).reshape(
        b, s, h * dh).astype(x.dtype)
    out = linear_apply(params["wo"], o, quant=quant, residual=residual)
    return out, new_cache


def _write_cross_slots(cache: dict, ck, cks, cv, cvs, kv_pos) -> dict:
    """Scatter one batch's projected+packed cross-K/V into its slot-pool
    rows.  ``cache`` leaves are ``(rows, cap, ...)`` with ``slots (B,)``
    ids (-1 = padded lane, write dropped).  Rows are written full-width:
    slots past the batch's encoder length carry pos -1 and stay masked,
    so a reused slot cannot leak a freed request's memory."""
    slots = cache["slots"]
    rows, cap = cache["k"].shape[0], cache["k"].shape[1]
    t = ck.shape[1]
    idx = jnp.where(slots >= 0, slots, rows)       # OOB -> dropped

    def pad_t(a, value=0):
        if cap == t:
            return a
        pad = [(0, 0)] * a.ndim
        pad[1] = (0, cap - t)
        return jnp.pad(a, pad, constant_values=value)

    def wr(key, new, value=0):
        buf = cache[key]
        return buf.at[idx].set(pad_t(new, value).astype(buf.dtype),
                               mode="drop")

    return dict(cache, k=wr("k", ck), k_scale=wr("k_scale", cks),
                v=wr("v", cv), v_scale=wr("v_scale", cvs),
                pos=wr("pos", kv_pos, -1))


def cross_attention_apply(params: dict, x: jax.Array, cfg: ModelConfig, *,
                          memory: Optional[jax.Array] = None,
                          cache: Optional[dict] = None,
                          quant=None, residual: Optional[jax.Array] = None):
    """Enc-dec cross-attention (no RoPE, non-causal).

    Prefill/train: ``memory (B, T, d)`` given -> project K/V (and fill
    ``cache`` if provided).  Decode: ``memory=None`` -> replay cached
    projected K/V (the encoder is NOT re-run per token).  A quantized
    cache (``k_scale`` present, :func:`make_cross_cache` with
    ``kv_bits``) stores packed bipolar planes on fill and decodes
    through :func:`repro.kernels.ops.kv_cache_attention`.

    Paged serving hands the cache as *slot-pool rows*: leaves are
    ``(n_slots+1, cap, ...)`` and ``cache["slots"] (B,)`` maps batch
    lanes to rows (slot 0 reserved null, -1 = padded lane).  Prefill
    scatters this request's packed planes into its row; decode gathers
    the batch's rows back.  Returns ``(out, new_cache)``.
    """
    b, s, _ = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // hk
    slotted = cache is not None and "slots" in cache
    q = linear_apply(params["wq"], x, quant=quant).reshape(b, s, h, dh)
    qg = q.reshape(b, s, hk, g, dh).transpose(0, 2, 3, 1, 4).reshape(
        b, hk, g * s, dh)
    qp = jnp.zeros((b, g * s), jnp.int32)   # positions unused (non-causal)
    quant_kv = None           # (k, k_scale, v, v_scale) packed planes
    if memory is not None:
        t = memory.shape[1]
        k = linear_apply(params["wk"], memory, quant=quant).reshape(
            b, t, hk, dh)
        v = linear_apply(params["wv"], memory, quant=quant).reshape(
            b, t, hk, dh)
        kv_pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        new_cache = None
        if cache is not None:
            kv_bits = cache["k"].shape[-2] if "k_scale" in cache else None
            if kv_bits:
                # attend through the quantized planes even in prefill so
                # every position sees the same precision as decode (the
                # recompute-reproduces-identical-tokens invariant)
                ck, cks = ops.quantize_kv(k, kv_bits)
                cv, cvs = ops.quantize_kv(v, kv_bits)
                quant_kv = (ck, cks, cv, cvs)
                if slotted:
                    new_cache = _write_cross_slots(cache, ck, cks, cv,
                                                   cvs, kv_pos)
                else:
                    new_cache = dict(cache, k=ck, v=cv, k_scale=cks,
                                     v_scale=cvs, pos=kv_pos)
            else:
                assert not slotted, \
                    "slot-pool cross caches store packed planes: the " \
                    "paged engine requires kv_bits for audio archs"
                new_cache = dict(cache, k=k.astype(cache["k"].dtype),
                                 v=v.astype(cache["v"].dtype), pos=kv_pos)
    else:
        assert cache is not None, "cross decode needs a filled cross cache"
        if slotted:
            # gather this batch's rows; padded lanes (-1) read the null
            # slot, whose pos stays -1 -> fully masked, contributes 0
            rows = cache["k"].shape[0]
            safe = jnp.clip(cache["slots"], 0, rows - 1)
            quant_kv = (cache["k"][safe], cache["k_scale"][safe],
                        cache["v"][safe], cache["v_scale"][safe])
            kv_pos = cache["pos"][safe]
            new_cache = cache
        elif "k_scale" in cache:
            new_cache, kv_pos = cache, cache["pos"]
            quant_kv = (cache["k"], cache["k_scale"],
                        cache["v"], cache["v_scale"])
        else:
            new_cache, kv_pos = cache, cache["pos"]
            k, v = cache["k"], cache["v"]
    if quant_kv is not None:
        o = _read_quantized_kv(qg, *quant_kv, qp, kv_pos,
                               d=dh, causal=False, window=None)
    else:
        chunked = (s > 1) and (k.shape[1] > ATTN_CHUNK_THRESHOLD)
        o = _attn_core(qg, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                       qp, kv_pos, causal=False, window=None, chunked=chunked)
    o = o.reshape(b, hk, g, s, dh).transpose(0, 3, 1, 2, 4).reshape(
        b, s, h * dh).astype(x.dtype)
    return linear_apply(params["wo"], o, quant=quant,
                        residual=residual), new_cache


def make_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                  kv_bits: Optional[int] = None, paged: bool = False) -> dict:
    """Decode KV cache; for SWA archs the cache is a ring of ``window``.

    ``index`` is per batch row: under continuous batching each slot
    advances independently.  With ``kv_bits`` set (defaults to
    ``cfg.kv_bits``; ``QuantConfig.kv_bits`` overrides via
    ``config.effective_kv_bits`` in ``model.init_caches``) the cache
    stores packed bipolar-INT bit planes ``(B, L, H, kv_bits, D/32)``
    uint32 + per-(token, head) absmax scales: ``kv_bits`` bits per cache
    element instead of 16, dequantized on read (repro.kernels.ops).
    ``paged``: the serving pool's layout (``batch`` = blocks, ``max_len``
    = block size): planes as lane-dense token rows ``(B, L, kv_bits * H
    * D/32)`` (``ops.kv_pool_rows``) and scales ``(B, L, H)``.
    """
    kv_bits = cfg.kv_bits if kv_bits is None else kv_bits
    length = min(max_len, cfg.window) if cfg.window else max_len
    shape = (batch, length, cfg.n_kv_heads, cfg.head_dim)
    cache = {
        "pos": jnp.full((batch, length), -1, jnp.int32),
        "index": jnp.zeros((batch,), jnp.int32),
    }
    if kv_bits:
        assert 1 <= kv_bits <= 8, f"kv_bits={kv_bits} outside 1..8"
        dw = bipolar.packed_words(cfg.head_dim)
        if paged:
            packed = shape[:2] + (kv_bits * cfg.n_kv_heads * dw,)
            scale = shape[:3]
        else:
            packed = shape[:3] + (kv_bits, dw)
            scale = shape[:3] + (1,)
        cache["k"] = jnp.zeros(packed, jnp.uint32)
        cache["v"] = jnp.zeros(packed, jnp.uint32)
        cache["k_scale"] = jnp.zeros(scale, jnp.float32)
        cache["v_scale"] = jnp.zeros(scale, jnp.float32)
    else:
        cache["k"] = jnp.zeros(shape, dtype)
        cache["v"] = jnp.zeros(shape, dtype)
    return cache


def make_cross_cache(cfg: ModelConfig, batch: int, enc_len: int, dtype,
                     kv_bits: Optional[int] = None) -> dict:
    """Enc-dec cross-K/V cache (projected encoder memory, replayed every
    decode step).  With ``kv_bits`` the cache stores packed bipolar-INT
    planes + per-(token, head) scales, same format as the self-attention
    KV cache -- the cross stream is read every decode step, so its HBM
    traffic scales with bits/element too."""
    kv_bits = cfg.kv_bits if kv_bits is None else kv_bits
    shape = (batch, enc_len, cfg.n_kv_heads, cfg.head_dim)
    cache = {"pos": jnp.full((batch, enc_len), -1, jnp.int32)}
    if kv_bits:
        assert 1 <= kv_bits <= 8, f"kv_bits={kv_bits} outside 1..8"
        packed = shape[:3] + (kv_bits, bipolar.packed_words(cfg.head_dim))
        cache["k"] = jnp.zeros(packed, jnp.uint32)
        cache["v"] = jnp.zeros(packed, jnp.uint32)
        cache["k_scale"] = jnp.zeros(shape[:3] + (1,), jnp.float32)
        cache["v_scale"] = jnp.zeros(shape[:3] + (1,), jnp.float32)
    else:
        cache["k"] = jnp.zeros(shape, dtype)
        cache["v"] = jnp.zeros(shape, dtype)
    return cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    dt = _dtype(cfg)
    p = {"w_up": linear_init(k1, d, f, dt), "w_down": linear_init(k2, f, d, dt)}
    if cfg.act == "silu":
        p["w_gate"] = linear_init(k3, d, f, dt)
    return p


def mlp_apply(params: dict, x: jax.Array, cfg: ModelConfig, quant=None,
              residual: Optional[jax.Array] = None):
    """SwiGLU / GELU MLP.  Quantized + ``quant.fused_linear``: SwiGLU's
    gate and up projections run as ONE dual-GEMM fused-linear launch
    (shared quantized A-tile stream, ``silu(gate) * up`` fused before
    the output write) and the down projection fuses the block residual
    into its epilogue.  ``residual`` (the block input) is added to the
    down projection's output."""
    if cfg.act == "silu":
        if _use_fused_linear(params["w_up"]["w"], quant):
            h = ops.ap_linear_fused(
                x, params["w_gate"]["w"], w2=params["w_up"]["w"],
                a_bits=quant.a_bits, act="silu", variant=quant.variant,
                out_dtype=x.dtype, w_bits=quant.nested_bits)
        else:
            up = linear_apply(params["w_up"], x, quant=quant)
            gate = linear_apply(params["w_gate"], x, quant=quant)
            h = (jax.nn.silu(gate.astype(jnp.float32))
                 * up.astype(jnp.float32)).astype(x.dtype)
    else:
        h = linear_apply(params["w_up"], x, quant=quant, act="gelu")
    return linear_apply(params["w_down"], h, quant=quant,
                        residual=residual)


# ---------------------------------------------------------------------------
# MoE (top-k routing, capacity dispatch via segment-sum, optional shared)
# ---------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    dt = _dtype(cfg)
    scale = 1.0 / np.sqrt(d)
    p = {
        "router": {"w": (jax.random.normal(kr, (e, d)) * scale
                         ).astype(jnp.float32)},
        "w_up": (jax.random.normal(k1, (e, f, d)) * scale).astype(dt),
        "w_gate": (jax.random.normal(k2, (e, f, d)) * scale).astype(dt),
        "w_down": (jax.random.normal(k3, (e, d, f)) / np.sqrt(f)).astype(dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks, cfg, d_ff=cfg.n_shared_experts * f)
    return p


def _expert_quantize(x_eck, a_bits: int):
    """Per-(expert, row) activation quantization for the expert GEMMs:
    computed ONCE and shared by the gate and up projections (the
    reference-dataflow analogue of the fused kernel's single A-tile
    stream).

    The scale/divide/round chain runs in f32 (single rounding from the
    materialized input).  A native-bf16 chain is NOT compilation-stable:
    XLA's excess-precision pass elides the f32->bf16->f32 converts
    between consecutive bf16 ops inside a fused graph, so the rounded
    integers depend on the surrounding jit context -- the f32 chain has
    no narrowing converts to elide, which is what keeps the legacy path
    bit-identical to the grouped kernel under every compilation."""
    from repro.core import bipolar as bp
    xf = x_eck.astype(jnp.float32)
    sx = bp.absmax_scale(xf, a_bits, axis=-1)             # (E, C, 1) f32
    return bp.quantize_values(xf, a_bits, sx), sx         # (E, C, K) int32


def _expert_matmul(w, x_eck, quant=None, pre=None, out_dtype=None):
    """Batched per-expert NT GEMM: ``(E, C, K) x (E, N, K) -> (E, C, N)``.

    When ``w`` is a :class:`BipolarTensor` (packed ``(n, E, N, Kw)``, scale
    ``(E, N, 1)``), the GEMM runs the fused-APMM formulation batched over
    E: unpack-and-recover weights to bipolar integers in-registers,
    quantize activations per (e, c) row (or reuse ``pre`` = the shared
    ``_expert_quantize`` result), integer einsum, closed-form K-pad
    correction, scale outer product.  Bit-exact with the 2D APMM path.
    ``out_dtype`` overrides the output cast (``jnp.float32`` = hand the
    undegraded f32 dequant to a fused epilogue, the dual-GEMM pattern).
    """
    from repro.core import bipolar as bp
    od = out_dtype if out_dtype is not None else x_eck.dtype
    if isinstance(w, BipolarTensor):
        nested = getattr(quant, "nested_bits", None)
        if nested is not None:
            w = bp.nested_slice(w, nested)
        kp = w.packed.shape[-1] * bp.PACK_WIDTH
        k = w.shape[-1]
        planes = bp.unpack_planes(w.packed, -1, kp)       # (n, E, N, Kp)
        vals = bp.recover(planes, w.n_bits)               # pads -> +maxw
        xq, sx = pre if pre is not None \
            else _expert_quantize(x_eck, quant.a_bits)
        if kp > k:  # pad activations with -maxa (all-zero-bit convention)
            xq = jnp.pad(xq, ((0, 0), (0, 0), (0, kp - k)),
                         constant_values=-bp.max_value(quant.a_bits))
        y = jnp.einsum("eck,enk->ecn", xq, vals,
                       preferred_element_type=jnp.int32)
        y = y + (kp - k) * bp.max_value(quant.a_bits) * bp.max_value(w.n_bits)
        y = y.astype(jnp.float32) * sx * w.scale[:, None, :, 0]
        return y.astype(od)
    return jnp.einsum("eck,enk->ecn", x_eck, w.astype(x_eck.dtype)).astype(od)


MOE_DISPATCH_GROUPS = 32   # static token-group count (per-group capacity)

# Module flag: False forces the legacy batched-over-E expert path even for
# quantized weights -- the pre-rewire oracle for the engine token-identity
# test and the BENCH_moe baseline.  The grouped kernel is the default.
GROUPED_MOE = True


def _route(xf, router_w, cfg: ModelConfig):
    """Softmax router in f32: ``(probs (..., E), top_p (..., k), top_e
    (..., k))``; the top-k weights are renormalized to sum to 1 only
    where ``cfg.norm_topk_prob``."""
    logits = jnp.einsum("...d,ed->...e", xf, router_w)
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_e


def moe_tile_rows(t: int, k: int, e: int) -> int:
    """Rows of one expert tile of the dropless dispatch of ``t`` rows:
    8 while the step averages at most 8 assignments an expert (decode),
    else 32, so padded steps walk fewer dead tiles."""
    return 8 if t * k <= 8 * e else 32


def _dropless_experts(params: dict, xt, live, top_p, top_e,
                      cfg: ModelConfig, quant):
    """Routed experts of the live rows of ``xt (t, d)``, no assignment
    dropped: ``(y (t, d), stats)``.

    The live assignments are sorted by expert and each expert's run is
    padded to whole tiles of ``bc`` rows (``moe_tile_rows``), so every
    tile belongs to one expert and the grouped kernel computes only
    tiles that hold a live row.  An expert takes at most one assignment
    per token (top-k experts are distinct), so no bound on an expert's
    rows is needed; the static layout holds ``t * k`` assignments in
    ``T`` tiles.  Rows with ``live`` false (pad tokens) are neither
    routed nor counted; their outputs are zero."""
    t, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k
    bc = moe_tile_rows(t, k, e)
    n_t = min(t * k, -(-(t * k + e * (bc - 1)) // bc))
    flat_e = top_e.reshape(t * k)
    ok = jnp.repeat(live, k)                                    # (t*k,)
    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32) * ok[:, None]
    counts = oh.sum(0)                                          # (E,)
    rank = jnp.take_along_axis(jnp.cumsum(oh, 0) - oh, flat_e[:, None],
                               1)[:, 0]
    tiles = -(-counts // bc)
    tile_end = jnp.cumsum(tiles)                                # (E,)
    tile_start = tile_end - tiles
    # each live assignment's row; pad assignments go to the sink row
    row = jnp.where(ok, tile_start[flat_e] * bc + rank, n_t * bc)
    src = jnp.full(n_t * bc + 1, t, jnp.int32).at[row].set(
        jnp.arange(t * k, dtype=jnp.int32) // k)[:n_t * bc]
    disp = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], 0)[src]
    ids = jnp.arange(n_t, dtype=jnp.int32)
    tile_e = jnp.minimum(jnp.searchsorted(tile_end, ids, side="right"),
                         e - 1).astype(jnp.int32)
    tile_rows = jnp.where(ids < tile_end[-1],
                          jnp.clip(counts[tile_e]
                                   - (ids - tile_start[tile_e]) * bc, 0, bc),
                          0).astype(jnp.int32)

    if isinstance(params["w_up"], BipolarTensor) and GROUPED_MOE:
        # grouped kernel: one launch for gate+up (dual-GEMM, shared
        # quantized A-stream), one for down
        h = ops.ap_moe_tiles_linear(
            disp, params["w_gate"], w2=params["w_up"], tile_rows=tile_rows,
            tile_expert=tile_e, a_bits=quant.a_bits, act="silu",
            variant=quant.variant, out_dtype=xt.dtype,
            w_bits=quant.nested_bits)
        out = ops.ap_moe_tiles_linear(
            h, params["w_down"], tile_rows=tile_rows, tile_expert=tile_e,
            a_bits=quant.a_bits, variant=quant.variant, out_dtype=xt.dtype,
            w_bits=quant.nested_bits)                           # (R, d)
    else:
        # the batched expert path on each tile's own copy of its
        # expert's weights (the grouped kernel's oracle, and float
        # experts)
        out = _batched_experts(
            {n: (bipolar.take_experts(params[n], tile_e)
                 if isinstance(params[n], BipolarTensor)
                 else params[n][tile_e])
             for n in ("w_gate", "w_up", "w_down")},
            disp.reshape(n_t, bc, d), quant).reshape(n_t * bc, d)

    out = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)], 0)
    y = out[row] * (top_p.reshape(t * k) * ok)[:, None].astype(out.dtype)
    y = y.reshape(t, k, d).sum(1)
    stats = {"load": counts,
             "dropped": ok.sum(dtype=jnp.int32) - counts.sum(),
             "capacity": jnp.int32(n_t * bc),
             "computed": tiles.sum() * bc,
             "touched": (counts > 0).sum(dtype=jnp.int32)}
    return y, stats


def _batched_experts(w: dict, disp_e, quant):
    """Expert SwiGLU over ``disp_e (E', C, d)`` with batched per-expert
    GEMMs on ``w`` (each leaf ``(E', ...)``) -- no grouped kernel."""
    if isinstance(w["w_up"], BipolarTensor):
        # legacy batched-over-E oracle for the grouped kernel: gate and
        # up share one quantized-activation stream, the dual epilogue
        # composes in f32.  optimization_barrier pins the bf16
        # materialization points the kernel pins physically (its HBM
        # operand/result round-trips) -- without them XLA's excess-
        # precision pass elides the f32->bf16->f32 converts between
        # stages in a fused graph and the two paths bit-diverge
        disp_e = jax.lax.optimization_barrier(disp_e)
        pre = _expert_quantize(disp_e, quant.a_bits)
        gate = _expert_matmul(w["w_gate"], disp_e, quant, pre,
                              out_dtype=jnp.float32)
        up = _expert_matmul(w["w_up"], disp_e, quant, pre,
                            out_dtype=jnp.float32)
        h = jax.lax.optimization_barrier(
            (jax.nn.silu(gate) * up).astype(disp_e.dtype))
        return jax.lax.optimization_barrier(
            _expert_matmul(w["w_down"], h, quant))
    # dense (unquantized) fallback -- kept barrier-free: the float path
    # trains, and optimization_barrier has no grad rule
    up = _expert_matmul(w["w_up"], disp_e, quant)
    gate = _expert_matmul(w["w_gate"], disp_e, quant)
    h = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
         ).astype(disp_e.dtype)
    return _expert_matmul(w["w_down"], h, quant)


def moe_apply(params: dict, x: jax.Array, cfg: ModelConfig, quant=None,
              live: Optional[jax.Array] = None):
    """Top-k MoE over ``x (B, S, d)``.

    Serving (``live (B, S)`` bool given, false on pad tokens) is
    *dropless*: ``_dropless_experts`` routes every live token to all its
    top-k experts and no pad token to any.  Without ``live`` (training
    and cacheless forwards) the dispatch is capacity-bounded: per-group
    capacity ``ceil(k * T_g * capacity_factor / E)`` assignments an
    expert, the rest dropped, which keeps the dispatch's shape
    independent of the routing for SPMD partitioning.

    *Grouped* capacity dispatch: tokens are split into G static groups with
    per-group capacity (= per-device capacity at scale).  The dispatch
    scatter and the position cumsum are then *batched over G*, which SPMD
    partitions along the group axis -- the flat global scatter was
    "involuntarily replicated" by XLA, costing ~1.4 TiB of all-reduce per
    MoE layer on the jamba-398B train cell (EXPERIMENTS.md §Perf iter 3).
    Memory is O(G * E * C_g * d) = O(k*T*cf*d).

    Quantized experts run through the grouped kernel
    (``ops.ap_moe_tiles_linear`` / ``ops.ap_moe_expert_linear``: one
    launch per projection stage, gate+up fused dual-GEMM, scalar-
    prefetched live-row counts skipping empty tiles) -- token-identical
    to the legacy batched ``_expert_matmul`` path (``GROUPED_MOE =
    False``), which remains the dense fallback.

    Returns ``(y, aux, stats)``; ``stats`` carries per-layer telemetry
    -- ``load (E,)`` assignments kept per expert, ``dropped ()``
    assignments lost to the capacity bound, ``capacity ()`` dispatch
    slots, ``computed ()`` rows the grouped kernel computes (live row
    tiles, tile padding included), ``touched ()`` experts holding a
    kept assignment -- all int32, computed from the routing (XLA
    dead-code-eliminates them when the caller drops ``stats``).
    """
    if live is not None:
        b, s, d = x.shape
        xt = x.reshape(b * s, d)
        probs, top_p, top_e = _route(xt.astype(jnp.float32),
                                     params["router"]["w"], cfg)
        y, stats = _dropless_experts(params, xt, live.reshape(b * s),
                                     top_p, top_e, cfg, quant)
        if "shared" in params:
            y = y + mlp_apply(params["shared"], xt, cfg, quant=quant)
        return y.reshape(b, s, d), jnp.zeros((), jnp.float32), stats
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    # grouping pays when groups are token-heavy (train/prefill); for tiny
    # decode batches a flat dispatch avoids XLA replicating the expert
    # weights to satisfy group-sharded operands (EXPERIMENTS.md §Perf A4)
    if t >= 4096:
        g = next(gg for gg in (MOE_DISPATCH_GROUPS, 16, 8, 4, 2, 1)
                 if t % gg == 0)
    else:
        g = 1
    tg = t // g
    # capacity never needs to exceed the group's total routed assignments
    # (tg*k): with tiny decode batches and a generous capacity_factor the
    # ceil formula would dispatch mostly-empty rows the expert GEMM then
    # pays for -- the clamp cannot drop a token (pos < tg*k always), it
    # only removes rows that could never hold one
    cap = min(int(np.ceil(k * tg * cfg.capacity_factor / e)), tg * k)
    xt = x.reshape(t, d)
    xg = x.reshape(g, tg, d)

    probs, top_p, top_e = _route(xg.astype(jnp.float32),
                                 params["router"]["w"], cfg)    # (G, Tg, k)

    flat_e = top_e.reshape(g, tg * k)                           # (G, Tg*k)
    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)             # (G, Tg*k, E)
    pos = (jnp.cumsum(oh, axis=1) - oh)                         # count before
    pos = jnp.take_along_axis(pos, flat_e[..., None], 2)[..., 0]
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, e * cap)         # (G, Tg*k)

    x_rep = jnp.repeat(xg, k, axis=1)                           # (G, Tg*k, d)
    disp = jax.vmap(
        lambda xr, sl: jax.ops.segment_sum(xr, sl, num_segments=e * cap + 1)
    )(x_rep, slot)[:, :e * cap]
    disp = disp.reshape(g, e, cap, d).astype(x.dtype)
    disp = constrain(disp, "moe_dispatch")
    # fold groups into capacity for the expert GEMMs: (E, G*C, d)
    disp_e = disp.transpose(1, 0, 2, 3).reshape(e, g * cap, d)

    # kept assignments per (group, expert) -- drives both the grouped
    # kernel's tile-skip prefetch and the capacity telemetry
    counts = (oh * keep[..., None].astype(jnp.int32)).sum(1)    # (G, E)
    counts_e = counts.T                                         # (E, G)

    quantized = isinstance(params["w_up"], BipolarTensor)
    if quantized and GROUPED_MOE:
        # grouped kernel: one launch for gate+up (dual-GEMM, shared
        # quantized A-stream), one for down; scalar-prefetched counts
        # skip capacity tiles with no live tokens
        h = ops.ap_moe_expert_linear(
            disp_e, params["w_gate"], w2=params["w_up"], counts=counts_e,
            a_bits=quant.a_bits, act="silu", variant=quant.variant,
            out_dtype=x.dtype, w_bits=quant.nested_bits)
        out = ops.ap_moe_expert_linear(
            h, params["w_down"], counts=counts_e, a_bits=quant.a_bits,
            variant=quant.variant, out_dtype=x.dtype,
            w_bits=quant.nested_bits)                           # (E, G*C, d)
    else:
        out = _batched_experts(params, disp_e, quant)           # (E, G*C, d)

    out_g = out.reshape(e, g, cap, d).transpose(1, 0, 2, 3)     # (G, E, C, d)
    if g > 1:
        # bring expert outputs back token-local BEFORE the combine gather
        # (all-to-all instead of a model-axis replicating all-gather)
        out_g = constrain(out_g, "moe_combine")
    out_flat = jnp.concatenate(
        [out_g.reshape(g, e * cap, d),
         jnp.zeros((g, 1, d), out.dtype)], 1)
    y = jnp.take_along_axis(out_flat, slot[..., None], 1)
    y = y * (top_p.reshape(g, tg * k)[..., None]
             * keep[..., None]).astype(out.dtype)
    y = y.reshape(g, tg, k, d).sum(2).reshape(t, d)

    if "shared" in params:
        y = y + mlp_apply(params["shared"], xt, cfg, quant=quant)

    # Switch-style load-balance auxiliary loss
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_e[..., 0].reshape(-1), e, dtype=jnp.float32), 0)
    frac_probs = jnp.mean(probs.reshape(-1, e), 0)
    aux = e * jnp.sum(frac_tokens * frac_probs) * cfg.router_aux_coef

    routed = oh.sum(axis=(0, 1))                                # (E,) int32
    load = counts.sum(axis=0)                                   # (E,) int32
    bc = ops.moe_segment_tile_rows(cap)
    stats = {"load": load,
             "dropped": (routed - load).sum(),
             "capacity": jnp.int32(e * cap * g),
             "computed": (-(-counts // bc)).sum() * bc,
             "touched": (load > 0).sum(dtype=jnp.int32)}
    return y.reshape(b, s, d), aux, stats
