"""Model assembly: decoder LMs (dense/MoE/SSM/hybrid/VLM) and enc-dec.

A config's layers are described by a *plan* ``[(mixer_kind, ffn_kind)]``.
For compile efficiency at depth (40-72 layers, 512-way SPMD) the plan is
split into a *prelude* (unrolled leading layers that break the repetition,
e.g. deepseek-moe's dense layer 0) and a repeating *unit* scanned with
``jax.lax.scan`` over stacked params -- the jamba 8-layer hybrid group
(7 mamba + 1 attention, alternating MoE/dense FFN) is one unit.

Serving-time quantization is a pure param transform
(:func:`quantize_params`): every APLinear-able weight leaf is replaced by
a packed :class:`BipolarTensor`; apply functions dispatch on leaf type.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bipolar import BipolarTensor
from repro.kernels import ops
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.config import ModelConfig, QuantConfig
from repro.distributed.sharding import constrain

LOSS_CHUNK = 512  # sequence chunk for the CE loss (bounds logits memory)
# per-layer MoE telemetry keys (``layers.moe_apply``'s stats)
MOE_STATS = ("load", "dropped", "capacity", "computed", "touched")


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------

def layer_plan(cfg: ModelConfig):
    """[(mixer_kind, ffn_kind)] for the decoder stack."""
    return [(cfg.layer_kind(i), cfg.ffn_kind(i)) for i in range(cfg.n_layers)]


def plan_split(cfg: ModelConfig):
    """-> (prelude_plan, unit_plan, n_units). The unit is the smallest
    pattern that tiles the post-prelude plan."""
    plan = layer_plan(cfg)
    prelude = plan[:cfg.first_dense]
    rest = plan[cfg.first_dense:]
    for ul in range(1, len(rest) + 1):
        if len(rest) % ul:
            continue
        unit = rest[:ul]
        if all(rest[i:i + ul] == unit for i in range(0, len(rest), ul)):
            return prelude, unit, len(rest) // ul
    return prelude, rest, 1


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, mixer_kind: str, ffn_kind: str) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"norm1": L.norm_init(cfg.d_model, cfg)}
    p["mixer"] = (L.attention_init(k1, cfg) if mixer_kind == "attn"
                  else S.ssm_init(k1, cfg))
    if ffn_kind != "none":
        p["norm2"] = L.norm_init(cfg.d_model, cfg)
        p["ffn"] = (L.moe_init(k2, cfg) if ffn_kind == "moe"
                    else L.mlp_init(k2, cfg))
    return p


def _stack_init(key, cfg: ModelConfig, unit_plan, n_units: int):
    """Stacked params for the scanned unit: leaves get a leading n_units dim.

    ``vmap`` over the unit keys draws the same values as one init per
    key, but writes each leaf stacked once: per-unit trees followed by a
    stack would hold the model twice (11 GB for StableLM-3B in bf16)."""
    def one_unit(k):
        ks = jax.random.split(k, len(unit_plan))
        return [_block_init(ks[i], cfg, mk, fk)
                for i, (mk, fk) in enumerate(unit_plan)]
    return jax.vmap(one_unit)(jax.random.split(key, n_units))


def init_params(cfg: ModelConfig, key) -> dict:
    ke, kp, kb, kh, kenc = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.dtype)
    prelude_plan, unit_plan, n_units = plan_split(cfg)
    params: dict = {
        "embed": L.embed_init(ke, cfg.vocab_padded, cfg.d_model, dt),
        "final_norm": L.norm_init(cfg.d_model, cfg),
    }
    if prelude_plan:
        ks = jax.random.split(kp, len(prelude_plan))
        params["prelude"] = [
            _block_init(ks[i], cfg, mk, fk)
            for i, (mk, fk) in enumerate(prelude_plan)]
    params["blocks"] = _stack_init(kb, cfg, unit_plan, n_units)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.linear_init(kh, cfg.d_model,
                                          cfg.vocab_padded, dt)
    if cfg.family == "audio":
        # encoder stack (non-causal self-attention) + frontend projection
        k_f, k_s, k_n, k_x = jax.random.split(kenc, 4)
        enc_cfg = dataclasses.replace(cfg, n_kv_heads=cfg.n_heads)
        ks = jax.random.split(k_s, cfg.enc_layers)
        params["encoder"] = {
            "frontend": L.linear_init(k_f, cfg.frontend_dim, cfg.d_model, dt),
            "blocks": jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[_block_init(ks[i], enc_cfg, "attn", "dense")
                  for i in range(cfg.enc_layers)]),
            "final_norm": L.norm_init(cfg.d_model, cfg),
        }
        # decoder cross-attention (one per decoder layer, stacked like blocks)
        kx = jax.random.split(k_x, n_units)
        params["cross"] = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[{"attn": L.attention_init(kx[i], cfg),
               "norm": L.norm_init(cfg.d_model, cfg)}
              for i in range(n_units * len(unit_plan))])
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _apply_block(p, x, cfg, mixer_kind, ffn_kind, *, positions, cache,
                 cross_memory=None, cross_params=None, cross_cache=None,
                 quant=None):
    """One transformer block.

    Returns ``(x, (new_cache, new_cross), aux, moe_stats)`` --
    ``moe_stats`` is the :func:`repro.models.layers.moe_apply` telemetry
    dict for MoE blocks and ``None`` otherwise (callers that ignore it
    let XLA dead-code-eliminate the collection).

    Quantized serving with ``quant.fused_linear`` (and the default
    ``residual_scale == 1``) threads the block input as ``residual``
    into the attention output projection and the MLP down projection,
    so the residual add runs in the fused linear's epilogue instead of
    as a separate XLA op -- bit-identical (the unfused add multiplies
    ``h`` by 1.0 in the same dtype).
    """
    rs = jnp.asarray(cfg.residual_scale, x.dtype)
    fuse_res = (quant is not None and quant.enabled and quant.fused_linear
                and cfg.residual_scale == 1.0)
    h = L.norm_apply(p["norm1"], x, cfg)
    if mixer_kind == "attn":
        h, new_cache = L.attention_apply(
            p["mixer"], h, cfg, positions=positions, cache=cache,
            quant=quant, residual=x if fuse_res else None)
        x = h if fuse_res else x + h.astype(x.dtype) * rs
    else:
        h, new_cache = S.ssm_apply(p["mixer"], h, cfg, cache=cache,
                                   quant=quant)
        x = x + h.astype(x.dtype) * rs
    x = constrain(x, "residual")   # SP: keep every residual write
    new_cross = None
    if cross_params is not None:
        hc = L.norm_apply(cross_params["norm"], x, cfg)
        hc, new_cross = L.cross_attention_apply(
            cross_params["attn"], hc, cfg, memory=cross_memory,
            cache=cross_cache, quant=quant,
            residual=x if fuse_res else None)
        x = hc if fuse_res else x + hc.astype(x.dtype) * rs
    aux = 0.0
    moe_stats = None
    if ffn_kind != "none":
        h = L.norm_apply(p["norm2"], x, cfg)
        if ffn_kind == "moe":
            # with a cache this is a serving step: route dropless, and
            # only real tokens (pad positions are -1)
            live = None
            if cache is not None:
                live = (positions if positions.ndim == 2
                        else positions[0]) >= 0
            h, aux, moe_stats = L.moe_apply(p["ffn"], h, cfg, quant=quant,
                                            live=live)
            x = x + h.astype(x.dtype) * rs
        else:
            h = L.mlp_apply(p["ffn"], h, cfg, quant=quant,
                            residual=x if fuse_res else None)
            x = h if fuse_res else x + h.astype(x.dtype) * rs
        x = constrain(x, "residual")
    return x, (new_cache, new_cross), aux, moe_stats


def _make_cache_for(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                    dtype, kv_bits=None, paged: bool = False):
    if kind == "attn":
        return L.make_kv_cache(cfg, batch, max_len, dtype, kv_bits=kv_bits,
                               paged=paged)
    return S.make_ssm_cache(cfg, batch, dtype)


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                enc_len: Optional[int] = None,
                quant: Optional[QuantConfig] = None,
                state_batch: Optional[int] = None, paged: bool = False):
    """Decode caches: {'prelude': [..], 'blocks': stacked-unit caches,
    ['cross': stacked per-unit cross-KV]}.  ``enc_len`` (audio): encoder
    memory length for the projected cross-K/V cache.  ``quant``: its
    ``kv_bits`` (over ``cfg.kv_bits``) selects packed bipolar KV caches
    (self- AND cross-attention).

    The paged serving pool reuses this layout with ``batch=n_blocks,
    max_len=block_size``: every attention leaf's leading (batch, length)
    dims become (block, in-block slot) and requests address blocks
    through per-request block tables (:mod:`repro.serving.paged_cache`);
    ``paged=True`` stores its packed planes as lane-dense token rows
    (``layers.make_kv_cache``).
    ``state_batch`` sizes the *fixed-size per-request* state leaves
    independently of the block count: SSM conv+state and enc-dec
    cross-K/V caches get ``state_batch`` rows (the pool's slot rows,
    addressed through per-request slot ids) while attention KV leaves
    keep ``batch`` blocks.  ``None`` = everything shares ``batch`` (the
    contiguous layout)."""
    from repro.models.config import effective_kv_bits
    dt = jnp.dtype(cfg.dtype)
    kvb = effective_kv_bits(cfg, quant)
    sb = batch if state_batch is None else state_batch
    prelude_plan, unit_plan, n_units = plan_split(cfg)

    def cache_for(mk):
        return _make_cache_for(cfg, mk, batch if mk == "attn" else sb,
                               max_len, dt, kvb, paged)

    caches = {}
    if prelude_plan:
        caches["prelude"] = [cache_for(mk) for mk, _ in prelude_plan]
    unit_caches = [
        [cache_for(mk) for mk, _ in unit_plan]
        for _ in range(n_units)]
    caches["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *unit_caches)
    if cfg.family == "audio":
        if enc_len is None:
            from repro.launch.specs import enc_len as _el
            enc_len = _el(cfg, max_len)
        xc = [[L.make_cross_cache(cfg, sb, enc_len, dt, kv_bits=kvb)
               for _ in unit_plan] for _ in range(n_units)]
        caches["cross"] = jax.tree.map(lambda *xs: jnp.stack(xs), *xc)
    return caches


def forward(params: dict, tokens: jax.Array, cfg: ModelConfig, *,
            positions: Optional[jax.Array] = None,
            caches: Optional[dict] = None,
            patch_embeds: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None,
            quant: Optional[QuantConfig] = None,
            remat: bool = True,
            logits_mode: str = "none",
            collect_moe_stats: bool = False):
    """Run the stack.  Returns ``(hidden|logits, new_caches, aux_loss)``.

    ``logits_mode``: "none" (return final hidden states), "last" (logits of
    the final position only -- decode), "all" is handled by
    :func:`loss_and_logits` in chunks.

    ``collect_moe_stats=True`` appends a 4th element: the per-MoE-layer
    telemetry of :func:`repro.models.layers.moe_apply` -- ``{"load":
    (L_moe, E)}`` and ``(L_moe,)`` rows of ``dropped``, ``capacity``,
    ``computed`` and ``touched`` (int32; rows ordered prelude layers
    first, then scanned unit positions with their ``n_units`` stacked
    per row group), or ``None`` if the stack has no MoE layers.
    """
    b, s = tokens.shape
    # a QuantConfig that only sets kv_bits still matters (cache reads);
    # weight-path code checks quant.enabled / leaf types itself
    quant = quant if (quant and (quant.enabled or quant.kv_bits)) else None
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    x = params["embed"]["w"][tokens].astype(jnp.dtype(cfg.dtype))
    x = x * jnp.asarray(cfg.emb_scale, x.dtype)
    x = constrain(x, "residual")
    if patch_embeds is not None:      # vlm stub frontend: fuse patch embeds
        npt = patch_embeds.shape[1]
        x = x.at[:, :npt].add(patch_embeds.astype(x.dtype))

    cross_memory = None
    if cfg.family == "audio" and frames is not None:
        cross_memory = encode_frames(params, frames, cfg, quant=quant,
                                     remat=remat)
    elif cfg.family == "audio":
        assert caches is not None and "cross" in caches, \
            "audio decode without frames needs filled cross caches"

    prelude_plan, unit_plan, n_units = plan_split(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: dict = {}

    # --- prelude (unrolled) ---
    moe_parts = []
    if prelude_plan:
        new_caches["prelude"] = []
        for i, (mk, fk) in enumerate(prelude_plan):
            c = caches["prelude"][i] if caches else None
            x, (nc, _), aux, mst = _apply_block(
                params["prelude"][i], x, cfg, mk, fk,
                positions=positions, cache=c, quant=quant)
            aux_total += aux
            new_caches["prelude"].append(nc)
            if collect_moe_stats and mst is not None:
                moe_parts.append(mst)

    # --- scanned unit stack ---
    cross_stack = params.get("cross")

    def unit_body(x, unit_inp):
        p_unit, c_unit, x_unit, xc_unit = unit_inp
        new_c, new_xc, st_u = [], [], []
        aux_u = jnp.zeros((), jnp.float32)
        for i, (mk, fk) in enumerate(unit_plan):
            xp = (x_unit[i] if x_unit is not None else None)
            x, (nc, nxc), aux, mst = _apply_block(
                p_unit[i], x, cfg, mk, fk, positions=positions,
                cache=(c_unit[i] if c_unit is not None else None),
                cross_memory=cross_memory, cross_params=xp,
                cross_cache=(xc_unit[i] if xc_unit is not None else None),
                quant=quant)
            aux_u += aux
            new_c.append(nc)
            new_xc.append(nxc)
            st_u.append(mst if collect_moe_stats else None)
        x = constrain(x, "residual")
        return x, (new_c, new_xc, aux_u, st_u)

    body = jax.checkpoint(unit_body) if remat else unit_body

    def scan_fn(x, inp):
        x, out = body(x, inp)
        return x, out

    c_blocks = caches["blocks"] if caches else None
    # cross caches are already per-position lists with (n_units, ...) leaves
    xc_blocks = caches["cross"] if caches and "cross" in caches else None
    xs = (params["blocks"],
          c_blocks,
          _restack_cross(cross_stack, len(unit_plan)) if cross_stack else None,
          xc_blocks)
    x, (nc_blocks, nxc_blocks, aux_units, st_units) = \
        jax.lax.scan(scan_fn, x, xs)
    aux_total += aux_units.sum()
    if caches is not None:
        new_caches["blocks"] = nc_blocks
        if xc_blocks is not None:
            new_caches["cross"] = nxc_blocks
    moe_parts += [st for st in st_units if st is not None]

    x = L.norm_apply(params["final_norm"], x, cfg)

    moe_stats = None
    if collect_moe_stats and moe_parts:
        # prelude entries have no leading layer dim; scanned entries carry
        # (n_units, ...) -- normalize each to rows and concatenate
        moe_stats = {
            kk: jnp.concatenate(
                [p[kk][None] if p[kk].ndim == (1 if kk == "load" else 0)
                 else p[kk] for p in moe_parts], 0)
            for kk in MOE_STATS}

    out = x
    if logits_mode == "last":
        out = _logits(params, x[:, -1:, :], cfg, quant)[:, 0]
    ret = (out, (new_caches if caches is not None else None), aux_total)
    return ret + ((moe_stats,) if collect_moe_stats else ())


def _restack_cross(cross_stack, unit_len: int):
    """(n_units*unit_len, ...) stacked cross-attn params -> a list of
    ``unit_len`` trees with leading dim n_units (scan-sliceable)."""
    return [
        jax.tree.map(
            lambda a: a.reshape(a.shape[0] // unit_len, unit_len,
                                *a.shape[1:])[:, i],
            cross_stack)
        for i in range(unit_len)]


def encode_frames(params, frames, cfg: ModelConfig, *, quant=None,
                  remat=True):
    """Audio/enc-dec encoder: stub frontend embeddings -> memory (B,T,d)."""
    enc = params["encoder"]
    x = L.linear_apply(enc["frontend"], frames.astype(jnp.dtype(cfg.dtype)),
                       quant=quant)
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    enc_cfg = dataclasses.replace(cfg, n_kv_heads=cfg.n_heads, causal=False)

    def body(x, p):
        x, _, _, _ = _apply_block(p, x, enc_cfg, "attn", "dense",
                                  positions=positions, cache=None,
                                  quant=quant)
        return x, None

    body_fn = jax.checkpoint(body) if remat else body
    x, _ = jax.lax.scan(body_fn, x, enc["blocks"])
    return L.norm_apply(enc["final_norm"], x, cfg)


def _logits(params, x, cfg: ModelConfig, quant=None):
    x = x * jnp.asarray(cfg.logit_scale, x.dtype)
    if cfg.tie_embeddings:
        w = params["embed"]["w"]
        logits = jnp.einsum("bsd,vd->bsv", x, w.astype(x.dtype))
    else:
        logits = L.linear_apply(params["lm_head"], x, quant=quant)
    if cfg.vocab_padded > cfg.vocab:   # mask vocab-padding slots
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
        logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype), logits)
    return logits


# ---------------------------------------------------------------------------
# Loss (chunked over the sequence: logits never materialize at (B,S,V))
# ---------------------------------------------------------------------------

def loss_fn(params: dict, batch: dict, cfg: ModelConfig, *,
            quant: Optional[QuantConfig] = None, remat: bool = True):
    """Causal-LM cross-entropy (+ MoE aux). batch: tokens, labels, [mask]."""
    x, _, aux = forward(params, batch["tokens"], cfg,
                        positions=batch.get("positions"),
                        patch_embeds=batch.get("patch_embeds"),
                        frames=batch.get("frames"),
                        quant=quant, remat=remat)
    labels = batch["labels"]
    mask = batch.get("mask")
    mask = (labels >= 0) if mask is None else (mask > 0)
    b, s, d = x.shape
    chunk = min(LOSS_CHUNK, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    mask_full = mask
    labels = jnp.maximum(labels, 0)
    nc = x.shape[1] // chunk
    xc = x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)
    mc = mask_full.reshape(b, nc, chunk).transpose(1, 0, 2)

    def step(carry, inp):
        xs, ls, ms = inp
        logits = _logits(params, xs, cfg, quant).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, ls[..., None], -1)[..., 0]
        nll = (lse - gold) * ms
        return (carry[0] + nll.sum(), carry[1] + ms.sum()), None

    (tot, cnt), _ = jax.lax.scan(
        step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0) + aux


# ---------------------------------------------------------------------------
# Serving-time quantization (the paper's technique as a param transform)
# ---------------------------------------------------------------------------

_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down",
               "in_proj", "out_proj", "lm_head", "frontend")
# Elements per eager quantize call (``_quantize_leaf``): 128 MiB f32
# temporaries, a few alive at a time.
QUANT_CHUNK_ELEMS = 2**25


def quantize_params(params: Any, qcfg: QuantConfig, stacked: bool = False,
                    _key: str = "", *, donate: bool = False) -> Any:
    """Replace every quantizable linear weight with packed bipolar planes.

    ``stacked=True`` marks subtrees whose leaves carry a leading
    scan-stacking dim (``blocks``/``cross``): the packed planes are laid
    out ``(n_units, n_bits, ..., Kw)`` so ``lax.scan`` slices the unit
    axis and each slice is a well-formed packed tensor whose *static*
    metadata (shape, n_bits) describes the per-unit weight.

    ``donate=True`` deletes each float weight as soon as it is packed,
    so the float and packed trees never exist whole at once (a
    full-size model on one chip); ``params`` is unusable afterwards,
    except the leaves it shares with the result.

    Router, norms, embeddings and SSM state/conv params stay in bf16
    (DESIGN.md §4 caveats).
    """
    if not qcfg.enabled:
        return params
    if isinstance(params, dict):
        out = {}
        for k, v in params.items():
            sub_stacked = stacked or k in ("blocks", "cross")
            if k in _QUANT_KEYS and isinstance(v, dict) and "w" in v \
                    and not isinstance(v["w"], BipolarTensor):
                out[k] = {"w": _quantize_leaf(v["w"], qcfg, stacked, donate)}
            elif k in ("w_up", "w_gate", "w_down") and isinstance(v, jax.Array) \
                    and v.ndim >= 3:
                # stacked MoE experts
                out[k] = _quantize_leaf(v, qcfg, stacked, donate)
            else:
                out[k] = quantize_params(v, qcfg, sub_stacked, k,
                                         donate=donate)
        return out
    if isinstance(params, (list, tuple)):
        return type(params)(quantize_params(v, qcfg, stacked, _key,
                                            donate=donate)
                            for v in params)
    return params


def _quantize_leaf(w: jax.Array, qcfg: QuantConfig, stacked: bool,
                   donate: bool = False) -> BipolarTensor:
    """Pack a weight leaf ``(*lead, N, K)`` along K.

    Unstacked: packed ``(n_bits, *lead, N, Kw)``, static shape = w.shape.
    Stacked:   leading dim u = scan units; packed ``(u, n_bits, *rest, Kw)``
    and static shape = per-unit shape ``w.shape[1:]`` (what apply code sees
    after the scan slice).

    The per-width nested scales ride along the same way (unstacked
    ``(n_bits, *lead, N, 1)``; stacked ``(u, n_bits, *rest, 1)``), so a
    scan slice -- which peels the unit axis off every array leaf -- always
    hands ops a plane-leading tensor that ``bipolar.nested_slice`` can
    serve at any width k <= w_bits.

    The leaf is quantized in row chunks of at most
    ``QUANT_CHUNK_ELEMS`` elements, each scan unit on its own, so the
    f32 copy and the clip-search temporaries stay small: at published
    widths a whole stacked leaf in f32 (StableLM-3B's gate, 32 x 6912 x
    2560 x 4 B = 2.3 GB) would not fit beside the bf16 tree on a 16 GB
    chip.  Every step is per row, so this equals quantizing the leaf
    whole.  The ops run eagerly, not under one jit: XLA would rewrite
    the per-row division in ``quantize_values`` into a reciprocal
    multiply and move a few rounded integers.  Each chunk is waited
    for: the host dispatches eager ops faster than the device runs
    large ones, and a queued op's output is allocated when it is
    queued.  ``donate`` deletes ``w`` once it is packed.
    """
    shape = tuple(w.shape)
    n_bits = qcfg.w_bits
    step = max(1, QUANT_CHUNK_ELEMS // shape[-1])
    packed, scale, width_scales = [], [], []
    for u in range(shape[0]) if stacked else [None]:
        rows = (w if u is None else w[u]).reshape(-1, shape[-1])
        ts = [jax.block_until_ready(ops.quantize_rows(
                  rows[r:r + step].astype(jnp.float32), n_bits, pad_bit=1,
                  impl="reference", scale_search=True))
              for r in range(0, rows.shape[0], step)]
        packed.append(jnp.concatenate([t.packed for t in ts], 1))
        scale.append(jnp.concatenate([t.scale for t in ts], 0))
        if ts[0].width_scales is not None:
            width_scales.append(
                jnp.concatenate([t.width_scales for t in ts], 1))
    if donate:
        w.delete()
    static_shape = shape[1:] if stacked else shape
    rest, kw = static_shape[:-1], packed[0].shape[-1]
    join = jnp.stack if stacked else (lambda xs: xs[0])
    return BipolarTensor(
        packed=join([p.reshape(n_bits, *rest, kw) for p in packed]),
        scale=join([s.reshape(*rest, 1) for s in scale]),
        n_bits=n_bits, shape=static_shape,
        pack_axis=len(static_shape) - 1,
        width_scales=(join([s.reshape(n_bits, *rest, 1)
                            for s in width_scales])
                      if width_scales else None))
