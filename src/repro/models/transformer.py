"""Decoder LM assembly: scan-over-superblocks, heterogeneous mixers, caches.

The layer stack is expressed as a repeating *superblock* (``cfg.layer_pattern``)
scanned ``pattern_repeats`` times with stacked parameters — HLO size scales
with the superblock, not the depth (critical for 512-device compiles and real
TPU compile times).  Remainder layers (e.g. gemma3's trailing 4 local layers)
are applied unscanned.

Supports train / prefill (returns KV+SSM caches) / single-token decode, VLM
prefix embeddings (stub frontends), cross-attention to an encoder (whisper),
Horn parallel-dropout hooks, MoE aux losses, and remat per superblock.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, LOCAL, MAMBA, ModelConfig
from repro.core import parallel_dropout as pdrop
from repro.launch.mesh import ShardingCtx
from repro.models import layers as L
from repro.models.attention import attn_apply, attn_specs
from repro.models.params import ParamSpec, init_params, param_axes, stack_specs
from repro.models.ssm import (mamba_apply, mamba_paged, mamba_specs,
                              ssm_dims)

f32 = jnp.float32


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------
def block_specs(cfg: ModelConfig, kind: str, is_moe: bool, *,
                cross: bool = False):
    s: Dict[str, Any] = {"pre_norm": L.norm_specs(cfg)}
    if kind in (ATTN, LOCAL):
        s["attn"] = attn_specs(cfg)
    elif kind == MAMBA:
        s["mamba"] = mamba_specs(cfg)
    else:
        raise ValueError(kind)
    if cfg.post_sublayer_norm:
        s["post_mixer_norm"] = L.norm_specs(cfg)
    if cross:
        s["cross_norm"] = L.norm_specs(cfg)
        s["cross_attn"] = attn_specs(cfg, cross=True)
    if is_moe or cfg.d_ff > 0:
        s["ffn_norm"] = L.norm_specs(cfg)
        if is_moe:
            s["moe"] = L.moe_specs(cfg)
        else:
            s["mlp"] = L.mlp_specs(cfg)
        if cfg.post_sublayer_norm:
            s["post_ffn_norm"] = L.norm_specs(cfg)
    return s


def lm_specs(cfg: ModelConfig, *, cross: bool = False):
    specs: Dict[str, Any] = {"embed": L.embed_specs(cfg)}
    R = cfg.pattern_repeats
    pat = cfg.layer_pattern
    if R:
        sb = {f"l{i}": block_specs(cfg, k, cfg.layer_is_moe(i), cross=cross)
              for i, k in enumerate(pat)}
        specs["blocks"] = stack_specs(sb, R)
    if cfg.pattern_remainder:
        specs["rem"] = {
            f"r{i}": block_specs(cfg, pat[i],
                                 cfg.layer_is_moe(R * len(pat) + i), cross=cross)
            for i in range(cfg.pattern_remainder)}
    specs["final_norm"] = L.norm_specs(cfg)
    if cfg.learned_pos:
        specs["pos_embed"] = ParamSpec((cfg.max_pos, cfg.d_model),
                                       ("noshard", "embed"), "normal", 0.02)
    return specs


def lm_init(key, cfg: ModelConfig, *, cross: bool = False):
    return init_params(key, lm_specs(cfg, cross=cross))


def lm_axes(cfg: ModelConfig, *, cross: bool = False):
    return param_axes(lm_specs(cfg, cross=cross))


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------
def _mix_mask(a, b):
    """Compose two optional multiplicative masks (either may be None)."""
    if a is None:
        return b
    return a if b is None else a * b


def _serve_slice(serve_masks, key: str, layer_idx):
    """Layer ``layer_idx``'s per-slot sub-model mask ([B, units]) from a
    serve-mask dict, or None.  ``layer_idx`` may be traced (the superblock
    scan) — the [G-gathered B, L, units] tensor is indexed dynamically."""
    if serve_masks is None or key not in serve_masks:
        return None
    return serve_masks[key][:, layer_idx]


def _block_apply(bp, x, cfg: ModelConfig, ctx: ShardingCtx, *, kind: str,
                 is_moe: bool, layer_idx, horn, positions, cache,
                 cache_index, encoder_out=None, causal: bool = True,
                 block_tables=None, chunk_lens=None, serve_masks=None,
                 ssm_index=None):
    """Returns (x, new_mix_cache, aux).  In the unified serving step a
    Mamba layer's ``cache`` is the whole per-slot state pool
    (``init_paged_cache``'s "ssm") and ``ssm_index`` its row of it; the
    pool comes back as ``new_mix_cache``."""
    B = x.shape[0]
    aux: Dict[str, Any] = {}
    h = L.norm_apply(bp["pre_norm"], x, cfg)
    if kind in (ATTN, LOCAL):
        hm = pdrop.head_mask(horn, layer_idx, B, cfg.num_heads)
        sh = _serve_slice(serve_masks, "heads", layer_idx)
        if sh is not None:
            hm = _mix_mask(hm, sh[:, None, :, None])       # [B,1,H,1]
        out, new_mix_cache = attn_apply(
            bp["attn"], h, cfg, ctx, kind=kind, positions=positions,
            cache=cache, cache_index=cache_index, head_mask=hm, causal=causal,
            block_tables=block_tables, chunk_lens=chunk_lens)
    elif block_tables is not None:
        out, new_mix_cache = mamba_paged(
            bp["mamba"], h, cfg, ctx, cache=cache, index=ssm_index,
            starts=cache_index, chunk_lens=chunk_lens)
    else:
        d_in = ssm_dims(cfg)[0]
        cm = pdrop.unit_mask(horn, layer_idx, B, d_in, salt=3)
        out, new_mix_cache = mamba_apply(
            bp["mamba"], h, cfg, ctx, cache=cache, channel_mask=cm)
    if cfg.post_sublayer_norm:
        out = L.norm_apply(bp["post_mixer_norm"], out, cfg)
    x = x + _residual_scaled(out, cfg).astype(x.dtype)

    if "cross_attn" in bp and encoder_out is not None:
        h = L.norm_apply(bp["cross_norm"], x, cfg)
        out, _ = attn_apply(bp["cross_attn"], h, cfg, ctx, cross=True,
                            positions=positions, kv_x=encoder_out)
        x = x + out.astype(x.dtype)

    if "ffn_norm" in bp:   # mamba2-style blocks have no FFN (d_ff == 0)
        h = L.norm_apply(bp["ffn_norm"], x, cfg)
        if is_moe:
            mm = pdrop.unit_mask(horn, layer_idx, B, cfg.moe_ff, salt=5)
            mm = None if mm is None else mm[:, None]       # [B,1,1,ff]
            sm = _serve_slice(serve_masks, "moe", layer_idx)
            if sm is not None:
                mm = _mix_mask(mm, sm[:, None, None, :])
            out, aux = L.moe_apply(bp["moe"], h, cfg, ctx, hidden_mask=mm)
        else:
            mb = pdrop.block_mask(horn, layer_idx, cfg.d_ff, salt=5)
            fm = None if mb is None else pdrop.expand_mask(mb, cfg.d_ff, B)
            sf = _serve_slice(serve_masks, "ffn", layer_idx)
            if sf is not None:
                fm = _mix_mask(fm, sf[:, None, :])         # [B,1,ff]
                mb = None
            out = L.mlp_apply(bp["mlp"], h, cfg, ctx, hidden_mask=fm,
                              mask_blocks=mb)
        if cfg.post_sublayer_norm:
            out = L.norm_apply(bp["post_ffn_norm"], out, cfg)
        x = x + _residual_scaled(out, cfg).astype(x.dtype)
    return x, new_mix_cache, aux


def _residual_scaled(out, cfg: ModelConfig):
    """A sublayer's output as it joins the residual stream (granite scales
    it by ``residual_multiplier``; at 1.0 it is left untouched)."""
    if cfg.residual_multiplier == 1.0:
        return out
    return out * jnp.asarray(cfg.residual_multiplier, out.dtype)


def _empty_aux():
    return {"load_balance_loss": jnp.zeros((), f32),
            "router_z_loss": jnp.zeros((), f32),
            "dropped_frac": jnp.zeros((), f32)}


def _pad_aux(aux):
    base = _empty_aux()
    base.update(aux)
    return base


# ---------------------------------------------------------------------------
# Decode cache construction
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Decode caches, structured to match the scan (stacked per superblock)."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim

    def mix_cache(kind):
        if kind in (ATTN, LOCAL):
            shape = (batch, max_len, kv, hd)
            return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
        d_in, H, P, N = ssm_dims(cfg)
        conv = jnp.zeros((batch, cfg.ssm_conv_width - 1, d_in + 2 * N), dtype)
        return (conv, jnp.zeros((batch, H, P, N), f32))

    R = cfg.pattern_repeats
    cache: Dict[str, Any] = {}
    if R:
        sb = {f"l{i}": mix_cache(k) for i, k in enumerate(cfg.layer_pattern)}
        cache["blocks"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), sb)
    if cfg.pattern_remainder:
        cache["rem"] = {f"r{i}": mix_cache(cfg.layer_pattern[i])
                        for i in range(cfg.pattern_remainder)}
    return cache


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16, *, num_slots: int = 0,
                     conv_dtype=jnp.bfloat16):
    """Block-paged decode caches: every attention layer gets a pool of
    ``num_pages`` fixed-size pages [P, KH, psize, D] addressed through a
    shared per-sequence block table (page ids are layer-agnostic: page j of
    layer 0 and page j of layer 7 belong to the same sequence).  Page 0 is
    reserved as the null page for empty decode slots.  Structured to match
    the superblock scan, like ``init_cache``.

    ``dtype=jnp.int8`` selects the quantized-pool mode: each layer carries
    (k_pages int8, v_pages int8, k_scale f32 [P, KH], v_scale f32 [P, KH])
    — one symmetric scale per (page, kv-head) stored beside the pool, so a
    page costs ~1/2 the HBM of bf16 (~1/4 of f32) and the sidecar follows
    the page through every COW copy (same page ids index both arrays).

    Mamba layers hold no pages (their entries are None): all of them
    share one per-slot state pool beside the page pools, ``cache["ssm"]``
    = (conv tail [Lm, num_slots, W-1, d_inner + 2N] in ``conv_dtype``, SSM
    state [Lm, num_slots, H, P, N] float32), one row per Mamba layer in
    layer order, slot b's state belonging to whatever sequence runs in slot
    b (``ssm.mamba_paged``).  One array per kind, carried whole through the
    layer scan, so each layer's update lands in place."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    quantized = jnp.dtype(dtype) == jnp.int8

    def mix_cache(kind):
        if kind == MAMBA:
            return None
        if kind not in (ATTN, LOCAL):
            raise ValueError(
                f"paged KV cache supports attention and Mamba mixers, got "
                f"{kind!r}")
        pools = (jnp.zeros((num_pages, kv, page_size, hd), dtype),
                 jnp.zeros((num_pages, kv, page_size, hd), dtype))
        if quantized:
            pools += (jnp.zeros((num_pages, kv), jnp.float32),
                      jnp.zeros((num_pages, kv), jnp.float32))
        return pools

    R = cfg.pattern_repeats
    cache: Dict[str, Any] = {}
    if R:
        sb = {f"l{i}": mix_cache(k) for i, k in enumerate(cfg.layer_pattern)}
        cache["blocks"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), sb)
    if cfg.pattern_remainder:
        cache["rem"] = {f"r{i}": mix_cache(cfg.layer_pattern[i])
                        for i in range(cfg.pattern_remainder)}
    n_mamba = sum(k == MAMBA for k in cfg.layer_kinds())
    if n_mamba:
        if num_slots <= 0:
            raise ValueError("Mamba layers keep one state per slot: pass "
                             "num_slots")
        d_in, H, P, N = ssm_dims(cfg)
        cache["ssm"] = (
            jnp.zeros((n_mamba, num_slots, cfg.ssm_conv_width - 1,
                       d_in + 2 * N), conv_dtype),
            jnp.zeros((n_mamba, num_slots, H, P, N), f32))
    return cache


def cache_logical_axes(cfg: ModelConfig, cache):
    """Logical-axes pytree matching ``init_cache`` output (for shardings)."""
    if cfg.ssm_state:
        d_in, H, P, N = ssm_dims(cfg)
    else:
        d_in = H = P = N = -1

    def ax(x):
        s = x.shape
        if len(s) >= 4 and s[-1] == cfg.head_dim and s[-2] == cfg.num_kv_heads:
            base = ("batch", "kv_seq", "kv_heads", "kv_head_dim")  # KV buffer
        elif len(s) >= 4 and s[-1] == N and s[-2] == P:
            base = ("batch", "ssm_heads", None, "ssm_state")     # SSM state
        elif len(s) >= 3 and s[-1] == d_in + 2 * N:
            base = ("batch", None, None)                          # conv tail
        else:
            return tuple(None for _ in s)
        if x.ndim == len(base) + 1:
            base = ("layers",) + base
        return base

    return jax.tree.map(ax, cache)


# ---------------------------------------------------------------------------
# Full forward
# ---------------------------------------------------------------------------
def lm_forward(params, tokens, cfg: ModelConfig, ctx: ShardingCtx, *,
               horn=None, patch_embeds=None, cache=None, cache_index=None,
               mode: str = "train", remat: bool = True, encoder_out=None,
               causal: bool = True, block_tables=None, chunk_lens=None,
               serve_masks=None, logit_index=None):
    """Returns (hidden [B,S,d], new_cache or None, aux dict).

    ``logit_index`` ([B, n] int32, paged decode only) fuses the verify /
    last-position window into the forward: the n selected chunk rows are
    gathered from the residual stream right after the final block, and the
    final norm runs on those n rows only — the returned hidden is [B, n, d]
    and no full-width post-norm tensor is ever materialized.  Bitwise
    identical to gathering after the norm (the norm is row-wise).

    mode: "train" (no cache out, remat on) | "prefill" (cache out = full-seq
    KV / final SSM states) | "decode" (cache required; S is 1 for dense-cache
    decode, or the chunk width C for the unified paged step).

    Paged (unified serving step): pass ``block_tables`` [B, maxp], a
    per-sequence [B] ``cache_index`` (KV tokens already in pages — each slot
    at its own depth) and ``chunk_lens`` [B] (valid tokens of each slot's
    [B, C] chunk); ``cache`` must come from ``init_paged_cache``.  Token j of
    slot b sits at absolute position ``cache_index[b] + j``.

    ``serve_masks`` (multi-submodel serving) is a dict of *fixed per-slot*
    sub-model masks, already gathered by submodel id: "input" [B, d_model],
    "ffn" [B, L, d_ff], "moe" [B, L, moe_ff], "heads" [B, L, H] — binary
    {0, 1}, applied multiplicatively so each slot runs its own Horn circuit
    of the shared parent weights.  Orthogonal to ``horn`` (train-time
    stochastic masks); serving passes ``horn=None``.
    """
    decode = mode == "decode"
    x = L.embed_apply(params["embed"], tokens, cfg, ctx)
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    B, Stot = x.shape[0], x.shape[1]
    if cfg.learned_pos:
        if decode:
            pos_emb = jax.lax.dynamic_slice_in_dim(
                params["pos_embed"], cache_index, Stot, axis=0)
        else:
            pos_emb = params["pos_embed"][:Stot]
        x = x + pos_emb.astype(x.dtype)[None]

    im = pdrop.input_mask(horn, B, cfg.d_model)
    if im is not None:
        x = x * im.astype(x.dtype)
    if serve_masks is not None and "input" in serve_masks:
        x = x * serve_masks["input"][:, None, :].astype(x.dtype)

    if decode:
        ci = jnp.asarray(cache_index)
        start = ci[:, None] if ci.ndim == 1 else jnp.full((B, 1), ci)
        positions = start + jnp.arange(Stot)[None, :]   # per-token positions
    else:
        positions = jnp.arange(Stot)[None, :]
    pat = cfg.layer_pattern
    R = cfg.pattern_repeats
    new_cache: Dict[str, Any] = {}
    aux0 = _empty_aux()
    # the unified serving step's Mamba state pool, carried whole through
    # the layers; Mamba layer k of the stack (in layer order) owns row k
    ssm = cache.get("ssm") if decode else None
    n_m = sum(k == MAMBA for k in pat)

    def superblock(x, aux_acc, sb_params, sb_cache, r, ssm=None):
        caches_out = {}
        j = 0
        for i, kind in enumerate(pat):
            li = r * len(pat) + i
            own = ssm is not None and kind == MAMBA
            x, mix_c, aux = _block_apply(
                sb_params[f"l{i}"], x, cfg, ctx, kind=kind,
                is_moe=cfg.layer_is_moe(i), layer_idx=li, horn=horn,
                positions=positions,
                cache=ssm if own else
                None if sb_cache is None else sb_cache[f"l{i}"],
                cache_index=cache_index, encoder_out=encoder_out,
                causal=causal, block_tables=block_tables,
                chunk_lens=chunk_lens, serve_masks=serve_masks,
                ssm_index=r * n_m + j if own else None)
            if own:
                ssm, mix_c, j = mix_c, None, j + 1
            caches_out[f"l{i}"] = mix_c
            aux_acc = jax.tree.map(jnp.add, aux_acc, _pad_aux(aux))
        return x, aux_acc, caches_out, ssm

    if R:
        # one scan body for every mode: ``ssm`` is the Mamba state pool
        # in the unified step and None (no carry leaves) otherwise
        def body(carry, inp):
            x, acc, ssm = carry
            sb_params, sb_cache, r = inp
            x, acc, caches, ssm = superblock(x, acc, sb_params, sb_cache, r,
                                             ssm)
            return (x, acc, ssm), caches
        xs = (params["blocks"], cache["blocks"] if decode else None,
              jnp.arange(R))
        if remat and mode == "train":
            body = jax.checkpoint(body)
        (x, aux0, ssm), caches_stacked = jax.lax.scan(body, (x, aux0, ssm),
                                                      xs)
        if mode != "train":
            new_cache["blocks"] = caches_stacked

    if cfg.pattern_remainder:
        rem_cache = {}
        j = R * n_m
        for i in range(cfg.pattern_remainder):
            li = R * len(pat) + i
            own = ssm is not None and pat[i] == MAMBA
            x, mix_c, aux = _block_apply(
                params["rem"][f"r{i}"], x, cfg, ctx, kind=pat[i],
                is_moe=cfg.layer_is_moe(li), layer_idx=li, horn=horn,
                positions=positions,
                cache=ssm if own else
                None if not decode else cache["rem"][f"r{i}"],
                cache_index=cache_index, encoder_out=encoder_out,
                causal=causal, block_tables=block_tables,
                chunk_lens=chunk_lens, serve_masks=serve_masks,
                ssm_index=j if own else None)
            if own:
                ssm, mix_c, j = mix_c, None, j + 1
            rem_cache[f"r{i}"] = mix_c
            aux0 = jax.tree.map(jnp.add, aux0, _pad_aux(aux))
        if mode != "train":
            new_cache["rem"] = rem_cache
    if ssm is not None:
        new_cache["ssm"] = ssm

    if logit_index is not None:
        x = jnp.take_along_axis(
            x, logit_index[..., None].astype(jnp.int32), axis=1)
    x = L.norm_apply(params["final_norm"], x, cfg)
    aux_mean = jax.tree.map(lambda v: v / max(1, cfg.num_layers), aux0)
    return x, (new_cache if mode != "train" else None), aux_mean


# ---------------------------------------------------------------------------
# Losses / heads
# ---------------------------------------------------------------------------
def chunked_xent(hidden, params, labels, cfg: ModelConfig, ctx: ShardingCtx,
                 *, chunk: int = 512, label_mask=None):
    """Cross-entropy without materializing full [B, S, V] logits.

    Scans over sequence chunks; each chunk computes logits + log-softmax and
    is rematerialized in backward.  Essential at vocab 262k x seq 4k.
    """
    B, Stot, D = hidden.shape
    chunk = min(chunk, Stot)
    while Stot % chunk:
        chunk //= 2
    n = Stot // chunk
    hc = hidden.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, chunk).transpose(1, 0, 2)
    if label_mask is None:
        label_mask = jnp.ones(labels.shape, f32)
    mc = label_mask.reshape(B, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def one(carry, inp):
        h, lbl, m = inp
        with jax.named_scope("xent_chunk"):
            logits = L.unembed_apply(params["embed"], h, cfg, ctx).astype(f32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lbl[..., None], axis=-1)[..., 0]
            nll = (lse - gold) * m
        loss, cnt = carry
        return (loss + nll.sum(), cnt + m.sum()), None

    (tot, cnt), _ = jax.lax.scan(one, (jnp.zeros((), f32), jnp.zeros((), f32)),
                                 (hc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def lm_logits(params, hidden, cfg: ModelConfig, ctx: ShardingCtx):
    return L.unembed_apply(params["embed"], hidden, cfg, ctx)
