"""GQA attention: chunked online-softmax reference path + KV-cache decode.

The reference path (used by smoke tests and by the 512-device dry-run, where
Pallas cannot lower on the CPU backend) never materializes an [S, S] score
matrix: it scans over KV chunks with a running (max, sum, acc) — the same
algorithm the Pallas flash kernel implements in VMEM.  ``repro.kernels`` swaps
in the Pallas kernel on TPU via the backend switch in ``kernels/ops.py``.

Supports: GQA (kv_heads <= heads), qk-norm (qwen3), QKV bias (qwen1.5),
attention-logit softcapping (gemma2), sliding windows (gemma local layers),
query scaling overrides, cross-attention (whisper), single-token decode.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.mesh import ShardingCtx
from repro.models.layers import apply_rope, norm_apply
from repro.models.params import ParamSpec

f32 = jnp.float32
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def attn_specs(cfg: ModelConfig, *, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    specs = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "kv_head_dim")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "kv_head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((h, hd), ("heads", "head_dim"), "zeros")
        specs["bk"] = ParamSpec((kv, hd), ("kv_heads", "kv_head_dim"), "zeros")
        specs["bv"] = ParamSpec((kv, hd), ("kv_heads", "kv_head_dim"), "zeros")
    if cfg.qk_norm and not cross:
        specs["q_norm"] = {"scale": ParamSpec((hd,), ("noshard",), "zeros")}
        specs["k_norm"] = {"scale": ParamSpec((hd,), ("noshard",), "zeros")}
    return specs


# ---------------------------------------------------------------------------
# Core chunked attention
# ---------------------------------------------------------------------------
def _mask_block(q_pos, k_pos, *, causal: bool, window: Optional[int],
                kv_len: Optional[jnp.ndarray]):
    """Additive mask block [..., Sq, Skv_chunk] from absolute positions."""
    m = jnp.zeros(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), f32)
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if causal:
        m = jnp.where(kp > qp, NEG_INF, m)
    if window is not None:
        m = jnp.where(kp <= qp - window, NEG_INF, m)
    if kv_len is not None:  # decode: positions beyond current length are invalid
        m = jnp.where(kp >= kv_len[..., None, None], NEG_INF, m)
    return m


def chunked_attention(q, k, v, *, scale: float, causal: bool = True,
                      window: Optional[int] = None,
                      softcap: Optional[float] = None,
                      q_positions=None, k_positions=None,
                      kv_len=None, kv_chunk: int = 1024):
    """Online-softmax attention.

    q: [B, Sq, H, D]; k, v: [B, Skv, KH, D] with H = KH * G.
    Returns [B, Sq, H, D].  fp32 accumulation throughout.
    """
    B, Sq, H, D = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    G = H // KH
    kv_chunk = min(kv_chunk, Skv)
    while Skv % kv_chunk:
        kv_chunk //= 2
    n_chunks = Skv // kv_chunk

    if q_positions is None:
        q_positions = jnp.arange(Sq)
    if k_positions is None:
        k_positions = jnp.arange(Skv)
    q_positions = jnp.broadcast_to(q_positions, (B, Sq))
    k_positions = jnp.broadcast_to(k_positions, (B, Skv))

    qg = q.reshape(B, Sq, KH, G, D).transpose(0, 2, 3, 1, 4)   # [B,KH,G,Sq,D]
    kc = k.transpose(0, 2, 1, 3).reshape(B, KH, n_chunks, kv_chunk, D)
    vc = v.transpose(0, 2, 1, 3).reshape(B, KH, n_chunks, kv_chunk, D)
    kpos_c = k_positions.reshape(B, n_chunks, kv_chunk)

    def step(carry, inp):
        acc, m_run, l_run = carry
        k_blk, v_blk, kp_blk = inp                              # [B,KH,C,D], [B,C]
        # bf16 inputs, f32 accumulation via preferred_element_type — avoids
        # materializing f32 copies of K/V (hillclimb 3: -2x attn traffic)
        s = jnp.einsum("bhgqd,bhcd->bhgqc", qg, k_blk,
                       preferred_element_type=f32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = _mask_block(q_positions, kp_blk, causal=causal, window=window,
                           kv_len=kv_len)                       # [B,Sq,C]
        s = s + mask[:, None, None]
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqc,bhcd->bhgqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=f32)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, KH, G, Sq, D), f32)
    m0 = jnp.full((B, KH, G, Sq), NEG_INF, f32)
    l0 = jnp.zeros((B, KH, G, Sq), f32)
    xs = (kc.transpose(2, 0, 1, 3, 4), vc.transpose(2, 0, 1, 3, 4),
          kpos_c.transpose(1, 0, 2))
    with jax.named_scope("flash_attn"):
        if n_chunks == 1:
            (acc, _, l), _ = step((acc0, m0, l0),
                                  jax.tree.map(lambda x: x[0], xs))
        else:
            # checkpoint the chunk step: backward recomputes p from (q, k)
            # instead of saving [n_chunks, ..., Sq, C] f32 score residuals
            # (hillclimb 3: the p-stack dominated attention HBM traffic)
            (acc, _, l), _ = jax.lax.scan(jax.checkpoint(step),
                                          (acc0, m0, l0), xs)
        out = acc / jnp.clip(l[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D).astype(q.dtype)


def cache_update(buf, new, pos, ctx: ShardingCtx):
    """Write one token into the KV cache at dynamic position ``pos``.

    When the cache's sequence axis is sharded (rule "kv_seq"), a plain
    dynamic_update_slice makes GSPMD all-gather the ENTIRE stacked cache
    (observed: 2 x 1.7e12 B for qwen1.5 decode_32k).  Instead we shard_map a
    local update: each shard tests whether ``pos`` falls in its range —
    zero collective bytes (EXPERIMENTS.md §Perf hillclimb 2).
    """
    if ctx.mesh is None or ctx.rules.get("kv_seq") is None:
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), pos, axis=1)
    from jax.sharding import PartitionSpec as P
    buf_spec = ctx.spec("batch", "kv_seq", "kv_heads", "kv_head_dim")
    new_spec = ctx.spec("batch", None, "kv_heads", "kv_head_dim")
    seq_axes = buf_spec[1]

    def upd(b, n, p):
        s_loc = b.shape[1]
        if seq_axes is None:
            start = 0
        else:
            start = jax.lax.axis_index(seq_axes) * s_loc
        lp = jnp.clip(p - start, 0, max(s_loc - 1, 0))
        in_range = jnp.logical_and(p >= start, p < start + s_loc)
        updated = jax.lax.dynamic_update_slice_in_dim(
            b, n.astype(b.dtype), lp, axis=1)
        return jnp.where(in_range, updated, b)

    fn = jax.shard_map(upd, mesh=ctx.mesh,
                       in_specs=(buf_spec, new_spec, P()),
                       out_specs=buf_spec, check_vma=False)
    return fn(buf, new, jnp.asarray(pos, jnp.int32))


def decode_attention(q, k_buf, v_buf, *, scale: float,
                     window, softcap, kv_len, q_positions, ctx):
    """Single-token attention over a full cache — no chunk scan.

    One masked softmax over [B, KH, G, 1, S]: GSPMD partitions the S axis
    (rule "kv_seq") with partial-softmax reductions (flash-decode), instead
    of the chunk-scan path whose sharded-xs scan made GSPMD all-gather the
    entire cache (EXPERIMENTS.md §Perf hillclimb 2).
    """
    B, Sq, H, D = q.shape
    S, KH = k_buf.shape[1], k_buf.shape[2]
    G = H // KH
    qg = q.reshape(B, Sq, KH, G, D)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qg, k_buf,
                   preferred_element_type=f32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    kp = jnp.arange(S)[None, :]
    mask = jnp.zeros((B, Sq, S), f32)
    qp = q_positions[..., :, None]
    if window is not None:
        mask = jnp.where(kp[:, None] <= qp - window, NEG_INF, mask)
    if kv_len is not None:
        mask = jnp.where(kp[:, None] >= kv_len[:, None, None], NEG_INF, mask)
    s = s + mask[:, None, None]                  # [B,KH,G,Sq,S]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p.astype(v_buf.dtype), v_buf,
                     preferred_element_type=f32)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention sublayer
# ---------------------------------------------------------------------------
def _project_qkv(params, x, kv_x, cfg: ModelConfig, positions, kv_positions,
                 *, use_rope: bool, rope_theta: float):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if "q_norm" in params:
        q = norm_apply(params["q_norm"], q, cfg)
        k = norm_apply(params["k_norm"], k, cfg)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, kv_positions, rope_theta)
    return q, k, v


def attn_apply(params, x, cfg: ModelConfig, ctx: ShardingCtx, *,
               kind: str = "attn", positions=None, cache=None, cache_index=None,
               kv_x=None, cross: bool = False, head_mask=None,
               causal: bool = True, block_tables=None, chunk_lens=None):
    """Attention sublayer.

    Modes:
      - training/prefill: ``cache is None`` -> returns (out, new_kv) where
        new_kv=(k, v) so prefill can build a cache.
      - decode: ``cache=(k_buf, v_buf)`` [B, S_max, KH, D] and ``cache_index``
        scalar -> one-token update, returns (out, updated cache).
      - paged (the unified serving step): ``block_tables`` [B, maxp] given,
        ``cache`` is a (k_pages, v_pages) [P, KH, psize, D] pool pair,
        ``cache_index`` is a *per-sequence* [B] vector of KV tokens already
        in pages, and ``chunk_lens`` [B] counts the valid tokens of this
        call's [B, C] chunk (decode slots: 1; admitting prompts: up to C;
        idle slots: 0).  Chunk K/V is appended to the pool in place, then
        every token attends to prior pages plus its own chunk's causal
        prefix (continuous batching: every slot sits at its own depth).
      - cross-attention: ``kv_x`` given, no cache/rope on kv side.
    """
    B, Sq, _ = x.shape
    window = cfg.sliding_window if kind == "local" else None
    theta = 10_000.0 if (kind == "local" and cfg.rope_theta > 1e5) else cfg.rope_theta
    # gemma2 scales queries by query_pre_attn_scalar instead of head_dim
    scale = cfg.query_scale if cfg.query_scale else cfg.head_dim ** -0.5
    use_rope = cfg.use_rope and not cross

    if positions is None:
        positions = jnp.arange(Sq)[None, :]
    kv_src = kv_x if cross else x

    if cache is None:
        kv_positions = jnp.arange(kv_src.shape[1])[None, :] if cross else positions
        q, k, v = _project_qkv(params, x, kv_src, cfg, positions, kv_positions,
                               use_rope=use_rope, rope_theta=theta)
        if ctx.rules.get("sp_seq") is not None:
            # sequence-parallel attention (prefill w/ unshardable heads)
            q = ctx.constrain(q, "batch", "sp_seq", "heads", "head_dim")
        else:
            q = ctx.constrain(q, "batch", "seq", "heads", "head_dim")
        k = ctx.constrain(k, "batch", "seq", "kv_heads", "kv_head_dim")
        v = ctx.constrain(v, "batch", "seq", "kv_heads", "kv_head_dim")
        from repro.kernels.backend import get_backend
        if get_backend() != "ref" and not cross:
            # production TPU path: Pallas flash kernel ([B,H,S,D] layout)
            from repro.kernels.flash_attention.ops import flash_attention
            # per-device heads keep their GQA grouping only when q and kv
            # heads shard alike; sequences stay whole (causal masks)
            hq, hkv = ("heads", "kv_heads") \
                if ctx.rules.get("heads") == ctx.rules.get("kv_heads") \
                else (None, None)
            flash = ctx.shard_kernel(
                partial(flash_attention, scale=scale, causal=causal,
                        window=window, softcap=cfg.attn_logit_softcap),
                in_axes=(("batch", hq, None, None),
                         ("batch", hkv, None, None),
                         ("batch", hkv, None, None)),
                out_axes=("batch", hq, None, None))
            out = flash(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
        else:
            out = chunked_attention(
                q, k, v, scale=scale, causal=causal and not cross,
                window=window, softcap=cfg.attn_logit_softcap,
                q_positions=positions, k_positions=kv_positions)
        new_kv = (k, v)
    elif block_tables is not None:
        # unified paged step: per-sequence chunk append + paged attention.
        # ``cache`` is (k_pages, v_pages) — or, in int8-pool mode,
        # (k_pages, v_pages, k_scale, v_scale) with per-(page, kv-head)
        # scales riding beside the pools: the append path quantizes
        # in-device and the kernel dequantizes in-register after the gather
        from repro.kernels.paged_attention.ops import (
            paged_chunk_attention, paged_pool_append, paged_pool_append_quant)
        quantized = len(cache) == 4
        if quantized:
            k_pages, v_pages, k_scale, v_scale = cache
        else:
            (k_pages, v_pages), k_scale, v_scale = cache, None, None
        if chunk_lens is None:                          # plain decode tick
            chunk_lens = jnp.ones((B,), jnp.int32)
        q, k_new, v_new = _project_qkv(
            params, x, kv_src, cfg, positions, positions,
            use_rope=use_rope, rope_theta=theta)
        if quantized:
            k_pages, k_scale = paged_pool_append_quant(
                k_pages, k_scale, k_new, block_tables, cache_index, chunk_lens)
            v_pages, v_scale = paged_pool_append_quant(
                v_pages, v_scale, v_new, block_tables, cache_index, chunk_lens)
        else:
            k_pages = paged_pool_append(k_pages, k_new, block_tables,
                                        cache_index, chunk_lens)
            v_pages = paged_pool_append(v_pages, v_new, block_tables,
                                        cache_index, chunk_lens)
        out = paged_chunk_attention(
            q, k_pages, v_pages, block_tables, cache_index, chunk_lens,
            scale=scale, window=window, softcap=cfg.attn_logit_softcap,
            k_scale=k_scale, v_scale=v_scale)
        new_kv = (k_pages, v_pages, k_scale, v_scale) if quantized \
            else (k_pages, v_pages)
    else:
        # single-token decode against a preallocated cache
        k_buf, v_buf = cache
        q, k_new, v_new = _project_qkv(
            params, x, kv_src, cfg, positions, positions,
            use_rope=use_rope, rope_theta=theta)
        if not cross:
            k_buf = cache_update(k_buf, k_new, cache_index, ctx)
            v_buf = cache_update(v_buf, v_new, cache_index, ctx)
        kv_len = None if cross else jnp.full((B,), cache_index + Sq)
        k_buf = ctx.constrain(k_buf, "batch", "kv_seq", "kv_heads",
                              "kv_head_dim")
        v_buf = ctx.constrain(v_buf, "batch", "kv_seq", "kv_heads",
                              "kv_head_dim")
        out = decode_attention(
            q, k_buf, v_buf, scale=scale, window=window,
            softcap=cfg.attn_logit_softcap, kv_len=kv_len,
            q_positions=positions, ctx=ctx)
        new_kv = (k_buf, v_buf)

    if head_mask is not None:  # Horn per-group head dropout (optional)
        out = out * head_mask.astype(out.dtype)
    # row-parallel out-proj: keep the TP psum in the activation dtype
    proj = jnp.einsum("bshk,hkd->bsd", out, params["wo"],
                      preferred_element_type=x.dtype)
    return ctx.constrain(proj, "batch", "seq", "act_embed"), new_kv
