"""Shared layers: norms, RoPE, gated MLP, sort-based MoE, embeddings.

All modules follow the same convention:
  ``<name>_specs(cfg) -> pytree[ParamSpec]``   (single source of truth)
  ``<name>_apply(params, x, cfg, ctx, ...)``   (pure function)
Sharding is expressed through ``ctx.constrain`` with logical axes only.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.mesh import ShardingCtx
from repro.models.params import ParamSpec

f32 = jnp.float32

ACTS = {
    "silu": jax.nn.silu,
    "gelu": partial(jax.nn.gelu, approximate=True),
    "relu": jax.nn.relu,
}


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def norm_specs(cfg: ModelConfig, dim: int | None = None):
    d = dim or cfg.d_model
    # gemma family parameterizes RMSNorm weight as (1 + w); init zeros either way
    init = "zeros" if cfg.norm == "rmsnorm" else "ones"
    specs = {"scale": ParamSpec((d,), ("noshard",), init)}
    if cfg.norm == "layernorm":
        specs["bias"] = ParamSpec((d,), ("noshard",), "zeros")
    return specs


def norm_apply(params, x, cfg: ModelConfig):
    """RMSNorm/LayerNorm: reductions in f32, elementwise math in x.dtype.

    Keeping the big elementwise chain in bf16 (only the [..., 1] statistics
    are f32) removes ~4x f32 activation traffic per norm that dominated the
    train-step memory term (EXPERIMENTS.md §Perf hillclimb 3).
    """
    # norm weights are (D,): broadcast them explicitly so the elementwise
    # chain is rank-clean under jax_numpy_rank_promotion='raise' (the
    # --sanitize mode); reshape-then-broadcast is bit-identical
    def wide(w):
        return jnp.reshape(w.astype(x.dtype), (1,) * (x.ndim - 1) + (-1,))

    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(x.astype(f32)), axis=-1, keepdims=True)
        mult = jax.lax.rsqrt(var + cfg.norm_eps).astype(x.dtype)
        y = x * mult * wide(1.0 + params["scale"])
    else:
        xf = x.astype(f32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        mult = jax.lax.rsqrt(var + cfg.norm_eps)
        y = ((x - mu.astype(x.dtype)) * mult.astype(x.dtype)
             * wide(params["scale"]) + wide(params["bias"]))
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=f32) / head_dim)


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, D]; positions: [..., S] int32."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                      # [D/2]
    pos = positions[..., :, None].astype(f32)       # [..., S, 1]
    # explicit rank match (rank-promotion-clean under --sanitize)
    ang = pos * jnp.reshape(inv, (1,) * (pos.ndim - 1) + (-1,))
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(f32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU) with Horn parallel-dropout hook
# ---------------------------------------------------------------------------
def mlp_specs(cfg: ModelConfig):
    d, ff = cfg.d_model, cfg.d_ff
    specs = {
        "wi": ParamSpec((d, ff), ("embed", "ffn")),
        "wo": ParamSpec((ff, d), ("ffn", "embed")),
    }
    if cfg.mlp_gated:
        specs["wg"] = ParamSpec((d, ff), ("embed", "ffn"))
    return specs


def _dropout_kernel_fits(x_shape, mask_shape, d_ff: int) -> bool:
    """Whether ``dropout_matmul``'s tiles cover this MLP call exactly, in
    blocks the TPU compiler accepts (128-lane mask blocks)."""
    B, S, d = x_shape
    G, nb = mask_shape
    rows = (B // G) * S
    block_n = d_ff // nb
    return (B % G == 0 and d_ff % nb == 0 and rows % min(128, rows) == 0
            and d % min(128, d) == 0
            and (block_n % 128 == 0 or block_n == d_ff))


def mlp_apply(params, x, cfg: ModelConfig, ctx: ShardingCtx, *,
              hidden_mask=None, mask_blocks=None):
    """x: [B, S, d].  hidden_mask: [B, 1, ff]-broadcastable or None.

    ``hidden_mask`` is Horn's per-group structured neuron mask (inverted-dropout
    scaled at mask-creation time); group -> sample expansion happens upstream.
    ``mask_blocks`` ([G, ff/block] in {0, 1/keep}, the blocks ``hidden_mask``
    expands) enables the block-sparse Pallas path: dropped blocks of hidden
    units are *skipped* in the up/gate matmuls (kernels/dropout_matmul) —
    the paper's compute-saving claim realized.  It is taken wherever the
    kernel backend is not "ref" and the shapes tile (groups split the batch,
    blocks split d_ff, 128-row tiles); semantics identical to the masked
    dense path, which serves every other case.
    """
    act = ACTS[cfg.act]
    from repro.kernels.backend import get_backend
    backend = get_backend()
    if mask_blocks is not None and backend != "ref" \
            and _dropout_kernel_fits(x.shape, mask_blocks.shape, cfg.d_ff):
        from repro.kernels.dropout_matmul.ops import dropout_matmul
        B, S, d = x.shape
        G, nb = mask_blocks.shape
        block_n = cfg.d_ff // nb
        xg = x.reshape(G, (B // G) * S, d)
        # gate uses a {0,1} mask (masking *inside* the activation is wrong);
        # the 1/keep scale rides on the up projection.
        blocks01 = (mask_blocks > 0).astype(f32)
        # each device multiplies its own groups by its own hidden blocks
        grp = "batch" if G % ctx.dp_size == 0 else None
        ffn = "ffn" if nb % ctx.model_size == 0 else None
        dmm = ctx.shard_kernel(
            partial(dropout_matmul, block_n=block_n, backend=backend),
            in_axes=((grp, None, None), (None, ffn), (grp, ffn)),
            out_axes=(grp, None, ffn))
        if cfg.mlp_gated:
            up = dmm(xg, params["wi"], mask_blocks)
            gate = dmm(xg, params["wg"], blocks01)
            h = act(gate) * up
        else:
            # act(up * s) != act(up) * s, so mask {0,1} first, scale after
            h = act(dmm(xg, params["wi"], blocks01))
            mask = jnp.repeat(mask_blocks, block_n, axis=-1)
            h = h * mask[:, None, :]
        h = h.astype(x.dtype).reshape(B, S, cfg.d_ff)
        out = jnp.einsum("...f,fd->...d", h, params["wo"])
        return ctx.constrain(out, "batch", "seq", "act_embed")
    with jax.named_scope("mlp_block"):
        up = jnp.einsum("...d,df->...f", x, params["wi"])
        if cfg.mlp_gated:
            gate = jnp.einsum("...d,df->...f", x, params["wg"])
            h = act(gate) * up
        else:
            h = act(up)
        h = ctx.constrain(h, "batch", "seq", "act_ffn")
        if hidden_mask is not None:
            h = h * hidden_mask.astype(h.dtype)
        # row-parallel down-proj: keep the TP psum in the activation dtype
        out = jnp.einsum("...f,fd->...d", h, params["wo"],
                         preferred_element_type=x.dtype)
    return ctx.constrain(out, "batch", "seq", "act_embed")


# ---------------------------------------------------------------------------
# Mixture of Experts (sort-based static-capacity dispatch)
# ---------------------------------------------------------------------------
def moe_specs(cfg: ModelConfig):
    d, ff, e = cfg.d_model, cfg.moe_ff, cfg.num_experts
    specs = {
        "router": ParamSpec((d, e), ("embed", "experts")),
        "wi": ParamSpec((e, d, ff), ("experts", "embed", "moe_ffn")),
        "wo": ParamSpec((e, ff, d), ("experts", "moe_ffn", "embed")),
    }
    if cfg.mlp_gated:
        specs["wg"] = ParamSpec((e, d, ff), ("experts", "embed", "moe_ffn"))
    return specs


def _positions_in_segment(sorted_ids, length):
    """Given row-sorted expert ids, rank of each element within its id-segment."""
    idx = jnp.arange(length)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
    seg_start = jax.lax.associative_scan(jnp.maximum, jnp.where(is_start, idx, 0))
    return idx - seg_start


def _route_row(flat_e, num_experts):
    """Per-row routing bookkeeping.  flat_e: [S*k] expert ids.

    Returns (pos_in_expert [S*k], counts [E], order [S*k]).
    """
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    pos_sorted = _positions_in_segment(flat_e[order], n)
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    counts = jnp.sum(jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32), axis=0)
    return pos, counts, order


def moe_apply(params, x, cfg: ModelConfig, ctx: ShardingCtx, *, hidden_mask=None):
    """x: [..., S, d] -> [..., S, d] plus aux losses dict.

    Routing is per-sequence (GShard 'group = sequence'), sort-based:
    argsort tokens by expert, gather into a static [*, E, C, d] buffer, run the
    expert FFN as one einsum (experts sharded over `model` => EP all-to-all),
    scatter-gather back, combine with router weights.  Over-capacity tokens are
    dropped (residual passes them through); drop fraction reported in aux.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xr = x.reshape((-1,) + orig_shape[-2:])          # [R, S, d] rows
    R, S, _ = xr.shape
    E, K = cfg.num_experts, cfg.experts_per_tok
    C = -(-S * K * cfg.capacity_factor // E) if E else S   # ceil
    C = max(4, min(int(C), S * K))
    act = ACTS[cfg.act]

    logits = jnp.einsum("rsd,de->rse", xr, params["router"]).astype(f32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_e = jax.lax.top_k(probs, K)          # [R, S, K]
    gate_w = gate_w / jnp.clip(gate_w.sum(-1, keepdims=True), 1e-9)

    flat_e = gate_e.reshape(R, S * K)
    pos, counts, order = jax.vmap(partial(_route_row, num_experts=E))(flat_e)
    keep = pos < C                                     # [R, S*K]

    # --- dispatch: build [R, E, C] source-token indices from the sort order ---
    starts = jnp.cumsum(counts, axis=-1) - counts      # exclusive prefix  [R, E]
    slot_idx = starts[:, :, None] + jnp.arange(C)[None, None, :]       # [R, E, C]
    slot_valid = jnp.arange(C)[None, None, :] < jnp.minimum(counts, C)[:, :, None]
    slot_idx = jnp.clip(slot_idx, 0, S * K - 1)
    src_flat = jnp.take_along_axis(order, slot_idx.reshape(R, E * C), axis=1)
    src_tok = (src_flat // K).reshape(R, E, C)         # token index per slot

    disp = jnp.take_along_axis(xr, src_tok.reshape(R, E * C)[..., None], axis=1)
    disp = disp.reshape(R, E, C, d) * slot_valid[..., None].astype(x.dtype)
    disp = ctx.constrain(disp, "batch", "experts", None, "act_embed")

    # --- expert FFN ---
    with jax.named_scope("moe_ffn"):
        up = jnp.einsum("recd,edf->recf", disp, params["wi"])
        if cfg.mlp_gated:
            gate = jnp.einsum("recd,edf->recf", disp, params["wg"])
            h = act(gate) * up
        else:
            h = act(up)
        if hidden_mask is not None:                # Horn mask on expert hidden
            h = h * hidden_mask.astype(h.dtype)
        eout = jnp.einsum("recf,efd->recd", h, params["wo"])
    eout = ctx.constrain(eout, "batch", "experts", None, "act_embed")

    # --- combine: each (token, k) reads its slot (e, pos) if kept ---
    flat_pos = jnp.clip(pos, 0, C - 1)
    slot_of_choice = flat_e * C + flat_pos             # [R, S*K]
    gathered = jnp.take_along_axis(
        eout.reshape(R, E * C, d), slot_of_choice[..., None], axis=1)
    gathered = gathered.reshape(R, S, K, d) * keep.reshape(R, S, K, 1).astype(x.dtype)
    out = jnp.einsum("rskd,rsk->rsd", gathered, gate_w.astype(x.dtype))

    # --- aux losses / stats ---
    me = jnp.mean(jax.nn.one_hot(gate_e, E, dtype=f32), axis=(0, 1, 2))  # frac routed
    ce = jnp.mean(probs, axis=(0, 1))                                    # router mass
    aux = {
        "load_balance_loss": E * jnp.sum(me * ce),
        "router_z_loss": jnp.mean(jax.scipy.special.logsumexp(logits, -1) ** 2),
        "dropped_frac": 1.0 - jnp.mean(keep.astype(f32)),
    }
    return out.reshape(orig_shape), aux


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------
def embed_specs(cfg: ModelConfig):
    specs = {"embedding": ParamSpec((cfg.vocab_size, cfg.d_model),
                                    ("vocab", "embed"), "normal", 1.0)}
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                     ("embed", "vocab"))
    return specs


def embed_apply(params, tokens, cfg: ModelConfig, ctx: ShardingCtx):
    x = jnp.take(params["embedding"], tokens, axis=0).astype(cfg.dtype)
    if cfg.post_sublayer_norm:   # gemma family scales embeddings
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    if cfg.embedding_multiplier != 1.0:                  # granite
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return ctx.constrain(x, "batch", "seq", "act_embed")


def unembed_apply(params, x, cfg: ModelConfig, ctx: ShardingCtx):
    w = params.get("unembed")
    if w is None:
        w = params["embedding"].T
    logits = jnp.einsum("...d,dv->...v", x, w.astype(x.dtype))
    if cfg.logits_scaling != 1.0:                        # granite
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    if cfg.final_logit_softcap:
        c = cfg.final_logit_softcap
        logits = jnp.tanh(logits / c) * c
    return ctx.constrain(logits, "batch", "seq", "vocab")
