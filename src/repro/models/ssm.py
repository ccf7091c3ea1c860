"""Mamba2 (SSD — state-space duality) mixer, pure-JAX chunked reference.

The chunked SSD algorithm (arXiv:2405.21060) processes the sequence in chunks:
inside a chunk the dual quadratic form is used (small Q x Q matmuls — MXU
friendly), between chunks a linear recurrence carries the [H, P, N] state.
We scan chunks sequentially (lax.scan), which bounds activation memory to one
chunk and maps 1:1 onto the Pallas kernel's sequential grid.

Layout notes: ngroups = 1 (public mamba2 configs), so B/C are shared across
heads.  Heads shard over `model` (logical axis "ssm_heads").
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.mesh import ShardingCtx
from repro.models.params import ParamSpec

f32 = jnp.float32


def ssm_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    """(d_inner, n_heads, head_dim P, state N)."""
    d_in = cfg.ssm_expand * cfg.d_model
    return d_in, d_in // cfg.ssm_head_dim, cfg.ssm_head_dim, cfg.ssm_state


def mamba_specs(cfg: ModelConfig):
    d = cfg.d_model
    d_in, H, P, N = ssm_dims(cfg)
    W = cfg.ssm_conv_width
    return {
        "wz": ParamSpec((d, d_in), ("embed", "ssm_inner")),
        "wx": ParamSpec((d, d_in), ("embed", "ssm_inner")),
        "wB": ParamSpec((d, N), ("embed", "ssm_state")),
        "wC": ParamSpec((d, N), ("embed", "ssm_state")),
        "wdt": ParamSpec((d, H), ("embed", "ssm_heads")),
        "dt_bias": ParamSpec((H,), ("ssm_heads",), "zeros"),
        "A_log": ParamSpec((H,), ("ssm_heads",), "ones", 0.5),   # A = -exp(A_log)
        "D": ParamSpec((H,), ("ssm_heads",), "ones"),
        "conv_x": ParamSpec((W, d_in), ("conv", "ssm_inner"), "normal", 0.5),
        "conv_x_bias": ParamSpec((d_in,), ("ssm_inner",), "zeros"),
        "conv_B": ParamSpec((W, N), ("conv", "ssm_state"), "normal", 0.5),
        "conv_C": ParamSpec((W, N), ("conv", "ssm_state"), "normal", 0.5),
        **({"conv_B_bias": ParamSpec((N,), ("ssm_state",), "zeros"),
            "conv_C_bias": ParamSpec((N,), ("ssm_state",), "zeros")}
           if cfg.ssm_conv_bias_bc else {}),
        "gnorm": ParamSpec((d_in,), ("ssm_inner",), "zeros"),
        "wo": ParamSpec((d_in, d), ("ssm_inner", "embed")),
    }


# ---------------------------------------------------------------------------
# Causal depthwise conv (width-4) — shift-and-add, no conv primitive needed
# ---------------------------------------------------------------------------
def causal_conv(x, weight, bias=None):
    """x: [B, S, C]; weight: [W, C] depthwise."""
    W = weight.shape[0]
    pad = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    S = x.shape[1]
    out = sum(pad[:, w:w + S] * weight[w] for w in range(W))
    if bias is not None:
        out = out + bias
    return out


def conv_decode_step(conv_state, x_new, weight, bias=None):
    """conv_state: [B, W-1, C]; x_new: [B, C] -> (y [B, C], new_state)."""
    full = jnp.concatenate([conv_state, x_new[:, None]], axis=1)   # [B, W, C]
    y = jnp.einsum("bwc,wc->bc", full, weight)
    if bias is not None:
        y = y + bias
    return y, full[:, 1:]


# ---------------------------------------------------------------------------
# Chunked SSD core
# ---------------------------------------------------------------------------
def ssd_chunked(x, dt, A, Bm, Cm, *, chunk: int, initial_state=None):
    """Chunked state-space duality.

    x:  [B, S, H, P]     (already multiplied by nothing; dt applied inside)
    dt: [B, S, H]        (post-softplus, > 0)
    A:  [H]              (negative)
    Bm, Cm: [B, S, N]    (ngroups = 1)
    Returns (y [B, S, H, P], final_state [B, H, P, N]) — fp32 state.
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    while S % Q:
        Q //= 2
    nc = S // Q

    xc = x.reshape(B, nc, Q, H, P).transpose(1, 0, 2, 3, 4)
    dtc = dt.reshape(B, nc, Q, H).transpose(1, 0, 2, 3).astype(f32)
    Bc = Bm.reshape(B, nc, Q, N).transpose(1, 0, 2, 3).astype(f32)
    Cc = Cm.reshape(B, nc, Q, N).transpose(1, 0, 2, 3).astype(f32)
    Af = A.astype(f32)

    if initial_state is None:
        initial_state = jnp.zeros((B, H, P, N), f32)

    idx = jnp.arange(Q)
    causal = idx[:, None] >= idx[None, :]                          # [Q, Q]

    def step(state, inp):
        xq, dtq, Bq, Cq = inp                   # [B,Q,H,P],[B,Q,H],[B,Q,N]x2
        xq = xq.astype(f32)
        dA = dtq * Af[None, None]                # [B,Q,H] (negative)
        cum = jnp.cumsum(dA, axis=1)             # [B,Q,H]
        # --- intra-chunk (dual quadratic form) ---
        CB = jnp.einsum("bin,bjn->bij", Cq, Bq)  # [B,Q,Q]
        # masked before the exp: above the diagonal cum_i - cum_j > 0 grows
        # with the chunk and overflows to inf (and inf * 0 is nan)
        seg = jnp.where(causal[None, :, :, None],
                        cum[:, :, None] - cum[:, None, :], -jnp.inf)
        T = CB[..., None] * jnp.exp(seg) * dtq[:, None]             # [B,Q,Q,H]
        y = jnp.einsum("bijh,bjhp->bihp", T, xq)
        # --- contribution of carried state ---
        y = y + jnp.einsum("bin,bhpn,bih->bihp", Cq, state, jnp.exp(cum))
        # --- state update ---
        seg = jnp.exp(cum[:, -1:, :] - cum) * dtq                    # [B,Q,H]
        new_state = (state * jnp.exp(cum[:, -1])[..., None, None]
                     + jnp.einsum("bjh,bjhp,bjn->bhpn", seg, xq, Bq))
        return new_state, y

    with jax.named_scope("ssd_chunk"):
        if nc == 1:
            final, y = step(initial_state, jax.tree.map(lambda t: t[0],
                                                        (xc, dtc, Bc, Cc)))
            y = y[None]
        else:
            final, y = jax.lax.scan(step, initial_state, (xc, dtc, Bc, Cc))
    y = y.transpose(1, 0, 2, 3, 4).reshape(B, S, H, P)
    return y.astype(x.dtype), final


def ssd_decode_step(state, x, dt, A, Bm, Cm):
    """One-token recurrence.  x: [B,H,P], dt: [B,H], Bm/Cm: [B,N].

    Returns (y [B,H,P], new_state [B,H,P,N]).
    """
    xf, dtf = x.astype(f32), dt.astype(f32)
    dA = jnp.exp(dtf * A.astype(f32))[..., None, None]              # [B,H,1,1]
    upd = dtf[..., None, None] * xf[..., None] * Bm[:, None, None, :].astype(f32)
    new_state = state * dA + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, Cm.astype(f32))
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Full Mamba2 sublayer
# ---------------------------------------------------------------------------
def _gated_norm(params, y, z, cfg: ModelConfig):
    """RMSNormGated: RMSNorm(y * silu(z)) * (1 + w)."""
    g = (y * jax.nn.silu(z)).astype(f32)
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    w = 1.0 + params["gnorm"].astype(f32)
    out = g * jax.lax.rsqrt(var + cfg.norm_eps) \
        * w.reshape((1,) * (g.ndim - 1) + (-1,))
    return out.astype(y.dtype)


def _in_proj(params, x):
    """(z, x, B, C, dt after softplus (f32), A (f32)) of the mixer's
    input projection."""
    z = jnp.einsum("bsd,de->bse", x, params["wz"])
    xs = jnp.einsum("bsd,de->bse", x, params["wx"])
    Bs = jnp.einsum("bsd,dn->bsn", x, params["wB"])
    Cs = jnp.einsum("bsd,dn->bsn", x, params["wC"])
    dt = jnp.einsum("bsd,dh->bsh", x, params["wdt"])
    dt = jax.nn.softplus(dt.astype(f32)
                         + params["dt_bias"].astype(f32)[None, None])
    A = -jnp.exp(params["A_log"].astype(f32))
    return z, xs, Bs, Cs, dt, A


def mamba_apply(params, x, cfg: ModelConfig, ctx: ShardingCtx, *,
                cache=None, channel_mask=None):
    """Mamba2 mixer.

    Train/prefill: cache None -> (out, (conv_states, ssm_state)) final states.
    Decode: cache = (conv_states [B, W-1, d_in + 2N], ssm_state [B,H,P,N]),
    x: [B, 1, d].  channel_mask: Horn per-group mask over d_inner ([B, 1, d_in]).
    """
    B, S, _ = x.shape
    d_in, H, P, N = ssm_dims(cfg)
    z, xs, Bs, Cs, dt, A = _in_proj(params, x)

    if cache is None:
        xs = jax.nn.silu(causal_conv(xs, params["conv_x"], params["conv_x_bias"]))
        Bs = jax.nn.silu(causal_conv(Bs, params["conv_B"],
                                     params.get("conv_B_bias")))
        Cs = jax.nn.silu(causal_conv(Cs, params["conv_C"],
                                     params.get("conv_C_bias")))
        if channel_mask is not None:
            xs = xs * channel_mask.astype(xs.dtype)
        xs = ctx.constrain(xs, "batch", "seq", "ssm_inner")
        xh = xs.reshape(B, S, H, P)
        y, final = ssd_chunked(xh, dt, A, Bs, Cs, chunk=cfg.ssm_chunk)
        y = y + xh * params["D"].astype(y.dtype)[:, None]
        # conv tail state for a later decode continuation
        tail = jnp.concatenate([xs, Bs, Cs], axis=-1)[:, -(cfg.ssm_conv_width - 1):]
        new_cache = (tail, final)
    else:
        conv_state, ssm_state = cache
        W = cfg.ssm_conv_width
        cx, cB, cC = jnp.split(conv_state, [d_in, d_in + N], axis=-1)
        xs1, cx = conv_decode_step(cx, xs[:, 0], params["conv_x"],
                                   params["conv_x_bias"])
        Bs1, cB = conv_decode_step(cB, Bs[:, 0], params["conv_B"],
                                   params.get("conv_B_bias"))
        Cs1, cC = conv_decode_step(cC, Cs[:, 0], params["conv_C"],
                                   params.get("conv_C_bias"))
        xs1, Bs1, Cs1 = map(jax.nn.silu, (xs1, Bs1, Cs1))
        if channel_mask is not None:
            xs1 = xs1 * channel_mask[:, 0].astype(xs1.dtype)
        xh = xs1.reshape(B, H, P)
        y, ssm_state = ssd_decode_step(ssm_state, xh, dt[:, 0], A, Bs1, Cs1)
        y = y + xh * params["D"].astype(y.dtype)[:, None]
        y = y[:, None]                                      # [B, 1, H, P]
        new_cache = (jnp.concatenate([cx, cB, cC], axis=-1), ssm_state)

    y = y.reshape(B, S, d_in)
    y = _gated_norm(params, y, z, cfg)
    out = jnp.einsum("bse,ed->bsd", y, params["wo"])
    return ctx.constrain(out, "batch", "seq", "act_embed"), new_cache


def padding_masked(dt, chunk_lens):
    """dt [B, C, H] with 0 at each slot's positions past ``chunk_lens``:
    there exp(0 A) = 1 and dt x B^T = 0, so the state passes unchanged."""
    valid = jnp.arange(dt.shape[1])[None, :] < chunk_lens[:, None]
    return jnp.where(valid[..., None], dt, 0.0)


def mamba_paged(params, x, cfg: ModelConfig, ctx: ShardingCtx, *, cache,
                index, starts, chunk_lens):
    """Mamba2 mixer in the unified serving step: slot b's chunk x[b]
    ([B, C, d], ``chunk_lens[b]`` real positions, the rest padding)
    continues from the slot's stored state.

    cache = the Mamba layers' state pool (conv tails [Lm, B, W-1,
    d_in + 2N] -- the last W-1 pre-conv x, B, C rows each slot has seen
    --, SSM states [Lm, B, H, P, N] f32), of which this layer owns row
    ``index``.  A slot whose chunk starts at position 0 (``starts == 0``
    with real positions: a fresh or recomputed sequence) starts from
    zeros; a slot with no positions this tick (the engine fills its
    ``starts`` and ``chunk_lens`` with 0) keeps its state.  Padded
    positions take dt = 0, so the state passes through them unchanged; the
    new conv tail is the W-1 rows ending at the chunk's last real position
    (the old tail at chunk_len 0).  Returns (out [B, C, d], the pool with row
    ``index`` advanced in place).
    """
    B, C, _ = x.shape
    d_in, H, P, N = ssm_dims(cfg)
    W = cfg.ssm_conv_width
    z, xs, Bs, Cs, dt, A = _in_proj(params, x)
    tails, states = cache
    tail = jax.lax.dynamic_index_in_dim(tails, index, 0, keepdims=False)
    fresh = (starts == 0) & (chunk_lens > 0)
    tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail), tail)
    xbc = jnp.concatenate([xs, Bs, Cs], axis=-1)
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    weight = jnp.concatenate(
        [params["conv_x"], params["conv_B"], params["conv_C"]], axis=-1)
    bias = jnp.concatenate(
        [params["conv_x_bias"],
         params.get("conv_B_bias", jnp.zeros((N,), weight.dtype)),
         params.get("conv_C_bias", jnp.zeros((N,), weight.dtype))])
    conv = sum(full[:, w:w + C] * weight[w][None, None] for w in range(W)) \
        + bias[None, None]
    xs, Bs, Cs = jnp.split(jax.nn.silu(conv), [d_in, d_in + N], axis=-1)
    rows = chunk_lens[:, None] + jnp.arange(W - 1)[None, :]       # [B, W-1]
    new_tail = jnp.take_along_axis(full, rows[..., None], axis=1)
    tails = jax.lax.dynamic_update_index_in_dim(
        tails, new_tail.astype(tails.dtype), index, 0)
    dt = padding_masked(dt, chunk_lens)
    xh = xs.reshape(B, C, H, P)
    # on the chip the kernel reads and writes row ``index`` of the
    # (donated) pool in place, zeroing a fresh slot's state as it reads it
    from repro.kernels.ssd.ops import ssd_chunk_scan
    y, states = ssd_chunk_scan(xh, dt, A, Bs, Cs, states, fresh, index,
                               chunk=cfg.ssm_chunk)
    y = y + xh * params["D"].astype(y.dtype)[None, None, :, None]
    y = _gated_norm(params, y.reshape(B, C, d_in), z, cfg)
    out = jnp.einsum("bse,ed->bsd", y, params["wo"])
    return ctx.constrain(out, "batch", "seq", "act_embed"), (tails, states)
