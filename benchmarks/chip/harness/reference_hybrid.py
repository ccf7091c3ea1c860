"""The plain reference for the Granite hybrid: granite-4.0-h-micro's
forward in jax.numpy, float32 at ``Precision.HIGHEST``.

Independent of the program: it imports nothing of it and takes only the
benchmark's own weights (``harness/weights_hybrid.py``, bf16 values, read
as float32).  Every projection goes through ``mm`` at the precision asked
for: "f32" (the reference) or "fp8" (both operands rounded to
float8_e4m3fn under one scale per tensor -- the control, one precision
below the bf16 compute the configuration states), as in
``harness/reference.py``.

Architecture (HF ``GraniteMoeHybridForCausalLM`` with no experts,
huggingface.co/ibm-granite/granite-4.0-h-micro): embeddings times
``embedding_multiplier``; per layer, x += mixer(RMSNorm(x)) *
``residual_multiplier`` and x += MLP(RMSNorm(x)) * ``residual_multiplier``,
the MLP SwiGLU (silu(x gate) * (x up)) down; final RMSNorm; the tied head
divided by ``logits_scaling``.

  attention  GQA, no position embedding, causal softmax of q k^T times
             ``attention_multiplier``;
  Mamba-2    (arXiv:2405.21060, one group) z, x, B, C, dt projections; a
             causal depthwise conv of width ``mamba_d_conv`` with bias over
             [x, B, C], then silu; dt = softplus(dt + dt_bias); A =
             -exp(A_log); the recurrence run position by position, h <-
             exp(dt A) h + dt x B^T, y = h C + D x (the definition, not the
             chunked dual form the program uses); the gated RMSNorm
             RMSNorm(y * silu(z)) * gnorm; the out projection.

Departures from HF: none in the mathematics.  HF fuses z, x, B, C, dt
into one ``in_proj`` and the conv over [x, B, C] into one ``conv1d``; the
weights here are their blocks.  HF clamps dt to ``time_step_limit``,
(0, inf) by default, which changes nothing.  The layers are scanned over
the periods of ``layer_types`` (one compile of a period, not of 40 layers).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights_hybrid as WH

f32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _round(x, dtype):
    big = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    s = jnp.where(big > 0, big, 1.0)
    return (x / s).astype(dtype).astype(f32) * s


def mm(spec: str, a, b, precision: str):
    a, b = a.astype(f32), b.astype(f32)
    if precision == "fp8":
        a, b = _round(a, jnp.float8_e4m3fn), _round(b, jnp.float8_e4m3fn)
    elif precision != "f32":
        raise ValueError(f"unknown reference precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=HI, preferred_element_type=f32)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(f32)


def attention(p, h, m: dict, precision: str):
    T = h.shape[0]
    k = WH.dims(m)
    q = mm("td,dhk->thk", h, p["wq"], precision)
    kk = mm("td,dhk->thk", h, p["wk"], precision)
    v = mm("td,dhk->thk", h, p["wv"], precision)
    q = q.reshape(T, k["kh"], k["h"] // k["kh"], k["hd"])
    s = jnp.einsum("qhgk,shk->hgqs", q, kk, precision=HI) \
        * m["attention_multiplier"]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hgqs,shk->qhgk", a, v, precision=HI).reshape(
        T, k["h"], k["hd"])
    return mm("thk,hkd->td", o, p["wo"], precision)


def mamba(p, h, m: dict, precision: str):
    T = h.shape[0]
    k = WH.dims(m)
    H, P, N, W = k["H"], k["P"], k["N"], k["W"]
    z = mm("td,de->te", h, p["in_z"], precision)
    xbc = jnp.concatenate([mm("td,de->te", h, p[n], precision)
                           for n in ("in_x", "in_B", "in_C")], -1)
    pad = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1]), f32), xbc])
    cw = p["conv_w"].astype(f32)
    conv = sum(pad[i:i + T] * cw[i] for i in range(W)) \
        + p["conv_b"].astype(f32)
    xs, Bm, Cm = jnp.split(jax.nn.silu(conv), [H * P, H * P + N], -1)
    dt = jax.nn.softplus(mm("td,dh->th", h, p["in_dt"], precision)
                         + p["dt_bias"].astype(f32))
    A = -jnp.exp(p["A_log"].astype(f32))
    D = p["D"].astype(f32)
    xs = xs.reshape(T, H, P)

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp
        state = jnp.exp(dt_t * A)[:, None, None] * state \
            + dt_t[:, None, None] * x_t[:, :, None] * B_t[None, None, :]
        y = jnp.einsum("hpn,n->hp", state, C_t, precision=HI)
        return state, y + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), f32), (xs, dt, Bm, Cm))
    y = rmsnorm(y.reshape(T, H * P) * jax.nn.silu(z), p["gnorm"],
                m["rms_norm_eps"])
    return mm("te,ed->td", y, p["out"], precision)


def _periods(w, m: dict):
    """The weights regrouped [R, per-period count, ...] by kind."""
    per = WH._period(m)
    R = len(WH.kinds(m)) // len(per)
    n_m = per.count("mamba")
    grp = {}
    for name in WH.LAYER_KEYS:
        grp[name] = w[name].reshape((R, len(per)) + w[name].shape[1:])
    for name in WH.MAMBA_KEYS:
        grp[name] = w[name].reshape((R, n_m) + w[name].shape[1:])
    for name in WH.ATTN_KEYS:
        grp[name] = w[name].reshape((R, len(per) - n_m) + w[name].shape[1:])
    return per, grp


@partial(jax.jit, static_argnames=("m_items", "precision"))
def _seq_logits(w, tokens, m_items, precision):
    m = dict(m_items)
    per, grp = _periods(w, m)
    eps, rm = m["rms_norm_eps"], m["residual_multiplier"]
    x = w["embed"][tokens].astype(f32) * m["embedding_multiplier"]

    def period(x, g):
        im = ia = 0
        for i, kind in enumerate(per):
            if kind == "mamba":
                p, im = {n: g[n][im] for n in WH.MAMBA_KEYS}, im + 1
                mix = mamba
            else:
                p, ia = {n: g[n][ia] for n in WH.ATTN_KEYS}, ia + 1
                mix = attention
            h = rmsnorm(x, g["input_norm"][i], eps)
            x = x + mix(p, h, m, precision) * rm
            h = rmsnorm(x, g["post_norm"][i], eps)
            u = jax.nn.silu(mm("td,df->tf", h, g["mlp_gate"][i], precision)) \
                * mm("td,df->tf", h, g["mlp_up"][i], precision)
            x = x + mm("tf,fd->td", u, g["mlp_down"][i], precision) * rm
        return x, None

    x, _ = jax.lax.scan(period, x, grp)
    x = rmsnorm(x, w["final_norm"], eps)
    return mm("td,vd->tv", x, w["embed"], precision) / m["logits_scaling"]


@partial(jax.jit, static_argnames=("m_items",))
def _gaps(w, tokens, picks, m_items):
    """For each position t: max_v ref[t, v] - ref[t, picks[t]] (>= 0)."""
    lg = _seq_logits(w, tokens, m_items, "f32")
    best = jnp.max(lg, -1)
    return best - jnp.take_along_axis(lg, picks[:, None], -1)[:, 0]


@partial(jax.jit, static_argnames=("m_items",))
def _argmax(w, tokens, m_items):
    return jnp.argmax(_seq_logits(w, tokens, m_items, "fp8"), -1)


KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "shared_intermediate_size", "vocab_size", "mamba_n_heads",
        "mamba_d_head", "mamba_d_state", "mamba_d_conv", "rms_norm_eps",
        "attention_multiplier", "embedding_multiplier",
        "residual_multiplier", "logits_scaling")


def model_items(m: dict):
    return tuple((k, m[k]) for k in KEYS) \
        + (("layer_types", tuple(m["layer_types"])),)


def _padded(prompt, served, pad_to: int):
    """The prompt and the served tokens as one sequence of ``pad_to`` ids
    (padding at the end changes no earlier position: causal), and the
    slice of positions whose logits chose the served tokens."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    tokens = np.zeros((pad_to,), np.int32)
    tokens[:len(seq)] = seq
    first = len(prompt) - 1
    return tokens, slice(first, first + len(served))


def served_gaps(w, m: dict, prompt, served, pad_to: int, picks=None):
    """Gap of each served token below the reference's best logit at its
    position (``picks`` overrides the tokens scored, e.g. the control's
    own choices)."""
    tokens, at = _padded(prompt, served, pad_to)
    score = np.zeros((pad_to,), np.int32)
    score[at] = served if picks is None else picks
    gaps = _gaps(w, jnp.asarray(tokens), jnp.asarray(score), model_items(m))
    return np.asarray(gaps)[at]


def control_picks(w, m: dict, prompt, served, pad_to: int):
    """The token the fp8 control puts first at each served position."""
    tokens, at = _padded(prompt, served, pad_to)
    return np.asarray(_argmax(w, jnp.asarray(tokens), model_items(m)))[at]
