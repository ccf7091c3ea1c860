"""A plain Granite-4.0-H hybrid forward in jax.numpy, float32, for the
tests to hold the serving engine to.

It imports nothing of the program.  ``m`` holds the HF config's keys
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``layer_types``, ``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``mamba_d_conv``, ``rms_norm_eps``, ``attention_multiplier``,
``embedding_multiplier``, ``residual_multiplier``, ``logits_scaling``) and
``w`` the weights: ``embed`` [V, d], ``final_norm`` [d], and ``layers``, one
dict per layer with ``input_norm``, ``post_norm`` [d], ``gate``, ``up``
[d, ff], ``down`` [ff, d] and either the attention's ``wq`` [d, h, hd],
``wk``, ``wv`` [d, kh, hd], ``wo`` [h, hd, d] or the Mamba-2 mixer's
``in_z``, ``in_x`` [d, d_in], ``in_B``, ``in_C`` [d, N], ``in_dt`` [d, H],
``conv_w`` [W, d_in + 2N], ``conv_b`` [d_in + 2N], ``dt_bias``, ``A_log``,
``D`` [H], ``gnorm`` [d_in], ``out`` [d_in, d].  Norm weights multiply
(HF's convention).

The model (HF ``GraniteMoeHybridForCausalLM`` with no experts):
embeddings times ``embedding_multiplier``; per layer x += mixer(RMSNorm(x))
* residual_multiplier, then x += MLP(RMSNorm(x)) * residual_multiplier with
the SwiGLU MLP silu(x gate) * (x up) down; a final RMSNorm and the tied
head, divided by ``logits_scaling``.  Attention: GQA, no position
embedding, causal softmax of q k^T * ``attention_multiplier``.  Mamba-2
(arXiv:2405.21060, one group): the projections z, x, B, C, dt; a causal
depthwise conv of width W with bias over [x, B, C], then silu;
dt = softplus(dt + dt_bias), A = -exp(A_log); the recurrence run position
by position -- h <- exp(dt A) h + dt x B^T, y = h C + D x -- which is the
definition, not the chunked dual form; the gated RMSNorm
RMSNorm(y * silu(z)) * gnorm; the out projection.

Departures from HF: none in the mathematics.  HF fuses z, x, B, C and dt
into one ``in_proj`` and keeps the conv as one ``conv1d``; the split
weights here are its column blocks.  ``precision="fp8"`` rounds both
operands of every projection to float8_e4m3fn (one scale per tensor) --
the control, one precision below the bf16 the engine computes in.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

f32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _fp8(x):
    big = jnp.max(jnp.abs(x)) / float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.where(big > 0, big, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(f32) * s


def mm(spec, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI, preferred_element_type=f32)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def attention(p, h, m, precision):
    T = h.shape[0]
    kh = m["num_key_value_heads"]
    g = m["num_attention_heads"] // kh
    q = mm("td,dhk->thk", h, p["wq"], precision)
    k = mm("td,dhk->thk", h, p["wk"], precision)
    v = mm("td,dhk->thk", h, p["wv"], precision)
    q = q.reshape(T, kh, g, -1)
    s = jnp.einsum("qhgk,shk->hgqs", q, k, precision=HI) \
        * m["attention_multiplier"]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hgqs,shk->qhgk", a, v, precision=HI).reshape(
        T, -1, q.shape[-1])
    return mm("thk,hkd->td", o, p["wo"], precision)


def mamba(p, h, m, precision):
    T = h.shape[0]
    H, P, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    W = m["mamba_d_conv"]
    z = mm("td,de->te", h, p["in_z"], precision)
    xbc = jnp.concatenate([mm("td,de->te", h, p[k], precision)
                           for k in ("in_x", "in_B", "in_C")], -1)
    pad = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1]), f32), xbc])
    conv = sum(pad[i:i + T] * p["conv_w"][i] for i in range(W)) + p["conv_b"]
    xs, Bm, Cm = jnp.split(jax.nn.silu(conv), [H * P, H * P + N], -1)
    dt = jax.nn.softplus(mm("td,dh->th", h, p["in_dt"], precision)
                         + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xs = xs.reshape(T, H, P)

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp
        state = jnp.exp(dt_t * A)[:, None, None] * state \
            + dt_t[:, None, None] * x_t[:, :, None] * B_t[None, None, :]
        y = jnp.einsum("hpn,n->hp", state, C_t, precision=HI)
        return state, y + p["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), f32), (xs, dt, Bm, Cm))
    y = y.reshape(T, H * P) * jax.nn.silu(z)
    y = rmsnorm(y, p["gnorm"], m["rms_norm_eps"])
    return mm("te,ed->td", y, p["out"], precision)


@partial(jax.jit, static_argnames=("scalars", "kinds", "precision"))
def _logits(w, tokens, scalars, kinds, precision):
    m = dict(scalars)
    eps, rm = m["rms_norm_eps"], m["residual_multiplier"]
    x = w["embed"][tokens] * m["embedding_multiplier"]
    for kind, p in zip(kinds, w["layers"]):
        h = rmsnorm(x, p["input_norm"], eps)
        mix = attention if kind == "attention" else mamba
        x = x + mix(p, h, m, precision) * rm
        h = rmsnorm(x, p["post_norm"], eps)
        u = jax.nn.silu(mm("td,df->tf", h, p["gate"], precision)) \
            * mm("td,df->tf", h, p["up"], precision)
        x = x + mm("tf,fd->td", u, p["down"], precision) * rm
    x = rmsnorm(x, w["final_norm"], eps)
    return mm("td,vd->tv", x, w["embed"], precision) / m["logits_scaling"]


SCALARS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
           "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
           "rms_norm_eps", "attention_multiplier", "embedding_multiplier",
           "residual_multiplier", "logits_scaling")


def logits(w, tokens, m: dict, precision: str = "f32"):
    """[T, V] logits of one sequence ``tokens`` [T] at every position."""
    return _logits(w, jnp.asarray(tokens, jnp.int32),
                   tuple((k, m[k]) for k in SCALARS),
                   tuple(m["layer_types"]), precision)
