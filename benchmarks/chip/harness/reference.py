"""The plain reference: Qwen3 (and Horn's parallel dropout) in jax.numpy.

Independent of the program: it imports nothing of it and takes only the
benchmark's own weights (``harness/weights.py``).  Every matrix product
goes through ``mm`` at the precision asked for:

  "f32"  float32 at ``Precision.HIGHEST`` (a TPU runs a float32 product in
         bf16 passes otherwise) -- the reference;
  "fp8"  both operands rounded to float8_e4m3fn, each tensor scaled so
         its largest magnitude meets the format's largest (and in the
         backward pass each operand's gradient rounded to float8_e5m2,
         scaled alike), then the same product -- the control, one
         precision below the bf16 compute the configurations state, as
         fp8 training and serving run it.

Architecture (Qwen3, huggingface.co/Qwen/Qwen3-1.7B): pre-norm RMSNorm
blocks; GQA attention with RMSNorm on each query and key head, rotary
embeddings (half-split) with ``rope_theta``, causal softmax; SwiGLU FFN
(silu(x W_gate) * (x W_up)) W_down; final RMSNorm; tied embedding head.

Horn (the paper's collective parallel dropout, as the configuration
states it): the batch is split into ``groups`` consecutive slices; each
group draws, per step, a block mask over the embedding channels (keep
``keep_input``) and over each layer's FFN hidden units (keep
``keep_hidden``), in blocks of ``block``, scaled by 1/keep, with at least
one live block per group.  The masks follow from the run's seed, the
step and the layer by the rule the configuration names (``mask_rule``):
threefry ``fold_in`` of the seed, the salt, the step, the layer index and
the mask's salt, uniform draws below keep.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _round(x, dtype):
    """x rounded to ``dtype`` under one scale for the whole tensor."""
    big = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    s = jnp.where(big > 0, big, 1.0)
    return (x / s).astype(dtype).astype(f32) * s


@jax.custom_vjp
def fp8(x):
    return _round(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def mm(spec: str, a, b, precision: str):
    if precision == "fp8":
        a, b = fp8(a), fp8(b)
    elif precision != "f32":
        raise ValueError(f"unknown reference precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=HI, preferred_element_type=f32)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [..., T, heads, hd]; pos [..., T]."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=f32) / hd)
    ang = pos[..., None].astype(f32) * inv                 # [..., T, hd/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p, x, pos, m: dict, precision: str, ffn_mask=None):
    """One block. x [B, T, d]; pos [B, T]; ffn_mask [B, ff] or None."""
    eps = m["rms_norm_eps"]
    B, T, _ = x.shape
    kh = m["num_key_value_heads"]
    g = m["num_attention_heads"] // kh
    h = rmsnorm(x, p["attn_norm"], eps)
    q = mm("btd,dhk->bthk", h, p["wq"], precision)
    k = mm("btd,dhk->bthk", h, p["wk"], precision)
    v = mm("btd,dhk->bthk", h, p["wv"], precision)
    q = rope(rmsnorm(q, p["q_norm"], eps), pos, m["rope_theta"])
    k = rope(rmsnorm(k, p["k_norm"], eps), pos, m["rope_theta"])
    q = q.reshape(B, T, kh, g, -1)
    s = mm("bqhgk,bshk->bhgqs", q, k, precision) * q.shape[-1] ** -0.5
    causal = pos[:, None, None, :, None] >= pos[:, None, None, None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = mm("bhgqs,bshk->bqhgk", a, v, precision).reshape(B, T, -1,
                                                         q.shape[-1])
    x = x + mm("bthk,hkd->btd", o, p["wo"], precision)
    h = rmsnorm(x, p["ffn_norm"], eps)
    u = jax.nn.silu(mm("btd,df->btf", h, p["w_gate"], precision)) \
        * mm("btd,df->btf", h, p["w_up"], precision)
    if ffn_mask is not None:
        u = u * ffn_mask[:, None, :]
    return x + mm("btf,fd->btd", u, p["w_down"], precision)


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
              "ffn_norm", "w_gate", "w_up", "w_down")


def hidden(w, tokens, pos, m: dict, precision: str, in_mask=None,
           ffn_masks=None, remat: bool = False):
    """Final-normed hidden states [B, T, d]."""
    x = w["embed"][tokens]
    if in_mask is not None:
        x = x * in_mask[:, None, :]
    stack = {k: w[k] for k in LAYER_KEYS}

    def body(x, inp):
        p, fm = inp
        return layer(p, x, pos, m, precision, fm), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, (stack, ffn_masks))
    return rmsnorm(x, w["final_norm"], m["rms_norm_eps"])


def logits(w, x, precision: str):
    return mm("...d,vd->...v", x, w["embed"], precision)


# ---------------------------------------------------------------------------
# Serving: one sequence at a time, logits at every position
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("m_items", "precision"))
def _seq_logits(w, tokens, m_items, precision):
    m = dict(m_items)
    pos = jnp.arange(tokens.shape[0])[None]
    x = hidden(w, tokens[None], pos, m, precision)[0]
    return logits(w, x, precision)


@partial(jax.jit, static_argnames=("m_items",))
def _gaps(w, tokens, picks, m_items):
    """For each position t: max_v ref[t, v] - ref[t, picks[t]] (>= 0)."""
    lg = _seq_logits(w, tokens, m_items, "f32")
    best = jnp.max(lg, -1)
    return best - jnp.take_along_axis(lg, picks[:, None], -1)[:, 0]


@partial(jax.jit, static_argnames=("m_items",))
def _argmax(w, tokens, m_items, precision="fp8"):
    return jnp.argmax(_seq_logits(w, tokens, m_items, precision), -1)


def model_items(m: dict):
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "rms_norm_eps", "rope_theta")
    return tuple((k, m[k]) for k in keys)


def _padded(prompt, served, pad_to: int):
    """The prompt and the served tokens as one sequence of ``pad_to``
    ids (padding at the end changes no earlier position: causal), and the
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


# ---------------------------------------------------------------------------
# Training: Horn masks, loss, AdamW
# ---------------------------------------------------------------------------
def group_block_mask(key, groups: int, n_blocks: int, keep: float):
    u = jax.random.uniform(key, (groups, n_blocks))
    live = u < keep
    fallback = jax.nn.one_hot(jnp.argmax(u, -1), n_blocks, dtype=bool)
    live = jnp.where(live.any(-1, keepdims=True), live, fallback)
    return live.astype(f32) / keep


def horn_masks(seed: int, step, m: dict, horn: dict):
    """(input mask [G, d], FFN masks [L, G, ff]) of one step, per unit."""
    rule = horn["mask_rule"]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed),
                                                rule["salt"]), step)
    G, blk = horn["groups"], horn["block"]
    d, ff = m["hidden_size"], m["intermediate_size"]

    def mask(layer_id, salt, units, keep):
        k = jax.random.fold_in(jax.random.fold_in(key, layer_id), salt)
        return jnp.repeat(group_block_mask(k, G, units // blk, keep), blk, -1)

    in_mask = mask(rule["input_layer_id"], rule["input_salt"], d,
                   horn["keep_input"])
    ffn = jnp.stack([mask(li, rule["ffn_salt"], ff, horn["keep_hidden"])
                     for li in range(m["num_hidden_layers"])])
    return in_mask, ffn


def train_loss(w, tokens, labels, in_mask, ffn_masks, m: dict,
               precision: str, rows=None, chunk: int = 512):
    """Mean next-token cross-entropy; group g's masks apply to its
    consecutive slice of the batch.  ``rows`` keeps only the first rows
    (a planted fault: half the batch left out, the mean over the rest)."""
    B, T = tokens.shape
    G = in_mask.shape[0]
    per = B // G
    im = jnp.repeat(in_mask, per, 0)
    fm = jnp.repeat(ffn_masks, per, 1)                  # [L, B, ff]
    if rows is not None:
        tokens, labels, im, fm = tokens[:rows], labels[:rows], im[:rows], \
            fm[:, :rows]
    pos = jnp.broadcast_to(jnp.arange(T)[None], tokens.shape)
    x = hidden(w, tokens, pos, m, precision, im, fm, remat=True)
    xs = x.reshape(-1, chunk, x.shape[-1])
    ls = labels.reshape(-1, chunk)

    @jax.checkpoint
    def one(tot, inp):
        h, lb = inp
        lg = logits(w, h, precision)
        nll = jax.nn.logsumexp(lg, -1) \
            - jnp.take_along_axis(lg, lb[:, None], -1)[:, 0]
        return tot + nll.sum(), None

    tot, _ = jax.lax.scan(one, jnp.zeros((), f32), (xs, ls))
    return tot / ls.size


def adamw(w, g, m_, v_, t, opt: dict):
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    m_ = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m_, g)
    v_ = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v_, g)
    mh = jax.tree.map(lambda a: a / (1 - b1 ** t), m_)
    vh = jax.tree.map(lambda a: a / (1 - b2 ** t), v_)
    w = jax.tree.map(lambda p, a, b: p - lr * (a / (jnp.sqrt(b) + eps)
                                               + opt["weight_decay"] * p),
                     w, mh, vh)
    return w, m_, v_


def clip(g, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    s = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda x: x * s, g)
