"""Pure-jnp oracles for the paged-attention kernels.

Gathers the K/V pages named by each sequence's block table into a contiguous
[B, maxp * psize, KH, D] view and runs a masked softmax — the same math the
Pallas kernels perform page-by-page in VMEM.  Two entry points:

  paged_attention_ref        one query token per sequence (decode)
  paged_chunk_attention_ref  a C-token chunk per sequence (chunked prefill /
                             the unified serving step); each token attends to
                             prior context plus the causal prefix of its own
                             chunk, all read back from the page pool

Used on CPU (where Pallas cannot lower) and as the allclose reference in
tests.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

f32 = jnp.float32
NEG_INF = -1e30


def dequantize_pages(pages, scale):
    """int8 pool [P, KH, psize, D] + per-(page, kv-head) scale [P, KH] ->
    f32 pool (the pure-jnp mirror of the kernel's in-register dequant)."""
    if scale is None:
        return pages
    return pages.astype(f32) * scale[:, :, None, None]


def gather_pages(pages, block_tables):
    """[P, KH, psize, D] pool + [B, maxp] block table -> the contiguous
    [B, maxp * psize, KH, D] K or V view of every sequence."""
    g = pages[block_tables].astype(f32)                 # [B, maxp, KH, psize, D]
    B, maxp, KH, psize, D = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(B, maxp * psize, KH, D)


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths, *,
                        scale: float, window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        k_scale=None, v_scale=None):
    """Single-token decode attention over a block-paged KV pool.

    q:            [B, H, D]   one query token per sequence
    k/v_pages:    [P, KH, psize, D]  shared page pool (page 0 = null page)
    block_tables: [B, maxp] int32    page ids per sequence, 0-padded
    lengths:      [B] int32          valid KV tokens per sequence (incl. the
                                     token just written at position len-1)
    k/v_scale:    [P, KH] f32, optional — int8-pool mode (pages are int8,
                                     dequantized before the gather)
    Returns [B, H, D].
    """
    k_pages = dequantize_pages(k_pages, k_scale)
    v_pages = dequantize_pages(v_pages, v_scale)
    B, H, D = q.shape
    KH, psize = k_pages.shape[1], k_pages.shape[2]
    G = H // KH
    S = block_tables.shape[1] * psize

    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    qg = q.reshape(B, KH, G, D).astype(f32)

    s = jnp.einsum("bhgd,bshd->bhgs", qg, k) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    kp = jnp.arange(S)[None, :]
    mask = jnp.where(kp >= lengths[:, None], NEG_INF, 0.0)
    if window is not None:
        qpos = (lengths - 1)[:, None]
        mask = jnp.where(kp <= qpos - window, NEG_INF, mask)
    s = s + mask[:, None, None]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v)
    # empty slots (length 0): softmax's shift-invariance would turn the
    # all-masked row into a uniform average of garbage — emit zeros like
    # the kernel (whose l accumulator stays 0) instead
    out = jnp.where(lengths[:, None, None, None] > 0, out, 0.0)
    return out.reshape(B, H, D).astype(q.dtype)


def paged_chunk_attention_ref(q, k_pages, v_pages, block_tables, starts,
                              chunk_lens, *, scale: float,
                              window: Optional[int] = None,
                              softcap: Optional[float] = None,
                              k_scale=None, v_scale=None, logit_index=None):
    """Chunk-append attention over a block-paged KV pool.

    q:            [B, C, H, D]  a chunk of C tokens per sequence, right-padded
                  (token j of sequence b sits at absolute position
                  ``starts[b] + j``; rows with j >= chunk_lens[b] are padding)
    k/v_pages:    [P, KH, psize, D]  shared page pool.  The chunk's own K/V
                  must already be written (append-then-attend)
    block_tables: [B, maxp] int32    page ids per sequence, 0-padded
    starts:       [B] int32          KV tokens in pages *before* this chunk
    chunk_lens:   [B] int32          valid tokens in this chunk (0 = idle slot)
    k/v_scale:    [P, KH] f32, optional — int8-pool mode
    logit_index:  [B, S] int32, optional — additionally return the S
                  selected chunk rows per slot (the kernel's fused verify
                  window): (out [B, C, H, D], out_win [B, S, H, D])
    Returns [B, C, H, D]; padding rows (and fully-idle slots) emit zeros.

    With C == 1 and chunk_lens == 1 this is exactly ``paged_attention_ref``
    at ``lengths = starts + 1`` — the decode special case.
    """
    k_pages = dequantize_pages(k_pages, k_scale)
    v_pages = dequantize_pages(v_pages, v_scale)
    B, C, H, D = q.shape
    KH, psize = k_pages.shape[1], k_pages.shape[2]
    G = H // KH
    S = block_tables.shape[1] * psize

    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    qg = q.reshape(B, C, KH, G, D).astype(f32)

    s = jnp.einsum("bchgd,bshd->bhgcs", qg, k) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    kp = jnp.arange(S)[None, None, :]                       # [1, 1, S]
    qpos = starts[:, None] + jnp.arange(C)[None, :]         # [B, C]
    lengths = starts + chunk_lens
    mask = jnp.where(kp >= lengths[:, None, None], NEG_INF, 0.0)
    mask = jnp.where(kp > qpos[..., None], NEG_INF, mask)   # causal own-chunk
    if window is not None:
        mask = jnp.where(kp <= qpos[..., None] - window, NEG_INF, mask)
    s = s + mask[:, None, None]                             # [B,KH,G,C,S]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgcs,bshd->bchgd", p, v)
    # padding rows (j >= chunk_len) still attend to the valid prior context
    # (their qpos lies past it), producing well-defined but meaningless
    # output; zero them like the kernel, which masks them at emit time
    valid = jnp.arange(C)[None, :] < chunk_lens[:, None]    # [B, C]
    out = jnp.where(valid[:, :, None, None, None], out, 0.0)
    out = out.reshape(B, C, H, D).astype(q.dtype)
    if logit_index is not None:
        win = jnp.take_along_axis(
            out, logit_index[:, :, None, None].astype(jnp.int32), axis=1)
        return out, win
    return out
