"""Backend-switched paged attention (decode + chunk-append) and the paged
KV-pool scatter updates (f32/bf16 pools and the int8 + per-page-scale
quantized mode)."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.backend import get_backend
from repro.kernels.paged_attention.kernel import NULL_PAGE
from repro.kernels.paged_attention.kernel import paged_attention as _pallas
from repro.kernels.paged_attention.kernel import \
    paged_chunk_attention as _pallas_chunk
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_chunk_attention_ref)
from repro.optim.compression import quantize_int8

f32 = jnp.float32

# Default pages-per-grid-step for the Pallas kernels (the engine sets this
# once at construction from EngineConfig.pages_per_step, before tracing its
# jitted steps; kernel-level callers can always pass pages_per_step=...
# explicitly).  None: the chunk kernel derives its tiling from the call's
# shapes (``kernel.chunk_tiling``), the decode kernel takes one page.
_PAGES_PER_STEP: Optional[int] = None


def set_pages_per_step(n: Optional[int]) -> None:
    """Set the process-wide default ``pages_per_step`` for the paged
    kernels (None: derived from shapes).  A static tuning knob: it is read
    at trace time, so set it before the first call of any jitted step that
    should use it."""
    global _PAGES_PER_STEP
    if n is not None and n < 1:
        raise ValueError(f"pages_per_step must be >= 1, got {n}")
    _PAGES_PER_STEP = None if n is None else int(n)


def get_pages_per_step() -> Optional[int]:
    return _PAGES_PER_STEP


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale: float, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    k_scale=None, v_scale=None, **kw):
    """Dispatch [B, H, D] paged decode attention to pallas / interpret / ref."""
    backend = kw.pop("backend", None) or get_backend()
    if backend == "ref":
        kw.pop("pages_per_step", None)
        return paged_attention_ref(q, k_pages, v_pages, block_tables, lengths,
                                   scale=scale, window=window, softcap=softcap,
                                   k_scale=k_scale, v_scale=v_scale)
    if _PAGES_PER_STEP is not None:
        kw.setdefault("pages_per_step", _PAGES_PER_STEP)
    return _pallas(q, k_pages, v_pages, block_tables, lengths, scale=scale,
                   window=window, softcap=softcap, k_scale=k_scale,
                   v_scale=v_scale, interpret=backend == "interpret", **kw)


def paged_chunk_attention(q, k_pages, v_pages, block_tables, starts,
                          chunk_lens, *, scale: float,
                          window: Optional[int] = None,
                          softcap: Optional[float] = None,
                          k_scale=None, v_scale=None, logit_index=None,
                          **kw):
    """Dispatch [B, C, H, D] chunk-append paged attention (the unified
    serving step: decode tokens are C == 1 chunks, prompt chunks are wider).
    ``logit_index`` [B, S] turns on the fused verify-window output (returns
    (out, out_win)); ``k_scale``/``v_scale`` select the int8-pool mode."""
    backend = kw.pop("backend", None) or get_backend()
    if backend == "ref":
        kw.pop("pages_per_step", None)
        return paged_chunk_attention_ref(
            q, k_pages, v_pages, block_tables, starts, chunk_lens,
            scale=scale, window=window, softcap=softcap,
            k_scale=k_scale, v_scale=v_scale, logit_index=logit_index)
    kw.setdefault("pages_per_step", _PAGES_PER_STEP)
    return _pallas_chunk(q, k_pages, v_pages, block_tables, starts,
                         chunk_lens, scale=scale, window=window,
                         softcap=softcap, k_scale=k_scale, v_scale=v_scale,
                         logit_index=logit_index,
                         interpret=backend == "interpret", **kw)


def paged_pool_update(pool, new, block_tables, positions):
    """Write one token per sequence into its page at ``positions``.

    pool: [P, KH, psize, D]; new: [B, KH, D]; block_tables: [B, maxp];
    positions: [B] absolute write positions.  Empty slots must point at the
    reserved null page 0 (their garbage writes land there harmlessly).
    """
    psize = pool.shape[2]
    page = jnp.take_along_axis(
        block_tables, (positions // psize)[:, None], axis=1)[:, 0]
    slot = positions % psize
    return pool.at[page, :, slot].set(new.astype(pool.dtype))


def paged_pool_append(pool, new, block_tables, starts, chunk_lens):
    """Scatter each sequence's C-token chunk into its pages.

    pool: [P, KH, psize, D]; new: [B, C, KH, D]; block_tables: [B, maxp];
    starts: [B] absolute position of each chunk's first token; chunk_lens:
    [B] valid tokens per chunk.  Padding tokens (j >= chunk_len) are routed
    to the null page 0, so a partially-filled chunk never corrupts pages
    beyond the sequence's allocation.
    """
    B, C = new.shape[:2]
    psize, maxp = pool.shape[2], block_tables.shape[1]
    pos = starts[:, None] + jnp.arange(C)[None, :]              # [B, C]
    pidx = jnp.clip(pos // psize, 0, maxp - 1)
    page = jnp.take_along_axis(block_tables, pidx, axis=1)
    valid = jnp.arange(C)[None, :] < chunk_lens[:, None]
    page = jnp.where(valid, page, NULL_PAGE)
    slot = pos % psize
    return pool.at[page.reshape(-1), :, slot.reshape(-1)].set(
        new.reshape((B * C,) + new.shape[2:]).astype(pool.dtype))


def paged_pool_append_quant(pool, scale, new, block_tables, starts,
                            chunk_lens):
    """int8 variant of ``paged_pool_append``: quantize-on-append.

    pool: [P, KH, psize, D] int8; scale: [P, KH] f32 (one symmetric scale
    per (page, kv-head), ``optim/compression.quantize_int8`` semantics);
    new: [B, C, KH, D] fresh K or V in compute dtype.

    Only the pages the chunk touches are rewritten: they are gathered,
    dequantized, the new tokens spliced in at f32, and the whole page
    re-quantized with a fresh per-(page, head) scale — so a page's scale
    always reflects its current contents (appending a large-magnitude token
    re-ranges the page's older tokens too, which is what keeps the
    roundtrip error bound per page instead of drifting).  Padding tokens
    and out-of-table positions fall onto the null page 0 exactly like the
    unquantized path.  Returns (pool, scale).
    """
    P, KH, psize, D = pool.shape
    B, C = new.shape[:2]
    maxp = block_tables.shape[1]
    # pages a row's chunk can touch: the page holding ``start`` plus every
    # page the C tokens can spill into
    T = (C + psize - 1) // psize + 1
    p0 = starts // psize                                        # [B]
    prel = p0[:, None] + jnp.arange(T)[None, :]                 # [B, T]
    pvalid = prel < maxp
    pages = jnp.take_along_axis(block_tables,
                                jnp.clip(prel, 0, maxp - 1), axis=1)
    pages = jnp.where(pvalid, pages, NULL_PAGE)                 # [B, T]
    got = pool[pages].astype(f32) * scale[pages][..., None, None]
    # splice the chunk tokens into the gathered pages at f32
    pos = starts[:, None] + jnp.arange(C)[None, :]              # [B, C]
    valid = jnp.arange(C)[None, :] < chunk_lens[:, None]
    t = pos // psize - p0[:, None]                              # [B, C]
    t = jnp.where(valid & (t >= 0) & (t < T), t, T)             # T -> dropped
    b_ix = jnp.broadcast_to(jnp.arange(B)[:, None], (B, C))
    got = got.at[b_ix.reshape(-1), t.reshape(-1), :,
                 (pos % psize).reshape(-1)].set(
        new.reshape(B * C, KH, D).astype(f32), mode="drop")
    q, nsc = quantize_int8(got, axis=(3, 4))                    # [B,T,KH,1,1]
    pool = pool.at[pages.reshape(-1)].set(q.reshape(-1, KH, psize, D))
    scale = scale.at[pages.reshape(-1)].set(nsc.reshape(-1, KH))
    # writes routed to the null page (padding / dead rows) may have raced;
    # its contents are never read as live data, but keep its scale sane
    return pool, scale
