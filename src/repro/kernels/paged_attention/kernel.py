"""Paged-attention Pallas kernels (TPU): block-table K/V gather in VMEM.

Two kernels share one structure:

  paged_attention        one query token per sequence (decode)
  paged_chunk_attention  a C-token chunk per sequence — the unified serving
                         step's workhorse: decode slots ride as C == 1
                         chunks, admitting prompts as wider chunks, each
                         token attending to prior pages plus the causal
                         prefix of its own chunk (already appended to the
                         pool).  C == 1 reproduces paged_attention
                         bit-for-bit.

Per-sequence KV is addressed through a block table (the vLLM technique: KV
lives in a shared pool of fixed-size pages, so sequences of wildly different
lengths pack the HBM densely and join/leave a batch without reshuffling).

Pools are laid out [P, KH, psize, D], kv-heads ahead of the page's token
axis, so one page of ``hps`` consecutive kv heads is one contiguous
(1, hps, psize, D) block whose last two dims equal the pool's: the TPU
compiler accepts any page size and head dim, and one DMA fetches it.

Grid: (B, KH / heads_per_step, ceil(maxp / pages_per_step)) — pages
innermost (sequential).  ``dimension_semantics`` marks the (slot, head
group) dimensions ``parallel`` so TPU megacore splits the work across
cores; only the page axis stays ``arbitrary`` (it carries the
online-softmax (max, sum, acc) state in VMEM scratch).  The block table and
the per-sequence lengths ride in as *scalar-prefetch* operands
(``pltpu.PrefetchScalarGridSpec``) so the K/V ``index_map`` can resolve
``block_tables[b, page]`` before the DMA is issued: the gather costs zero
extra HBM traffic versus a contiguous cache.

A grid step carries ``pps`` whole pages of ``hps`` kv heads: one
independently-indexed K and V block per page offset (gathered pages are not
contiguous in the pool, hence one BlockSpec *per page offset* rather than
one wider block), so Pallas double-buffers the next step's page DMAs
against the current step's compute.  Inside the step the online-softmax
update runs page by page in page order and, within a page, head by head:
every (page, head) update is the op sequence of the one-page, one-head
kernel, so every tiling gives the same bits.  What a tiling changes is the
number of grid steps and of DMAs and the bytes each DMA carries; on the
chip one small DMA per page per kv head cost far more than the grid steps.
``chunk_tiling`` derives the tiling from the call's shapes
(``pages_per_step=None`` / ``heads_per_step=None``); explicit values are
honoured.  The decode-only ``paged_attention`` keeps one head per step.

Dead grid steps (pages past ``ceil(len / psize)``) are clamped to the null
page 0 *in the index map* — stale or garbage block-table entries past a
sequence's length never reach the DMA engine (previously they triggered
real gathers of arbitrary pool pages, masked only at compute time), and the
compute is skipped via ``pl.when``.

int8 paged KV: pass ``k_scale``/``v_scale`` ([P, KH] f32, one symmetric
scale per (page, kv-head) — see ``optim/compression.quantize_int8`` with
``axis=(2, 3)``) and int8 pools; each gathered page is dequantized
in-register right after the DMA, so the HBM traffic per page is ~half of
bf16 and ~quarter of f32.

Fused verify windows: ``paged_chunk_attention(..., logit_index=[B, S])``
additionally emits the S selected chunk rows per slot as a second,
window-compacted output — gathered in the kernel epilogue while the chunk
output is still in VMEM, so speculative verify stops paying a separate
device-wide gather pass over the full-width output.

GQA: each (page, kv head) update processes the whole [G, D] group of query
heads that share the kv head — no materialized K/V repeat.
Sequences with ``length == 0`` (empty decode slots) emit zeros.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
NEG_INF = -1e30

# The pool reserves page 0 as the null page: dead grid steps, vacated
# block-table slots, and masked appends all route there.  The serving
# layer (block_table/kv_cache/ops) shares this constant — hornshape
# checks the index-map clamp against it symbolically.
NULL_PAGE = 0

# int8 scales [P, KH] are read in row groups of this many pages: the
# smallest f32 block whose last two dims the TPU compiler accepts
SCALE_ROWS = 8

# (slot, kv-head) are embarrassingly parallel — megacore may split them;
# the page axis is sequential (online-softmax carry in VMEM scratch)
DIM_SEMANTICS = ("parallel", "parallel", "arbitrary")

# chunk_tiling's limits, from sweeps on one v5e at the serving cell's
# geometry (PERF.md).  What costs there is one DMA per page per kv head:
# all kv heads in a step, one (1, KH, psize, D) block per page, ran the
# C = 1 call 5.9x faster; pages per step moved it by 2-6%.
# STEP_ROWS: query rows (heads x C*G) a step holds; their f32
#   accumulators, lane-padded max / sum scratch and double-buffered q / out
#   blocks live in VMEM (4096: C = 256 over all 8 heads, about 10 MiB).
# STEP_UPDATES: (page, head) updates a step unrolls.  The kernel is traced
#   once per chunk bucket: 64 of them (8 pages of 8 heads) made the serving
#   cell's set-up 21-23 s longer, most of it tracing; a head loop rolled
#   into a fori_loop kept the set-up but ran the kernel 3x slower.
# STEP_ROW_PAGES: rows x pages, which bounds the unrolled work (C = 256
#   over all heads ran as fast at 1 to 8 pages a step and at half speed
#   at 16).
STEP_ROWS = 4096
STEP_UPDATES = 16
STEP_ROW_PAGES = 8192


def chunk_tiling(C: int, G: int, KH: int, maxp: int):
    """(pages_per_step, heads_per_step) of ``paged_chunk_attention`` for a
    C-token chunk of G query heads per kv head over a pool of KH kv heads
    and tables maxp pages wide.  All kv heads share a step while their
    C * G query rows stay within ``STEP_ROWS`` (else the widest divisor of
    KH that does); the step then takes as many pages as keep its updates
    within ``STEP_UPDATES`` and its rows x pages within
    ``STEP_ROW_PAGES``.  A function of shapes alone: every value gives the
    same bits."""
    hps = KH
    while hps > 1 and (hps * C * G > STEP_ROWS or KH % hps):
        hps -= 1
    pps = min(maxp, STEP_UPDATES // hps, STEP_ROW_PAGES // (hps * C * G))
    return max(1, pps), hps


def _kv_page_specs(*, pps: int, psize: int, maxp: int, D: int, KH: int,
                   length_of, quantized: bool, hps: int = 1):
    """One (1, hps, psize, D) K/V BlockSpec per page offset j of a grid step
    over the [P, KH, psize, D] pool (head group h: kv heads h*hps ..
    h*hps + hps - 1), plus one (SCALE_ROWS, KH) scale spec per offset in
    int8 mode (the row group of the [P, KH] scale array that holds the
    page; ``_page_scale`` picks the (page, head) entry in-register).
    Dead pages (past the sequence's live length) are clamped to the null page
    0 in the index map itself, so garbage block-table entries are never
    dereferenced and no DMA bandwidth is spent on them."""
    def page_of(b, p, j, refs):
        bt = refs[0]
        pg = p * pps + j
        live = pg * psize < length_of(b, refs)
        return jnp.where(live, bt[b, jnp.minimum(pg, maxp - 1)], NULL_PAGE)

    def kv_map(j):
        return lambda b, h, p, *refs: (page_of(b, p, j, refs), h, 0, 0)

    def sc_map(j):
        return lambda b, h, p, *refs: (page_of(b, p, j, refs) // SCALE_ROWS, 0)

    kv = [pl.BlockSpec((1, hps, psize, D), kv_map(j)) for j in range(pps)]
    sc = [pl.BlockSpec((SCALE_ROWS, KH), sc_map(j)) for j in range(pps)] \
        if quantized else []
    return kv, sc


def _page_scale(sc_ref, page, h):
    """The f32 scale of (page, kv-head h) out of its (SCALE_ROWS, KH) row
    group, as a [1, 1] value: a masked sum, since Mosaic has no dynamic
    lane index."""
    blk = sc_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    hit = (row == page % SCALE_ROWS) & (col == h)
    return jnp.sum(jnp.where(hit, blk, 0.0), keepdims=True)


def _split_kv_refs(rest, *, pps: int, quantized: bool):
    """Kernel ref layout: k_0..k_{pps-1}, v_0.., [ksc_0.., vsc_0..], rest."""
    k_refs, v_refs = rest[:pps], rest[pps:2 * pps]
    base = 2 * pps
    ks_refs = vs_refs = None
    if quantized:
        ks_refs, vs_refs = rest[base:base + pps], rest[base + pps:base + 2 * pps]
        base += 2 * pps
    return k_refs, v_refs, ks_refs, vs_refs, rest[base:]


def _kernel(bt_ref, len_ref, q_ref, *rest, scale: float,
            window: Optional[int], softcap: Optional[float], psize: int,
            grid_p: int, pps: int, quantized: bool):
    k_refs, v_refs, ks_refs, vs_refs, tail = _split_kv_refs(
        rest, pps=pps, quantized=quantized)
    o_ref, acc_ref, m_ref, l_ref = tail
    b, h, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    for j in range(pps):
        pg = p * pps + j
        live = pg * psize < length

        @pl.when(live)
        def _page(j=j, pg=pg):
            q = q_ref[0, 0].astype(f32)                 # [G, D]
            k = k_refs[j][0, 0].astype(f32)             # [psize, D]
            v = v_refs[j][0, 0].astype(f32)
            if quantized:                               # in-register dequant
                page = bt_ref[b, pg]
                k = k * _page_scale(ks_refs[j], page, h)
                v = v * _page_scale(vs_refs[j], page, h)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32) * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            kpos = pg * psize + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)                  # [G, psize]
            mask = jnp.where(kpos >= length, NEG_INF, 0.0)
            if window is not None:
                mask = jnp.where(kpos <= length - 1 - window, NEG_INF, mask)
            s = s + mask
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            prob = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + jnp.sum(prob, -1, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
                prob, v, (((1,), (0,)), ((), ())), preferred_element_type=f32)
            m_ref[...] = m_new

    @pl.when(p == grid_p - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _chunk_kernel(bt_ref, start_ref, clen_ref, *rest, scale: float,
                  window: Optional[int], softcap: Optional[float],
                  psize: int, grid_p: int, pps: int, hps: int, C: int,
                  G: int, quantized: bool, S_w: int):
    """Chunk-append variant: q is [hps, C * G, D] per (sequence, head
    group) — per kv head, C chunk tokens x G grouped query heads.  Row r
    holds chunk token r // G at absolute position ``start + r // G``; the
    mask adds a causal constraint against the token's own chunk prefix on
    top of the decode kernel's length mask.  Padding rows (token index >=
    chunk_len) are zeroed at emit.  Each (page, head) update is the op
    sequence of the one-page, one-head step, so every (pps, hps) gives the
    same bits, and with C == 1 every op matches ``_kernel`` bit-for-bit.

    ``S_w > 0``: a ``logit_index`` [B, S_w] scalar-prefetch operand follows
    the block table, and the epilogue additionally writes the S_w selected
    chunk rows into a window-compacted second output (the fused speculative
    verify window — no separate full-width gather pass)."""
    if S_w:
        widx_ref, rest = rest[0], rest[1:]
    q_ref, rest = rest[0], rest[1:]
    k_refs, v_refs, ks_refs, vs_refs, tail = _split_kv_refs(
        rest, pps=pps, quantized=quantized)
    if S_w:
        o_ref, ow_ref, acc_ref, m_ref, l_ref = tail
    else:
        o_ref, acc_ref, m_ref, l_ref = tail
    b, hg, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    start = start_ref[b]
    clen = clen_ref[b]
    length = start + clen
    for j in range(pps):
        pg = p * pps + j
        live = pg * psize < length

        @pl.when(live)
        def _page(j=j, pg=pg):
            shape = (C * G, psize)
            kpos = pg * psize + jax.lax.broadcasted_iota(
                jnp.int32, shape, 1)                    # [C*G, psize]
            qpos = start + jax.lax.broadcasted_iota(
                jnp.int32, shape, 0) // G               # row r -> token r // G
            mask = jnp.where(kpos >= length, NEG_INF, 0.0)
            mask = jnp.where(kpos > qpos, NEG_INF, mask)   # causal own-chunk
            if window is not None:
                mask = jnp.where(kpos <= qpos - window, NEG_INF, mask)
            for i in range(hps):
                q = q_ref[0, i].astype(f32)             # [C * G, D]
                k = k_refs[j][0, i].astype(f32)         # [psize, D]
                v = v_refs[j][0, i].astype(f32)
                if quantized:                           # in-register dequant
                    page = bt_ref[b, pg]
                    k = k * _page_scale(ks_refs[j], page, hg * hps + i)
                    v = v * _page_scale(vs_refs[j], page, hg * hps + i)
                s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=f32) * scale
                if softcap:
                    s = jnp.tanh(s / softcap) * softcap
                s = s + mask
                m_prev = m_ref[i]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                prob = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_ref[i] = l_ref[i] * corr + jnp.sum(prob, -1, keepdims=True)
                acc_ref[i] = acc_ref[i] * corr + jax.lax.dot_general(
                    prob, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)
                m_ref[i] = m_new

    @pl.when(p == grid_p - 1)
    def _emit():
        for i in range(hps):
            out = acc_ref[i] / jnp.maximum(l_ref[i], 1e-30)
            tok = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0) // G
            final = jnp.where(tok < clen, out, 0.0).astype(o_ref.dtype)
            o_ref[0, i] = final
            if S_w:
                # fused verify window: gather the S_w selected rows while
                # the chunk output sits in VMEM (row tok t -> q-head group
                # t*G:+G)
                for sw in range(S_w):
                    t = widx_ref[b, sw]
                    ow_ref[0, i, sw * G:(sw + 1) * G, :] = \
                        jax.lax.dynamic_slice_in_dim(final, t * G, G, axis=0)


def _check_quant(k_pages, k_scale, v_scale):
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    if k_scale is not None and k_pages.dtype != jnp.int8:
        raise ValueError(
            f"scales given but pages are {k_pages.dtype}, expected int8")
    return k_scale is not None


@functools.partial(jax.jit, static_argnames=(
    "scale", "window", "softcap", "interpret", "pages_per_step",
    "heads_per_step"))
def paged_chunk_attention(q, k_pages, v_pages, block_tables, starts,
                          chunk_lens, *, scale: float,
                          window: Optional[int] = None,
                          softcap: Optional[float] = None,
                          interpret: bool = False,
                          pages_per_step: Optional[int] = None,
                          heads_per_step: Optional[int] = None,
                          k_scale=None, v_scale=None, logit_index=None):
    """q: [B, C, H, D] right-padded chunks; k/v_pages: [P, KH, psize, D]
    (the chunk's own K/V already appended); block_tables: [B, maxp];
    starts/chunk_lens: [B] -> [B, C, H, D].  See paged_chunk_attention_ref
    for the contract; C == 1 reproduces ``paged_attention`` bit-for-bit.

    ``pages_per_step`` pages of ``heads_per_step`` kv heads make one grid
    step; ``None`` takes ``chunk_tiling``'s value.  Every tiling gives the
    same bits.
    ``k_scale``/``v_scale`` ([P, KH] f32) enable the int8-pool mode.
    ``logit_index`` ([B, S] int32 chunk positions, each < chunk_len or 0)
    switches the return to ``(out [B, C, H, D], out_win [B, S, H, D])``
    with the window rows gathered in the kernel epilogue."""
    B, C, H, D = q.shape
    KH, psize = k_pages.shape[1], k_pages.shape[2]
    maxp = block_tables.shape[1]
    G = H // KH
    quantized = _check_quant(k_pages, k_scale, v_scale)
    pps, hps = chunk_tiling(C, G, KH, maxp)
    if pages_per_step is not None:
        pps = max(1, min(pages_per_step, maxp))
    if heads_per_step is not None:
        hps = heads_per_step
    if hps < 1 or KH % hps:
        raise ValueError(f"heads_per_step={hps} must divide the {KH} kv "
                         f"heads")
    grid_p = -(-maxp // pps)
    S_w = 0 if logit_index is None else logit_index.shape[1]
    # [B, KH, C*G, D]: chunk tokens x grouped query heads, flattened so the
    # kernel works on one 2-D block per (seq, kv head) like the decode kernel
    qg = q.reshape(B, C, KH, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, KH, C * G, D)

    kernel = functools.partial(
        _chunk_kernel, scale=scale, window=window, softcap=softcap,
        psize=psize, grid_p=grid_p, pps=pps, hps=hps, C=C, G=G,
        quantized=quantized, S_w=S_w)
    kv_specs, sc_specs = _kv_page_specs(
        pps=pps, psize=psize, maxp=maxp, D=D, KH=KH,
        length_of=lambda b, refs: refs[1][b] + refs[2][b], quantized=quantized,
        hps=hps)
    q_spec = pl.BlockSpec((1, hps, C * G, D),
                          lambda b, h, p, *refs: (b, h, 0, 0))
    out_spec = pl.BlockSpec((1, hps, C * G, D),
                            lambda b, h, p, *refs: (b, h, 0, 0))
    out_shape = jax.ShapeDtypeStruct((B, KH, C * G, D), q.dtype)
    out_specs, out_shapes = out_spec, out_shape
    if S_w:
        out_specs = [out_spec,
                     pl.BlockSpec((1, hps, S_w * G, D),
                                  lambda b, h, p, *refs: (b, h, 0, 0))]
        out_shapes = [out_shape,
                      jax.ShapeDtypeStruct((B, KH, S_w * G, D), q.dtype)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + (1 if S_w else 0),
        grid=(B, KH // hps, grid_p),
        in_specs=[q_spec] + kv_specs + kv_specs + sc_specs + sc_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((hps, C * G, D), f32),
                        pltpu.VMEM((hps, C * G, 1), f32),
                        pltpu.VMEM((hps, C * G, 1), f32)],
    )
    scalars = [block_tables.astype(jnp.int32), starts.astype(jnp.int32),
               chunk_lens.astype(jnp.int32)]
    if S_w:
        scalars.append(logit_index.astype(jnp.int32))
    args = scalars + [qg] + [k_pages] * pps + [v_pages] * pps
    if quantized:
        args += [k_scale] * pps + [v_scale] * pps
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=DIM_SEMANTICS),
        interpret=interpret,
    )(*args)

    def unflatten(o, n):
        return o.reshape(B, KH, n, G, D).transpose(0, 2, 1, 3, 4).reshape(
            B, n, H, D)

    if S_w:
        return unflatten(out[0], C), unflatten(out[1], S_w)
    return unflatten(out, C)


@functools.partial(jax.jit, static_argnames=(
    "scale", "window", "softcap", "interpret", "pages_per_step"))
def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale: float, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    interpret: bool = False, pages_per_step: int = 1,
                    k_scale=None, v_scale=None):
    """q: [B, H, D]; k/v_pages: [P, KH, psize, D]; block_tables: [B, maxp];
    lengths: [B] -> [B, H, D].  ``pages_per_step``/``k_scale``/``v_scale``
    as in ``paged_chunk_attention``."""
    B, H, D = q.shape
    KH, psize = k_pages.shape[1], k_pages.shape[2]
    maxp = block_tables.shape[1]
    G = H // KH
    quantized = _check_quant(k_pages, k_scale, v_scale)
    pps = max(1, min(pages_per_step, maxp))
    grid_p = -(-maxp // pps)
    qg = q.reshape(B, KH, G, D)

    kernel = functools.partial(
        _kernel, scale=scale, window=window, softcap=softcap,
        psize=psize, grid_p=grid_p, pps=pps, quantized=quantized)
    kv_specs, sc_specs = _kv_page_specs(
        pps=pps, psize=psize, maxp=maxp, D=D, KH=KH,
        length_of=lambda b, refs: refs[1][b], quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KH, grid_p),
        in_specs=[pl.BlockSpec((1, 1, G, D),
                               lambda b, h, p, *refs: (b, h, 0, 0))]
        + kv_specs + kv_specs + sc_specs + sc_specs,
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, p, *refs: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((G, D), f32),
                        pltpu.VMEM((G, 1), f32),
                        pltpu.VMEM((G, 1), f32)],
    )
    args = [block_tables.astype(jnp.int32), lengths.astype(jnp.int32), qg] \
        + [k_pages] * pps + [v_pages] * pps
    if quantized:
        args += [k_scale] * pps + [v_scale] * pps
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=DIM_SEMANTICS),
        interpret=interpret,
    )(*args)
    return out.reshape(B, H, D)
