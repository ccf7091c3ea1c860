"""Flash attention Pallas kernel (TPU): blocked online softmax in VMEM.

Grid: (B, H, Sq/bq, Skv/bk) — kv innermost (sequential, the only
``arbitrary`` dimension: the online-softmax carry lives across its steps;
batch/head/q-block are declared ``parallel`` so the Mosaic compiler may
split them across TPU megacore); the running
(max, sum, acc) live in VMEM scratch, so per-step HBM traffic is just the
Q/K/V tiles + final O tile instead of the [Sq, Skv] score matrix the ref path
streams through HBM (the dominant memory term of the dry-run baselines).

GQA is handled in the K/V index_map (q head h reads kv head h // G) — no
materialized repeat.  Causal + sliding-window masking is applied per-block
with iota; *fully* masked kv blocks are skipped via ``pl.when`` on block
indices, so local-attention layers do O(S * window) work, not O(S^2).
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


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: Optional[int],
            softcap: Optional[float], bq: int, bk: int, n_k: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk
    # block-level skip: causal => skip blocks entirely above the diagonal;
    # window => skip blocks entirely left of the window
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window is not None:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _block():
        q = q_ref[0, 0].astype(f32)               # [bq, D]
        k = k_ref[0, 0].astype(f32)               # [bk, D]
        v = v_ref[0, 0].astype(f32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.zeros((bq, bk), f32)
        if causal:
            mask = jnp.where(kpos > qpos, NEG_INF, mask)
        if window is not None:
            mask = jnp.where(kpos <= qpos - window, NEG_INF, mask)
        s = s + mask
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=f32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False):
    """q: [B, H, Sq, D]; k, v: [B, KH, Skv, D] -> [B, H, Sq, D]."""
    B, H, Sq, D = q.shape
    KH, Skv = k.shape[1], k.shape[2]
    G = H // KH
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    while Sq % bq:
        bq //= 2
    while Skv % bk:
        bk //= 2
    n_k = Skv // bk
    grid = (B, H, Sq // bq, n_k)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), f32),
                        pltpu.VMEM((bq, 1), f32),
                        pltpu.VMEM((bq, 1), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
