"""Mamba2 SSD chunk-scan Pallas kernel.

Grid: (B, H, S/Q) — the chunk dim innermost/sequential; the inter-chunk
[P, N] state lives in VMEM scratch across grid steps (TPU guarantees
sequential iteration of the trailing grid dim), so the recurrence never
round-trips HBM.  Inside a chunk the dual quadratic form runs on the MXU:
CB^T ([Q,Q]), its decay/dt weighting, and three [Q,*] matmuls.

This is the TPU adaptation of mamba2's Triton kernel: same chunking math,
but the state-carry uses the sequential-grid + VMEM-scratch idiom instead of
a persistent CUDA block, and tile sizes follow (8,128)/MXU alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
            Q: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0].astype(f32)               # [Q, P]
    dt = dt_ref[0, :, 0].astype(f32)             # [Q]
    A = a_ref[0]                                  # scalar (this head)
    Bm = b_ref[0].astype(f32)                     # [Q, N]
    Cm = c_ref[0].astype(f32)                     # [Q, N]

    dA = dt * A                                   # [Q], negative
    cum = jnp.cumsum(dA)                          # [Q]
    # intra-chunk dual form
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)       # [Q, Q]
    seg = cum[:, None] - cum[None, :]             # [Q, Q]
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    qj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    T = jnp.where(qi >= qj, CB * jnp.exp(seg) * dt[None, :], 0.0)
    y = jax.lax.dot_general(T, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)        # [Q, P]
    # carried-state contribution: C_i . state * exp(cum_i)
    state = state_ref[...]                        # [P, N]
    y_off = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)    # [Q, P]
    y = y + y_off * jnp.exp(cum)[:, None]
    y_ref[0, :, 0] = y.astype(y_ref.dtype)
    # state update: state * exp(cum[-1]) + x^T @ (w[:,None] * B)
    w = jnp.exp(cum[-1] - cum) * dt               # [Q]
    state_new = state * jnp.exp(cum[-1]) + jax.lax.dot_general(
        x, Bm * w[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=f32)               # [P, N]
    state_ref[...] = state_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(x, dt, A, Bm, Cm, *, chunk: int = 256,
                   interpret: bool = False):
    """x: [B,S,H,P]; dt: [B,S,H]; A: [H]; Bm, Cm: [B,S,N] -> y [B,S,H,P] f32."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    while S % Q:
        Q //= 2
    n_chunks = S // Q

    grid = (B, H, n_chunks)
    kernel = functools.partial(_kernel, Q=Q, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, 1, P), lambda b, h, ci: (b, ci, h, 0)),
            pl.BlockSpec((1, Q, 1), lambda b, h, ci: (b, ci, h)),
            pl.BlockSpec((1,), lambda b, h, ci: (h,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Q, N), lambda b, h, ci: (b, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, ci: (b, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, 1, P), lambda b, h, ci: (b, ci, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, P), f32),
        scratch_shapes=[pltpu.VMEM((P, N), f32)],
        # the recurrent state carried in VMEM scratch across chunk steps
        # makes the chunk axis sequential; (batch, head) split across
        # megacore like the attention kernels
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A.astype(f32), Bm, Cm)
