"""Block-sparse dropout matmul — the Horn hot-spot kernel.

The paper claims sub-model training "reduce[s] the size of model [and]
improve[s] the computing performance"; with naive masking the dropped units
still burn MXU cycles.  This kernel makes the claim real on TPU: the dropout
mask is drawn per 128-wide block of output units (core/submodel.py), the
whole [G, N/bn] mask sits in SMEM as one block (the TPU compiler refuses a
(1, 1) block of it) from which each step reads its (group, n-block) value,
and the whole K-loop of a dropped output tile is *skipped* (``pl.when``), so
FLOPs and VMEM traffic scale with the kept fraction (~keep_rate at steady
state).

Grid: (G, M/bm, N/bn, K/bk), K innermost (sequential accumulation in a VMEM
scratch accumulator, fp32; the only ``arbitrary`` dimension — G/M/N tiles
are independent and declared ``parallel`` for TPU megacore partitioning).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32


def _kernel(mask_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    g, ni, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    mval = mask_ref[g, ni]                      # this (g, n-block)'s mask value

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mval != 0.0)                       # skip dropped blocks entirely
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[0], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=f32)

    @pl.when(ki == n_k - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] * mval).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def dropout_matmul(x, w, mask_blocks, *, block_m: int = 128,
                   block_n: int = 128, block_k: int = 128,
                   interpret: bool = False):
    """x: [G, M, K]; w: [K, N]; mask_blocks: [G, N/block_n] -> [G, M, N] f32.

    ``block_n`` must equal the mask's neuron-block size (Horn block_size).
    """
    G, M, K = x.shape
    N = w.shape[1]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert mask_blocks.shape == (G, N // bn), mask_blocks.shape
    n_k = K // bk

    grid = (G, M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((G, N // bn), lambda g, mi, ni, ki: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bm, bk), lambda g, mi, ni, ki: (g, mi, ki)),
            pl.BlockSpec((bk, bn), lambda g, mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, mi, ni, ki: (g, mi, ni)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), f32),
        scratch_shapes=[pltpu.VMEM((bm, bn), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(mask_blocks.astype(f32), x, w)
