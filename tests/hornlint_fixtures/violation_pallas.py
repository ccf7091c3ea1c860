"""Seeded HL3xx violations — hornlint MUST exit nonzero on this file."""
import functools

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def _kernel(bt_ref, x_ref, o_ref, acc_ref, *, n_pages):
    b, p = pl.program_id(0), pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += x_ref[...]

    @pl.when(p == n_pages - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


def carry_declared_parallel(x, bt):
    B, H, P = 4, 8, 2
    grid = (B, H, P)
    return pl.pallas_call(
        functools.partial(_kernel, n_pages=P),
        grid=grid,
        in_specs=[
            # HL304: unclamped block-table gather in the index_map
            pl.BlockSpec((1, 1), lambda b, h, p, *refs: (refs[0][b, p], 0)),
            pl.BlockSpec((1, 1), lambda b, h, p: (b, h)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda b, h: (b, h)),  # HL303: arity 2
        out_shape=jax.ShapeDtypeStruct((B, H), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, 8), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # HL302: dim 2 carries the accumulator but is 'parallel'
            dimension_semantics=("parallel", "parallel", "parallel")),
    )(bt, x)


def semantics_rank_mismatch(x):
    grid = (4, 8, 2)
    return pl.pallas_call(
        functools.partial(_kernel, n_pages=2),
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1), lambda b, h, p: (b, h)),
                  pl.BlockSpec((1, 1), lambda b, h, p: (b, h))],
        out_specs=pl.BlockSpec((1, 1), lambda b, h, p: (b, h)),
        out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, 8), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # HL301: two entries for a rank-3 grid
            dimension_semantics=("parallel", "arbitrary")),
    )(x, x)
