"""Compliant twin of ``violation_pallas.py`` — hornlint MUST stay quiet.

The paged-attention kernel's shape: full-rank dimension_semantics with
the carry dim 'arbitrary', index maps at grid arity (scalar-prefetch
``*refs`` tails allowed), block-table gathers clamped to the null page.
"""
import functools

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

DIM_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _kernel(bt_ref, x_ref, o_ref, acc_ref, *, n_pages):
    b, p = pl.program_id(0), pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += x_ref[...]

    @pl.when(p == n_pages - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


def page_of(b, p, refs, maxp):
    bt = refs[0]
    live = p < maxp
    return jnp.where(live, bt[b, jnp.minimum(p, maxp - 1)], 0)


def accumulating_scan(x, bt):
    B, H, P = 4, 8, 2
    grid = (B, H, P)
    return pl.pallas_call(
        functools.partial(_kernel, n_pages=P),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1),
                         lambda b, h, p, *refs: (page_of(b, p, refs, 2), 0)),
            pl.BlockSpec((1, 1), lambda b, h, p: (b, h)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda b, h, p: (b, h)),
        out_shape=jax.ShapeDtypeStruct((B, H), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, 8), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=DIM_SEMANTICS),
    )(bt, x)
