"""Mamba2 SSD chunk-scan Pallas kernel, with the state carried in and out.

``ssd_chunk_scan(x, dt, A, Bm, Cm, initial_state)`` continues each
sequence from its stored [H, P, N] state and returns the state after its
last position, written in place over ``initial_state``
(``input_output_aliases``): the serving engine hands it the per-slot state
pool of all its Mamba layers, [L, B, H, P, N], with the index of the layer
to advance (a scalar-prefetch operand the state's index map reads), and
gets the same buffer back with that layer advanced -- no slice of the
pool is copied out or back.

Grid: (B, H / heads_per_step, S / Q) -- the chunk axis innermost and
sequential.  The state block of a (sequence, head group) is the output
block of every chunk step, so it stays in VMEM across the chunks and
reaches HBM once, when the group is done.  A grid step holds several
heads: one head's state is 32 KiB at P = 64, N = 128, and a step per head
would leave a decode tick (one position per sequence) bound by the cost
of a grid step rather than by the state's bytes.  ``heads_per_step``
derives the group from the shapes (``STATE_BLOCK_BYTES`` of state,
``STEP_ROWS`` chunk rows).

Two bodies share the wrapper:

  S == 1   the decode update, on the vector unit: state * exp(dt A) +
           dt x B^T, then y = state C, for all heads of the group at once
           (x and y as [heads, P] rows, a free reshape at S == 1);
  S > 1    the chunked dual form per head on the MXU: the [Q, Q] decay-
           weighted C B^T against x, plus the carried state's contribution
           C state^T exp(cum), then the state update.

Positions a caller wants to leave out (padding past a sequence's length)
take dt = 0: exp(0 A) = 1 and the update dt x B^T is 0, so the state
passes through them unchanged and no earlier position's output moves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
HI = jax.lax.Precision.HIGHEST

STATE_BLOCK_BYTES = 1024 * 1024  # f32 state of one grid step's heads
STEP_ROWS = 2048                 # heads x chunk rows of one grid step


def heads_per_step(H: int, P: int, N: int, Q: int) -> int:
    """The widest head group of a grid step: a divisor of H that is H
    itself or a multiple of 8 (the block's tiling), whose f32 state fits
    ``STATE_BLOCK_BYTES`` and whose heads x chunk rows fit ``STEP_ROWS``;
    the narrowest legal group where none fits."""
    legal = [g for g in range(1, H + 1) if H % g == 0
             and (g == H or g % 8 == 0)]
    fits = [g for g in legal if g * P * N * 4 <= STATE_BLOCK_BYTES
            and g * Q <= STEP_ROWS]
    return max(fits) if fits else min(legal)


def _decode_kernel(layer_ref, keep_ref, a_ref, x_ref, dt_ref, b_ref, c_ref,
                   s_in_ref, y_ref, s_ref):
    """One position, every head of the group: x and y [hb, P] (P on the
    lanes, as the arrays lie in HBM), dt and A [hb, 1], B and C [1, N],
    state [1, 1, hb, P, N]."""
    x = x_ref[0].astype(f32)[:, :, None]          # [hb, P, 1]
    dt = dt_ref[0][:, :, None]                    # [hb, 1, 1]
    Bm = b_ref[0].astype(f32)[None]               # [1, 1, N]
    Cm = c_ref[0].astype(f32)[None]
    keep = keep_ref[pl.program_id(0)]
    decay = keep * jnp.exp(dt * a_ref[...][:, :, None])
    state = s_in_ref[0, 0] * decay + (dt * x) * Bm
    s_ref[0, 0] = state
    y_ref[0] = jnp.sum(state * Cm, axis=-1).astype(y_ref.dtype)


def _chunk_kernel(layer_ref, keep_ref, a_ref, x_ref, dt_ref, b_ref, c_ref,
                  s_in_ref, y_ref, s_ref, *, hb: int, Q: int):
    """One chunk of Q positions for hb heads: x [hb, Q, P], dt [hb, Q],
    B and C [Q, N], state [1, 1, hb, P, N] (resident across the chunk
    axis)."""
    keep = keep_ref[pl.program_id(0)]

    @pl.when(pl.program_id(2) == 0)
    def _load():
        s_ref[...] = s_in_ref[...] * keep

    Bm = b_ref[0].astype(f32)                      # [Q, N]
    Cm = c_ref[0].astype(f32)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())), precision=HI,
                             preferred_element_type=f32)          # [Q, Q]
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    qj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = qi >= qj
    lower = causal.astype(f32)                    # [i, j] = 1 for j <= i
    eye = (qi == qj).astype(f32)
    P = x_ref.shape[-1]

    def rowsum(m, row):
        """m [R, Q] times the row [1, Q], summed along Q: [R, 1]."""
        return jax.lax.dot_general(m, row, (((1,), (1,)), ((), ())),
                                   precision=HI, preferred_element_type=f32)

    g0 = pl.program_id(1) * hb
    for h in range(hb):
        x = x_ref[0, h].astype(f32)               # [Q, P]
        dt = dt_ref[0, h:h + 1, :]                # [1, Q]
        dA = dt * a_ref[g0 + h]
        # running sums of dA along the chunk, as a column and as a row
        cum_c = rowsum(lower, dA)                 # [Q, 1]
        cum_r = jax.lax.dot_general(dA, lower, (((1,), (1,)), ((), ())),
                                    precision=HI,
                                    preferred_element_type=f32)  # [1, Q]
        seg = jnp.where(causal, cum_c - cum_r, -jnp.inf)
        T = CB * jnp.exp(seg) * dt                # [Q, Q]
        y = jax.lax.dot_general(T, x, (((1,), (0,)), ((), ())), precision=HI,
                                preferred_element_type=f32)      # [Q, P]
        state = s_ref[0, 0, h]                    # [P, N]
        y_off = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                    precision=HI,
                                    preferred_element_type=f32)  # [Q, P]
        y_ref[0, h] = (y + y_off * jnp.exp(cum_c)).astype(y_ref.dtype)
        # the chunk's whole sum, as columns of Q and of P rows (a [1, 1]
        # value does not broadcast over both axes of a vreg)
        tot_q = rowsum(jnp.ones((Q, Q), f32), dA)            # [Q, 1]
        tot_p = rowsum(jnp.ones((P, Q), f32), dA)            # [P, 1]
        w = jnp.exp(tot_q - cum_c) * rowsum(eye, dt)         # [Q, 1]
        s_ref[0, 0, h] = state * jnp.exp(tot_p) + jax.lax.dot_general(
            x, Bm * w, (((0,), (0,)), ((), ())), precision=HI,
            preferred_element_type=f32)           # [P, N]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(x, dt, A, Bm, Cm, initial_state=None, keep=None,
                   layer=None, *, chunk: int = 256, interpret: bool = False):
    """x: [B,S,H,P]; dt: [B,S,H]; A: [H]; Bm, Cm: [B,S,N]; initial_state
    [B,H,P,N] f32 (None: zeros), or a stack [L,B,H,P,N] of which row
    ``layer`` ([1] int32) is read and written and the rest left alone; keep [B]
    f32 (None: ones), 1 where sequence b continues from its state and 0
    where it starts from zeros (applied as the state is read -- no
    separate pass over the pool) -> (y [B,S,H,P] in x's dtype, the state
    f32 in ``initial_state``'s shape, written over it)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    while S % Q:
        Q //= 2
    n_chunks = S // Q
    hb = heads_per_step(H, P, N, Q)
    if initial_state is None:
        initial_state = jnp.zeros((B, H, P, N), f32)
    if keep is None:
        keep = jnp.ones((B,), f32)
    stacked = initial_state.ndim == 5
    state = initial_state if stacked \
        else initial_state.reshape((1,) + initial_state.shape)
    lay = jnp.zeros((1,), jnp.int32) if layer is None else layer
    keep_spec = pl.BlockSpec((B,), lambda b, h, c, l: (0,),
                             memory_space=pltpu.SMEM)
    state_spec = pl.BlockSpec((1, 1, hb, P, N),
                              lambda b, h, c, l: (l[0], b, h, 0, 0))
    bc_spec = pl.BlockSpec((1, Q, N), lambda b, h, c, l: (b, c, 0))
    if S == 1:
        kernel = _decode_kernel
        args = (A.astype(f32).reshape(H, 1), x.reshape(B, H, P),
                dt.astype(f32).reshape(B, H, 1))
        a_spec = pl.BlockSpec((hb, 1), lambda b, h, c, l: (h, 0))
        x_spec = pl.BlockSpec((1, hb, P), lambda b, h, c, l: (b, h, 0))
        dt_spec = pl.BlockSpec((1, hb, 1), lambda b, h, c, l: (b, h, 0))
        y_shape = (B, H, P)
    else:
        kernel = functools.partial(_chunk_kernel, hb=hb, Q=Q)
        args = (A.astype(f32), x.transpose(0, 2, 1, 3),
                dt.astype(f32).transpose(0, 2, 1))
        a_spec = pl.BlockSpec((H,), lambda b, h, c, l: (0,),
                              memory_space=pltpu.SMEM)
        x_spec = pl.BlockSpec((1, hb, Q, P), lambda b, h, c, l: (b, h, c, 0))
        dt_spec = pl.BlockSpec((1, hb, Q), lambda b, h, c, l: (b, h, c))
        y_shape = (B, H, S, P)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H // hb, n_chunks),
        in_specs=[keep_spec, a_spec, x_spec, dt_spec, bc_spec, bc_spec,
                  state_spec],
        out_specs=[x_spec, state_spec],
    )
    y, state = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(y_shape, x.dtype),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operand 7 (after the layer index and six inputs) is the state
        input_output_aliases={7: 1},
        # the state is carried across the chunk axis, which stays
        # sequential; sequences and head groups split across cores
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lay, keep, *args, Bm, Cm, state.astype(f32))
    if not stacked:
        state = state.reshape(state.shape[1:])
    if S == 1:
        return y.reshape(B, 1, H, P), state
    return y.transpose(0, 2, 1, 3), state
