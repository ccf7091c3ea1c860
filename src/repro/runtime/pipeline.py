"""Pipeline parallelism (GPipe schedule) via shard_map + ppermute.

The ``pod`` axis of the production mesh can serve as the pipeline-stage axis:
each stage owns a contiguous slice of layers (stacked params sharded on the
layer dim), microbatches flow stage->stage through collective-permutes.

Forward is an explicit tick loop (T = M + S - 1); because ppermute is
differentiable (its transpose is the reverse permute), ``jax.grad`` through
:func:`pipelined_apply` yields the reverse-schedule backward automatically —
no hand-written 1F1B needed for correctness.  ``tests/test_pipeline.py``
checks forward and grad equality vs the unpipelined reference on a 4-stage
CPU mesh.

Bubble fraction is (S-1)/(M+S-1); callers pick M >= 4*S to keep it under 20%.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipelined_apply(block_fn: Callable, stage_params, x_mb, *,
                    mesh: Mesh, stage_axis: str = "stage"):
    """Run ``block_fn`` over pipeline stages.

    block_fn(stage_params_slice, x) -> x   (applies ONE stage's layers)
    stage_params: pytree with leading dim = num_stages (sharded over stages)
    x_mb: [M, mb, ...] microbatches (replicated input)
    Returns [M, mb, ...] outputs (replicated — result of the last stage).
    """
    S = mesh.shape[stage_axis]
    M = x_mb.shape[0]
    T = M + S - 1
    fwd_perm = [(i, i + 1) for i in range(S - 1)]

    def stage_prog(params_slice, x_local):
        # params_slice: [1, ...] this stage's layer stack; squeeze stage dim
        params_here = jax.tree.map(lambda p: p[0], params_slice)
        idx = jax.lax.axis_index(stage_axis)

        def tick(carry, t):
            act, outs = carry
            mb_id = t - idx
            inject = x_local[jnp.clip(t, 0, M - 1)]
            act_in = jnp.where(idx == 0, inject, act)
            out = block_fn(params_here, act_in)
            valid = (mb_id >= 0) & (mb_id < M)
            out = jnp.where(valid, out, jnp.zeros_like(out))
            # last stage records its finished microbatch
            rec_id = jnp.clip(mb_id, 0, M - 1)
            record = (idx == S - 1) & valid
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(record, out, outs[rec_id]), rec_id, 0)
            nxt = jax.lax.ppermute(out, stage_axis, fwd_perm) if fwd_perm else out
            return (nxt, outs), None

        act0 = jnp.zeros_like(x_local[0])
        outs0 = jnp.zeros_like(x_local)
        (act, outs), _ = jax.lax.scan(tick, (act0, outs0), jnp.arange(T))
        # broadcast result from the last stage to all (so output is replicated)
        outs = jax.lax.psum(
            jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)), stage_axis)
        return outs

    pspec = jax.tree.map(lambda _: P(stage_axis), stage_params)
    fn = jax.shard_map(stage_prog, mesh=mesh,
                       in_specs=(pspec, P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x_mb)


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
