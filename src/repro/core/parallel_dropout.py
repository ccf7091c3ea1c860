"""Collective & Parallel Dropout — Horn's core technique (paper §2).

Each worker *group* g draws an independent structured dropout over hidden
units ("a different disconnected sparse sub-model of the parent model") per
step; groups train in parallel on their data shards and updates are batch-
averaged.  On the TPU mesh, groups are slices of the (pod, data) batch axis, so
"different sub-model per group" is expressed as a mask tensor whose leading
axis is the group axis, broadcast against the group's samples.

Two faithfulness notes vs the 2016 paper:
  * The paper scales activations by the keep-rate at *eval* time; we use the
    mathematically equivalent inverted-dropout (scale 1/keep at train time).
    ``tests/test_parallel_dropout.py`` asserts the expectation equivalence.
  * The paper draws Bernoulli masks per neuron.  We draw per *block* of
    ``block_size`` contiguous neurons (default 128 = one TPU lane tile) so a
    dropped block is a skippable MXU tile (see kernels/dropout_matmul).
    ``block_size=1`` recovers the paper's exact per-neuron sub-models.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import HornConfig

f32 = jnp.float32


@dataclass(frozen=True)
class HornState:
    """Per-step dropout context threaded through a model apply."""

    key: jax.Array            # per-step base RNG
    cfg: HornConfig
    num_groups: int           # resolved group count (>=1)

    def layer_key(self, layer_idx) -> jax.Array:
        return jax.random.fold_in(self.key, layer_idx)


def make_horn_state(key, cfg: HornConfig, dp_size: int, step) -> Optional[HornState]:
    if not cfg.enabled:
        return None
    groups = cfg.num_groups or max(1, dp_size)
    key = jax.random.fold_in(jax.random.fold_in(key, cfg.seed_salt), step)
    return HornState(key=key, cfg=cfg, num_groups=groups)


def group_block_mask(key, num_groups: int, units: int, keep: float,
                     block_size: int) -> jax.Array:
    """[num_groups, n_blocks] mask with values in {0, 1/keep} (inverted dropout).

    Guarantees at least one live block per group (a fully-dropped layer would
    sever the sub-model — Horn's sub-models stay connected input->output).
    """
    nb = max(1, units // max(1, block_size))
    u = jax.random.uniform(key, (num_groups, nb))
    live = u < keep
    # force the argmax-u block alive if a group drew all-dead
    fallback = jax.nn.one_hot(jnp.argmax(u, axis=-1), nb, dtype=bool)
    live = jnp.where(live.any(axis=-1, keepdims=True), live, fallback)
    return live.astype(f32) / keep


def expand_units(mask_blocks, units: int) -> jax.Array:
    """[G, nb] block mask -> [G, units] unit mask; the last block covers the
    remainder tail.  THE block->unit rule — train-time masks (expand_mask)
    and the serving ModelBank both go through here, so a trained sub-model
    and its served circuit can never disagree on which units a block owns."""
    G, nb = mask_blocks.shape
    per = units // nb
    m = jnp.repeat(mask_blocks, per, axis=-1)            # [G, nb*per]
    if units % nb:
        m = jnp.concatenate([m, jnp.broadcast_to(m[:, -1:], (G, units % nb))], -1)
    return m


def expand_mask(mask_blocks, units: int, batch: int) -> jax.Array:
    """[G, nb] -> [batch, 1, units]: group->sample expansion + block->unit."""
    G = mask_blocks.shape[0]
    m = expand_units(mask_blocks, units)                 # [G, units]
    reps = max(1, batch // G)
    m = jnp.repeat(m, reps, axis=0)[:batch]              # [batch, units]
    return m[:, None, :]


def block_mask(state: Optional[HornState], layer_idx, units: int, *,
               keep: Optional[float] = None, salt: int = 0,
               block_size: Optional[int] = None):
    """A layer's [num_groups, n_blocks] group mask, or None in eval mode."""
    if state is None:
        return None
    keep = state.cfg.keep_hidden if keep is None else keep
    if keep >= 1.0:
        return None
    key = jax.random.fold_in(state.layer_key(layer_idx), salt)
    bs = state.cfg.block_size if block_size is None else block_size
    return group_block_mask(key, state.num_groups, units, keep, bs)


def unit_mask(state: Optional[HornState], layer_idx, batch: int, units: int,
              **kw):
    """The mask a layer multiplies its hidden units by, or None in eval mode."""
    mb = block_mask(state, layer_idx, units, **kw)
    return None if mb is None else expand_mask(mb, units, batch)


def input_mask(state: Optional[HornState], batch: int, units: int):
    """Input-layer mask (paper: keep 0.8), applied to embedding channels."""
    if state is None:
        return None
    return unit_mask(state, 100_003, batch, units, keep=state.cfg.keep_input,
                     salt=7)


def head_mask(state: Optional[HornState], layer_idx, batch: int, heads: int):
    """Optional whole-attention-head dropout ([B, 1, H, 1]) — beyond-paper."""
    if state is None or not state.cfg.mask_attention_heads:
        return None
    m = unit_mask(state, layer_idx, batch, heads, salt=13, block_size=1)
    if m is None:
        return None
    return m[..., None]    # [B, 1, H, 1]
