"""The main-path Pallas kernels compile for a TPU v5e at qwen3-1.7b's
widths, and at granite-4.0-h-micro's (the state-carrying SSD scan, paged
attention at head_dim 64).

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
described (not attached) ``v5e:2x2`` topology, which refuses what interpret
mode accepts — block shapes whose last two dims break the (8, 128) tiling
rule, too much VMEM or SMEM.  All such compiles live in this one file: the
topology is described inside a module-scoped fixture, so only the pytest
worker that runs this file loads the TPU compiler.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dropout_matmul.kernel import dropout_matmul
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.paged_attention.kernel import paged_chunk_attention
from repro.kernels.ssd.kernel import ssd_chunk_scan

# qwen3-1.7b: 16 query / 8 kv heads of 128, d_model 2048, d_ff 6144;
# the engine's default pool of 256 pages x 16 tokens, 8 slots
B, H, KH, D, PSIZE, PAGES, MAXP = 8, 16, 8, 128, 16, 256, 33
D_MODEL, D_FF, HORN_GROUPS = 2048, 6144, 4
# the serving benchmark's engine: 20 slots, 1,601 pages, 128-page tables
SERVE_B, SERVE_PAGES, SERVE_MAXP = 20, 1601, 128
# the hybrid serving cell (granite-4.0-h-micro): 24 slots; attention 32
# query / 8 kv heads of 64 over 3,841 pages, 160-page tables; Mamba-2 64
# heads of 64, state 128, in 36 layers
HYB_B, HYB_H, HYB_D, HYB_PAGES, HYB_MAXP = 24, 32, 64, 3841, 160
SSM_L, SSM_H, SSM_P, SSM_N = 36, 64, 64, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _compile_paged(one_chip, kv_dtype, C, batch, pages, maxp, heads=H,
                   head_dim=D):
    int8 = kv_dtype == "int8"

    def fn(q, kp, vp, bt, starts, clens, *scales):
        sc = dict(zip(("k_scale", "v_scale"), scales))
        return paged_chunk_attention(q, kp, vp, bt, starts, clens,
                                     scale=head_dim ** -0.5, **sc)

    pool = ((pages, KH, PSIZE, head_dim), jnp.dtype(kv_dtype))
    shapes = [((batch, C, heads, head_dim), jnp.bfloat16), pool, pool,
              ((batch, maxp), jnp.int32), ((batch,), jnp.int32),
              ((batch,), jnp.int32)]
    if int8:
        shapes += [((pages, KH), jnp.float32)] * 2
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("kv_dtype,C", [("bfloat16", 1), ("bfloat16", 16),
                                        ("int8", 1)])
def test_paged_chunk_attention_compiles(one_chip, kv_dtype, C):
    _compile_paged(one_chip, kv_dtype, C, B, PAGES, MAXP)


@pytest.mark.parametrize("kv_dtype,C", [("bfloat16", 1), ("bfloat16", 8),
                                        ("bfloat16", 256), ("int8", 1)])
def test_paged_chunk_attention_compiles_at_serving_cell(one_chip, kv_dtype,
                                                        C):
    """The serving benchmark's geometry with the derived tiling: the wide
    C = 256 step's blocks and scratch must fit the chip's VMEM."""
    _compile_paged(one_chip, kv_dtype, C, SERVE_B, SERVE_PAGES, SERVE_MAXP)


@pytest.mark.parametrize("C", [1, 256])
def test_paged_chunk_attention_compiles_at_head_dim_64(one_chip, C):
    """The hybrid cell's four attention layers: 32 query heads of 64 over
    8 kv heads, a (1, hps, 16, 64) page block."""
    _compile_paged(one_chip, "bfloat16", C, HYB_B, HYB_PAGES, HYB_MAXP,
                   heads=HYB_H, head_dim=HYB_D)


@pytest.mark.parametrize("C", [1, 256])
def test_ssd_chunk_scan_compiles_at_hybrid_cell(one_chip, C):
    """The hybrid cell's scan: one layer's row of the 36 Mamba layers'
    pool of 24 slots' f32 states, advanced by a C-position chunk (C = 1:
    a decode tick) and written back in place."""
    compiled = _compile(
        lambda *a: ssd_chunk_scan(*a), one_chip,
        ((HYB_B, C, SSM_H, SSM_P), jnp.bfloat16),
        ((HYB_B, C, SSM_H), jnp.float32), ((SSM_H,), jnp.float32),
        ((HYB_B, C, SSM_N), jnp.bfloat16), ((HYB_B, C, SSM_N), jnp.bfloat16),
        ((SSM_L, HYB_B, SSM_H, SSM_P, SSM_N), jnp.float32),
        ((HYB_B,), jnp.float32), ((1,), jnp.int32))
    # the state output is the state input's buffer
    assert "output_to_operand_aliasing" in compiled.as_text() \
        or "input_output_alias" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    S = 2048

    def fn(q, k, v):
        return flash_attention(q, k, v, scale=D ** -0.5)

    _compile(fn, one_chip, ((1, H, S, D), jnp.bfloat16),
             ((1, KH, S, D), jnp.bfloat16), ((1, KH, S, D), jnp.bfloat16))


def test_dropout_matmul_compiles(one_chip):
    rows = 8 * 512 // HORN_GROUPS          # batch 8 x seq 512 over 4 groups

    def fn(x, w, mask):
        return dropout_matmul(x, w, mask, block_n=128)

    _compile(fn, one_chip, ((HORN_GROUPS, rows, D_MODEL), jnp.bfloat16),
             ((D_MODEL, D_FF), jnp.bfloat16),
             ((HORN_GROUPS, D_FF // 128), jnp.float32))
