"""Elastic remesh, multi-device compressed merges, and HLO cost-model
regression (locks the §Roofline instrument against known-FLOPs programs)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest


# ---------------------------------------------------------------------------
# HLO cost model — known-truth regressions
# ---------------------------------------------------------------------------
def test_hlocost_counts_scan_trip_counts():
    from repro.launch.analysis import HloCost

    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    c = jax.jit(f).lower(jnp.zeros((128, 128), jnp.float32)).compile()
    cm = HloCost(c.as_text())
    expected = 10 * 2 * 128 ** 3
    assert abs(cm.flops - expected) / expected < 0.01
    # XLA's own cost_analysis undercounts by the trip count (the reason the
    # custom model exists) — guard that assumption too
    ca = c.cost_analysis()
    if isinstance(ca, list):     # older jax returns one dict per device
        ca = ca[0]
    raw = ca.get("flops", 0.0)
    assert raw < expected / 5


def test_hlocost_nested_scan_multiplies():
    from repro.launch.analysis import HloCost

    def f(x):
        def outer(c, _):
            def inner(d, _):
                return d @ d, None
            d, _ = jax.lax.scan(inner, c, None, length=3)
            return d, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y

    c = jax.jit(f).lower(jnp.zeros((64, 64), jnp.float32)).compile()
    cm = HloCost(c.as_text())
    expected = 4 * 3 * 2 * 64 ** 3
    assert abs(cm.flops - expected) / expected < 0.02


def test_collective_parse_shapes():
    from repro.launch.analysis import shape_bytes
    assert shape_bytes("bf16[2,128]{1,0}") == 2 * 128 * 2
    assert shape_bytes("(f32[4,4], s32[2])") == 64 + 8
    assert shape_bytes("pred[8]") == 8


def test_dryrun_applicability_matrix():
    from repro.launch.dryrun import applicable
    ok, _ = applicable("mamba2-2.7b", "long_500k")
    assert ok
    ok, why = applicable("qwen3-1.7b", "long_500k")
    assert not ok and "full-attention" in why
    ok, why = applicable("whisper-base", "long_500k")
    assert not ok


# ---------------------------------------------------------------------------
# Elastic remesh
# ---------------------------------------------------------------------------
def test_valid_meshes_after_node_loss():
    from repro.runtime.elastic import valid_meshes
    # 256 chips minus one 8-chip host = 248 -> (data, model) options
    opts = valid_meshes(248)
    assert (248, 1) in opts and (124, 2) in opts and (31, 8) in opts
    assert all(d * m == 248 for d, m in opts)


ELASTIC_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.configs.base import (HornConfig, RunConfig, ShapeConfig,
                                    get_model_config, reduced)
    from repro.core import steps as S
    from repro.launch.mesh import ShardingCtx, sharding_rules
    from repro.runtime.elastic import remesh_state
    from repro.checkpoint.checkpointer import Checkpointer
    import tempfile

    cfg = reduced(get_model_config("qwen3-1.7b"), d_model=64, d_ff=128)
    run = RunConfig(model=cfg, shape=ShapeConfig("t", "train", 32, 8),
                    horn=HornConfig(enabled=False), learning_rate=1e-2)

    # train 2 steps on a 4x2 mesh
    devs = np.array(jax.devices()).reshape(4, 2)
    mesh1 = Mesh(devs, ("data", "model"))
    step1, sh1 = S.make_train_step(run, mesh1)
    state = jax.jit(lambda k: S.init_state(k, run),
                    out_shardings=sh1["state"])(jax.random.key(0))
    batch = {"tokens": jnp.ones((8, 32), jnp.int32),
             "labels": jnp.ones((8, 32), jnp.int32)}
    state, m1 = step1(state, batch)
    state, m1 = step1(state, batch)

    # checkpoint, "lose" devices -> restore onto a 2x2 mesh and keep training
    ckdir = tempfile.mkdtemp()
    ck = Checkpointer(ckdir)
    ck.save(2, state)

    devs2 = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh2 = Mesh(devs2, ("data", "model"))
    step2, sh2 = S.make_train_step(run, mesh2)
    like = jax.eval_shape(lambda: S.init_state(jax.random.key(0), run))
    restored, at = ck.restore(like, shardings=sh2["state"])
    assert at == 2
    batch2 = {"tokens": jnp.ones((8, 32), jnp.int32),
              "labels": jnp.ones((8, 32), jnp.int32)}
    restored, m2 = step2(restored, batch2)
    assert np.isfinite(float(m2["loss"]))
    assert int(np.asarray(restored["step"])) == 3
    print("ELASTIC_OK", float(m2["loss"]))
""")


def test_elastic_restart_on_smaller_mesh():
    """Full elastic cycle: train on 4x2 -> checkpoint -> restore on 2x2 ->
    continue training.  Runs in a subprocess with 8 forced host devices."""
    import os
    r = subprocess.run([sys.executable, "-c", ELASTIC_PROG],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "ELASTIC_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


MERGE_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.configs.base import TopologyConfig
    from repro.core.group_sync import merge_grads

    mesh = Mesh(np.array(jax.devices()), ("data",))
    topo = TopologyConfig(kind="allreduce", grad_compression="int8")
    g_global = jnp.arange(4 * 8, dtype=jnp.float32).reshape(4, 8) / 37.0

    def run(g):
        merged, _ = merge_grads({"w": g}, "data", topo, residuals=None)
        return merged["w"]

    fn = jax.shard_map(run, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)
    out = np.asarray(fn(g_global))
    want = np.broadcast_to(np.asarray(g_global).mean(0), (4, 8))
    err = np.abs(out - want).max()
    assert err < np.abs(want).max() / 100, (err, out[0], want[0])
    print("MERGE_OK", err)
""")


def test_compressed_merge_multidevice():
    """int8 error-feedback merge across 4 real (host) devices ~ exact mean."""
    import os
    r = subprocess.run([sys.executable, "-c", MERGE_PROG],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "MERGE_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
