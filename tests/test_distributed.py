"""Distribution-layer tests: sharding rules, group sync, compression,
pipeline parallelism (multi-device via subprocess), elastic meshes."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs.base import (SHAPES, TopologyConfig, get_model_config,
                                list_archs)
from repro.core import group_sync as gs
from repro.launch.mesh import sharding_rules
from repro.optim import compression as C


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------
class _FakeMesh:
    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        import numpy as _np
        self.devices = _np.empty(tuple(sizes.values()))


@pytest.mark.parametrize("arch", [a for a in list_archs() if a != "horn-mnist"])
def test_rules_divisibility(arch):
    """Every mapped axis must divide: the fallback chain never produces an
    invalid sharding for any arch on the production mesh."""
    cfg = get_model_config(arch)
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = sharding_rules(cfg, mesh)
    dims = {
        "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim, "ffn": cfg.d_ff or 1,
        "embed": cfg.d_model, "vocab": cfg.vocab_size,
        "experts": cfg.num_experts or 1,
    }
    for axis, dim in dims.items():
        mapped = rules.get(axis)
        if mapped == "model":
            assert dim % 16 == 0, (arch, axis, dim)
        if mapped == "data":
            assert dim % 16 == 0, (arch, axis, dim)


def test_rules_degrade_on_odd_mesh():
    """Elastic scenario: a 12-way model axis makes 16 kv-heads unshardable ->
    replication, not an error."""
    cfg = get_model_config("gemma2-27b")
    rules = sharding_rules(cfg, _FakeMesh({"data": 14, "model": 12}))
    assert rules["kv_heads"] is None          # 16 % 12 != 0 -> replicate
    assert rules["ffn"] == "model"            # 36864 % 12 == 0 still TP


def test_batch_fallback_for_batch1_decode():
    cfg = get_model_config("mamba2-2.7b")
    rules = sharding_rules(cfg, _FakeMesh({"data": 16, "model": 16}),
                           SHAPES["long_500k"])
    assert rules["batch"] is None
    assert rules["seq"] == "data"


# ---------------------------------------------------------------------------
# group sync / local SGD
# ---------------------------------------------------------------------------
def test_local_sgd_merge_period():
    params = {"w": jnp.stack([jnp.full((3,), float(i)) for i in range(4)])}
    topo = TopologyConfig(kind="local_sgd", local_sgd_period=3)
    out, _ = gs.maybe_merge_local_sgd(params, jnp.asarray(0), topo)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(params["w"]))
    out, _ = gs.maybe_merge_local_sgd(params, jnp.asarray(2), topo)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.full((4, 3), 1.5))     # merged + broadcast


def test_group_drift_metric():
    same = {"w": jnp.ones((4, 3))}
    assert float(gs.group_drift(same)) == 0.0
    diff = {"w": jnp.stack([jnp.zeros(3), jnp.ones(3)])}
    assert float(gs.group_drift(diff)) > 0


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------
@given(seed=st.integers(0, 2**31 - 1), scale=st.floats(1e-3, 1e3))
@settings(max_examples=25, deadline=None)
def test_int8_quantization_bounded_error(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64,)) * scale, jnp.float32)
    q, s = C.quantize_int8(x)
    err = np.abs(np.asarray(C.dequantize_int8(q, s)) - np.asarray(x)).max()
    assert err <= float(s) * 0.5 + 1e-9       # half-ULP of the int8 grid


def test_error_feedback_converges():
    """With error feedback, the *accumulated* compressed signal tracks the
    accumulated true gradient (bias does not build up)."""
    rng = np.random.default_rng(0)
    true_sum = np.zeros(32, np.float32)
    sent_sum = np.zeros(32, np.float32)
    residual = jnp.zeros(32, jnp.float32)
    for t in range(200):
        g = jnp.asarray(rng.normal(size=32) * 0.01, jnp.float32)
        q, s, residual = C.ef_compress(g, residual)
        sent_sum += np.asarray(C.dequantize_int8(q, s))
        true_sum += np.asarray(g)
    # residual is bounded => sums differ by at most the residual
    np.testing.assert_allclose(sent_sum, true_sum,
                               atol=float(np.abs(residual).max()) + 1e-6)


# ---------------------------------------------------------------------------
# pipeline parallelism (needs >1 device -> subprocess with forced host count)
# ---------------------------------------------------------------------------
PIPELINE_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.runtime.pipeline import pipelined_apply

    S, L_per, M, mb, d = 4, 2, 8, 4, 16
    mesh = Mesh(np.array(jax.devices()[:S]), ("stage",))
    key = jax.random.key(0)
    Ws = jax.random.normal(key, (S, L_per, d, d)) * (d ** -0.5)
    x = jax.random.normal(jax.random.key(1), (M, mb, d))

    def block_fn(stage_w, h):
        for i in range(L_per):
            h = jnp.tanh(h @ stage_w[i])
        return h

    out = pipelined_apply(block_fn, Ws, x, mesh=mesh)
    # reference: apply all stages sequentially
    ref = x
    for s in range(S):
        ref = block_fn(Ws[s], ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

    # grads flow through ppermute (reverse schedule for free)
    def loss_pipe(Ws):
        return jnp.sum(pipelined_apply(block_fn, Ws, x, mesh=mesh) ** 2)
    def loss_ref(Ws):
        h = x
        for s in range(S):
            h = block_fn(Ws[s], h)
        return jnp.sum(h ** 2)
    g1 = jax.grad(loss_pipe)(Ws)
    g2 = jax.grad(loss_ref)(Ws)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=1e-4, rtol=1e-4)
    print("PIPELINE_OK")
""")


def test_pipeline_parallelism_4stage():
    r = subprocess.run([sys.executable, "-c", PIPELINE_PROG],
                       capture_output=True, text=True, timeout=300,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"})
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr


def test_bubble_fraction():
    from repro.runtime.pipeline import bubble_fraction
    assert bubble_fraction(4, 16) == pytest.approx(3 / 19)
    assert bubble_fraction(1, 8) == 0


# ---------------------------------------------------------------------------
# Pallas kernels under a mesh: shard_map'd flash + dropout_matmul must train
# like one device (subprocess: 4 forced host devices, interpret kernels)
# ---------------------------------------------------------------------------
KERNEL_MESH_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.kernels import backend as KB
    KB.set_backend("interpret")
    from repro.configs.base import (HornConfig, RunConfig, ShapeConfig,
                                    get_model_config, reduced)
    from repro.core import steps as S
    from repro.data.pipeline import SyntheticTokenPipeline, TokenPipelineConfig
    from repro.launch.mesh import make_test_mesh

    cfg = reduced(get_model_config("qwen3-1.7b"), d_model=128, d_ff=512,
                  head_dim=32)
    run = RunConfig(model=cfg, shape=ShapeConfig("t", "train", 32, 8),
                    horn=HornConfig(enabled=True, num_groups=4),
                    optimizer="adamw", learning_rate=3e-4)
    batch = SyntheticTokenPipeline(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)).batch_at(0)

    def losses(shape):
        mesh = make_test_mesh(*shape)
        step, sh = S.make_train_step(run, mesh)
        state = jax.jit(lambda k: S.init_state(k, run),
                        out_shardings=sh["state"])(jax.random.key(0))
        b = jax.device_put(batch, sh["batch"])
        out = []
        for _ in range(2):
            state, m = step(state, b)
            out.append(float(m["loss"]))
        return out

    want = losses((1, 1))
    for shape in ((4, 1), (2, 2)):
        np.testing.assert_allclose(losses(shape), want, rtol=1e-3)
    try:
        make_test_mesh(8, 1)
    except ValueError:
        print("KERNEL_MESH_OK")
""")


def test_kernels_under_mesh_match_one_device():
    r = subprocess.run([sys.executable, "-c", KERNEL_MESH_PROG],
                       capture_output=True, text=True, timeout=300,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"})
    assert "KERNEL_MESH_OK" in r.stdout, r.stdout + r.stderr[-3000:]
