"""Per-kernel allclose sweeps: pallas_call(interpret=True) vs ref.py oracles,
over shapes and dtypes, plus integration of the kernels into the model paths.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.dropout_matmul.kernel import dropout_matmul
from repro.kernels.dropout_matmul.ref import dropout_matmul_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd.kernel import ssd_chunk_scan
from repro.kernels.ssd.ref import ssd_ref


# ---------------------------------------------------------------------------
# dropout_matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("G,M,K,N,bn", [
    (1, 128, 128, 128, 128),
    (2, 256, 128, 512, 128),
    (4, 128, 256, 256, 64),
    (3, 128, 384, 640, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dropout_matmul_sweep(G, M, K, N, bn, dtype):
    rng = np.random.default_rng(hash((G, M, K, N)) % 2**31)
    x = jnp.asarray(rng.normal(size=(G, M, K)), dtype)
    w = jnp.asarray(rng.normal(size=(K, N)), dtype)
    mask = jnp.asarray(rng.choice([0.0, 2.0], size=(G, N // bn)), jnp.float32)
    out = dropout_matmul(x, w, mask, block_n=bn, interpret=True)
    ref = dropout_matmul_ref(x, w, mask, block_n=bn)
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=tol * K ** 0.5, rtol=tol)


def test_dropout_matmul_all_dropped_block_is_zero():
    x = jnp.ones((1, 128, 128), jnp.float32)
    w = jnp.ones((128, 256), jnp.float32)
    mask = jnp.asarray([[0.0, 2.0]], jnp.float32)
    out = np.asarray(dropout_matmul(x, w, mask, block_n=128, interpret=True))
    assert (out[:, :, :128] == 0).all()
    assert (out[:, :, 128:] == 2 * 128).all()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,KH,S,D", [
    (1, 2, 2, 128, 64),     # MHA
    (2, 4, 2, 256, 64),     # GQA
    (1, 8, 1, 128, 128),    # MQA
])
@pytest.mark.parametrize("variant", ["causal", "window", "softcap", "full"])
def test_flash_attention_sweep(B, H, KH, S, D, variant):
    rng = np.random.default_rng(hash((B, H, S, variant)) % 2**31)
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KH, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KH, S, D)), jnp.float32)
    kw = dict(causal=True)
    if variant == "window":
        kw = dict(causal=True, window=64)
    elif variant == "softcap":
        kw = dict(causal=True, softcap=50.0)
    elif variant == "full":
        kw = dict(causal=False)
    out = flash_attention(q, k, v, scale=D ** -0.5, block_q=64, block_k=64,
                          interpret=True, **kw)
    ref = attention_ref(q, k, v, scale=D ** -0.5, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    out = flash_attention(q, k, v, scale=0.125, block_q=64, block_k=64,
                          interpret=True)
    ref = attention_ref(q, k, v, scale=0.125)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=0.05)


# ---------------------------------------------------------------------------
# SSD chunk scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 16, 16, 16),
    (2, 128, 3, 16, 32, 32),
    (1, 256, 1, 32, 64, 64),
])
def test_ssd_kernel_sweep(B, S, H, P, N, chunk):
    rng = np.random.default_rng(hash((B, S, H, P, N)) % 2**31)
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32) * 0.5
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, H))) + 0.1, jnp.float32)
    A = -jnp.asarray(np.abs(rng.normal(size=(H,))) + 0.5, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32) * 0.5
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32) * 0.5
    out, _ = ssd_chunk_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref, _ = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("C", [1, 256])
def test_ssd_kernel_carries_state(C):
    """The serving step's call: each slot continues from its stored state
    and the kernel returns the state after the chunk.  Slot 0 runs the
    whole chunk, slot 1 a third of it from zero state (a fresh sequence;
    padding past it takes dt = 0), and slot 2 none of it (chunk_len 0: its
    state must come back unchanged).  The state is a two-layer pool of
    which the call advances one layer and leaves the other as it was."""
    rng = np.random.default_rng(C)
    B, H, P, N = 3, 16, 8, 16
    lens = np.array([C, max(1, C // 3), 0])
    x = jnp.asarray(rng.normal(size=(B, C, H, P)), jnp.float32) * 0.5
    dt = np.abs(rng.normal(size=(B, C, H))) + 0.1
    dt[np.arange(C)[None, :] >= lens[:, None]] = 0.0
    dt = jnp.asarray(dt, jnp.float32)
    A = -jnp.asarray(np.abs(rng.normal(size=(H,))) + 0.5, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, C, N)), jnp.float32) * 0.5
    Cm = jnp.asarray(rng.normal(size=(B, C, N)), jnp.float32) * 0.5
    # the pool of two layers' states; the call advances layer 1
    pool = jnp.asarray(rng.normal(size=(2, B, H, P, N)), jnp.float32)
    s0 = pool[1]
    # slot 1 starts a fresh sequence: its stored state is not read
    keep = jnp.asarray([1.0, 0.0, 1.0], jnp.float32)
    y, out = ssd_chunk_scan(x, dt, A, Bm, Cm, pool, keep,
                            jnp.asarray([1], jnp.int32), interpret=True)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(pool[0]))
    s1 = out[1]
    yr, sr = ssd_ref(x, dt, A, Bm, Cm, s0.at[1].set(0.0))
    for b, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(y[b, :n]),
                                   np.asarray(yr[b, :n]),
                                   atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(sr),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_array_equal(np.asarray(s1[2]), np.asarray(s0[2]))


def test_model_ssd_chunked_matches_sequential_ref():
    """The model's pure-jnp chunked SSD == exact sequential recurrence."""
    from repro.models.ssm import ssd_chunked
    rng = np.random.default_rng(3)
    B, S, H, P, N = 2, 96, 2, 8, 16
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32) * 0.5
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, H))) + 0.1, jnp.float32)
    A = -jnp.asarray(np.abs(rng.normal(size=(H,))) + 0.5, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32) * 0.5
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32) * 0.5
    y, final = ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    yref, fref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(final), np.asarray(fref),
                               atol=2e-4, rtol=1e-3)


def test_model_ssd_chunked_is_finite_under_strong_decay():
    """A whole 256-position chunk at Mamba-2's strongest decay (A = -16,
    dt = 0.1: the cumulative log-decay falls by 410 across the chunk) stays
    finite and exact: above the diagonal exp(cum_i - cum_j) would overflow,
    and the causal mask must act before the exp, not after."""
    from repro.models.ssm import ssd_chunked
    rng = np.random.default_rng(4)
    B, S, H, P, N = 1, 256, 2, 4, 8
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.full((B, S, H), 0.1, jnp.float32)
    A = jnp.asarray([-16.0, -1.0], jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    y, final = ssd_chunked(x, dt, A, Bm, Cm, chunk=256)
    yref, fref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(final), np.asarray(fref),
                               atol=2e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# model-path integration (interpret backend)
# ---------------------------------------------------------------------------
def test_mlp_kernel_path_matches_dense_mask():
    """mlp_apply(mask_blocks=...) via the Pallas kernel == dense masked path."""
    from repro.configs.base import get_model_config, reduced
    from repro.core.steps import make_ctx
    from repro.models.layers import mlp_apply, mlp_specs
    from repro.models.params import init_params
    from repro.kernels import backend as KB

    cfg = reduced(get_model_config("qwen3-1.7b"), d_ff=256, d_model=64)
    ctx = make_ctx(cfg, None)
    params = init_params(jax.random.key(0), mlp_specs(cfg))
    G, B, S = 2, 4, 8
    nb = 2
    x = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model), jnp.float32)
    blocks = jnp.asarray([[0.0, 2.0], [2.0, 0.0]], jnp.float32)  # [G, nb]
    dense_mask = jnp.repeat(jnp.repeat(blocks, cfg.d_ff // nb, -1),
                            B // G, 0)[:, None, :]
    ref = mlp_apply(params, x, cfg, ctx, hidden_mask=dense_mask)
    old = KB.get_backend()
    KB.set_backend("interpret")
    try:
        out = mlp_apply(params, x, cfg, ctx, mask_blocks=blocks)
    finally:
        KB.set_backend(old)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_attention_kernel_path_matches_ref_model():
    """attn_apply with interpret backend == ref backend (same params/input)."""
    from repro.configs.base import get_model_config, reduced
    from repro.core.steps import make_ctx
    from repro.models.attention import attn_apply, attn_specs
    from repro.models.params import init_params
    from repro.kernels import backend as KB

    cfg = reduced(get_model_config("qwen3-1.7b"), d_model=64, head_dim=16)
    ctx = make_ctx(cfg, None)
    params = init_params(jax.random.key(0), attn_specs(cfg))
    x = jax.random.normal(jax.random.key(1), (2, 64, cfg.d_model), jnp.float32)
    old = KB.get_backend()
    KB.set_backend("ref")
    try:
        ref, _ = attn_apply(params, x, cfg, ctx)
        KB.set_backend("interpret")
        out, _ = attn_apply(params, x, cfg, ctx)
    finally:
        KB.set_backend(old)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# dimension_semantics: megacore partitioning must not change numerics
# ---------------------------------------------------------------------------
def _strip_compiler_params(module, jitted):
    """Re-trace ``jitted`` with CompilerParams neutralized, restoring the
    module and jit cache afterwards — the with/without outputs must match
    bitwise (dimension_semantics only licenses megacore partitioning; it
    never reorders the per-step op sequence)."""
    import contextlib

    @contextlib.contextmanager
    def ctx():
        orig = module.pltpu.CompilerParams
        module.pltpu.CompilerParams = lambda **kw: None
        jitted.clear_cache()
        try:
            yield
        finally:
            module.pltpu.CompilerParams = orig
            jitted.clear_cache()

    return ctx()


def test_flash_attention_dimension_semantics_no_numeric_change():
    import repro.kernels.flash_attention.kernel as FK

    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.normal(size=(2, 4, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 128, 64)), jnp.float32)
    kw = dict(scale=0.125, window=48, block_q=64, block_k=64, interpret=True)
    with_sem = FK.flash_attention(q, k, v, **kw)
    with _strip_compiler_params(FK, FK.flash_attention):
        without = FK.flash_attention(q, k, v, **kw)
    assert np.array_equal(np.asarray(with_sem), np.asarray(without))


def test_dropout_matmul_dimension_semantics_no_numeric_change():
    import repro.kernels.dropout_matmul.kernel as DK

    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.normal(size=(2, 128, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    mask = jnp.asarray(rng.choice([0.0, 2.0], size=(2, 2)), jnp.float32)
    with_sem = DK.dropout_matmul(x, w, mask, block_n=128, interpret=True)
    with _strip_compiler_params(DK, DK.dropout_matmul):
        without = DK.dropout_matmul(x, w, mask, block_n=128, interpret=True)
    assert np.array_equal(np.asarray(with_sem), np.asarray(without))


# ---------------------------------------------------------------------------
# forward-only kernels take the reference's gradients; backend selection
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kernel", ["flash", "dropout"])
def test_kernel_gradients_are_the_references(kernel):
    from repro.kernels.dropout_matmul import ops as DO
    from repro.kernels.flash_attention import ops as FO

    rng = np.random.default_rng(19)
    if kernel == "flash":
        args = [jnp.asarray(rng.normal(size=s), jnp.float32)
                for s in ((1, 4, 64, 32), (1, 2, 64, 32), (1, 2, 64, 32))]
        fn = lambda backend: lambda *a: FO.flash_attention(
            *a, scale=0.2, block_q=32, block_k=32, backend=backend)
    else:
        args = [jnp.asarray(rng.normal(size=(2, 8, 64)), jnp.float32),
                jnp.asarray(rng.normal(size=(64, 256)), jnp.float32),
                jnp.asarray([[2.0, 0.0], [0.0, 2.0]], jnp.float32)]
        fn = lambda backend: lambda *a: DO.dropout_matmul(
            *a, block_n=128, backend=backend)
    nargs = tuple(range(len(args)))
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    got = jax.grad(loss(fn("interpret")), argnums=nargs)(*args)
    want = jax.grad(loss(fn("ref")), argnums=nargs)(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-4, rtol=1e-4)


def test_backend_on_a_tpu_is_pallas_only(monkeypatch):
    """A process with a TPU runs the compiled kernels: the default is
    "pallas" and no other backend can be selected."""
    from repro.kernels import backend as KB

    monkeypatch.setattr(KB, "_on_tpu", lambda: True)
    assert KB.default_backend() == "pallas"
    for name in ("ref", "interpret"):
        with pytest.raises(ValueError, match="on a TPU"):
            KB.set_backend(name)
    monkeypatch.setattr(KB, "_on_tpu", lambda: False)
    assert KB.default_backend() == "ref"
    with pytest.raises(ValueError, match="unknown"):
        KB.set_backend("cuda")
