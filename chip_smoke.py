"""Chip smoke test: Horn's serve and train paths on a TPU, end to end.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip Horn train step only

One chip runs five phases in one process, each raising on failure:

  device  the first device is a TPU and the kernel backend is "pallas"
  serve   the ``launch/serve.py`` engine at qwen3-1.7b's published widths
          (random weights from ``--seed``, 256 bf16 pages x 16 tokens)
          serves 8 requests (prompts mixed up to 512, 16 new tokens each);
          every compiled unified step holds the paged kernel
  parity  ``paged_chunk_attention`` (C 1 and 16, bf16 and int8 pools) and
          ``dropout_matmul`` at real widths against their ``ref.py`` oracles
  train   5 steps of ``make_train_step`` with Horn parallel dropout at
          qwen3-1.7b's widths cut to 4 layers (adamw state fits one 16 GB
          chip); finite losses, kernels in the step; then 5 steps of
          ``train_mnist``, the paper's MNIST path

``--chips 4`` runs only the Horn train step on a (data=4, model=1) and a
(data=2, model=2) mesh against the one-chip step on the same global batch
and seed: the losses of the first two steps (the second one follows the
first step's merged gradients) must agree, and the parameters must be
sharded over all four chips.

The last line of stdout is ``{"ok": true, "device": {...}}``; nothing is
printed there unless every phase passed.  Run from the repository root;
JAX's compile cache follows ``JAX_COMPILATION_CACHE_DIR`` or else sits at
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen3-1.7b"
SERVE_ARGV = ["--arch", ARCH, "--full-config", "--pages", "256",
              "--page-size", "16", "--slots", "8", "--budget", "256",
              "--max-prompt", "512", "--gen", "16", "--kv-dtype", "bfloat16"]
REQUESTS = 8
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 8, 512, 5
HORN_GROUPS = 4
# kernel vs oracle: both read the same bf16/int8 inputs and accumulate in
# f32; the bound covers bf16 output rounding plus accumulation order
ATTN_TOL = 2e-2
MATMUL_TOL = 5e-3
# a mesh's losses vs one chip's: two bf16 ulps, relative
LOSS_RTOL = 2.0 ** -7


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_gib(device) -> float:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2 ** 30


def check_device():
    import jax
    from repro.kernels.backend import get_backend

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"device: no TPU (JAX's first device is "
                         f"{dev.platform!r})")
    if get_backend() != "pallas":
        raise SystemExit(f"device: kernel backend {get_backend()!r}, "
                         f"expected 'pallas'")
    log(f"device: {dev.device_kind} x{len(jax.devices())} "
        f"(platform {dev.platform}, backend {get_backend()})")
    return dev


def serve_phase(argv, seed: int, requests: int):
    """Serve ``requests`` through the launcher's engine; returns the
    engine's compiled-step HLO texts after checking every stream."""
    import numpy as np
    from repro.launch import serve

    args = serve.make_parser().parse_args(argv + ["--seed", str(seed)])
    cfg, _, engine = serve.build_engine(args)
    rng = np.random.default_rng(seed)
    for _, prompt, _ in serve.make_requests(
            requests, cfg.vocab_size, rng, stream="batch",
            max_prompt=args.max_prompt, gen=args.gen, long_frac=0.25):
        engine.submit(prompt, args.gen, arrival_time=0.0)
    t0 = time.monotonic()
    while engine.sched.has_work():
        engine.step(time.monotonic() - t0)
    wall = time.monotonic() - t0
    done = engine.sched.finished
    if len(done) != requests:
        raise AssertionError(f"serve: {len(done)} of {requests} finished")
    for r in done:
        toks = np.asarray(r.out_tokens)
        if len(toks) != args.gen or toks.min() < 0 \
                or toks.max() >= cfg.vocab_size:
            raise AssertionError(f"serve: request {r.id} produced {toks!r}")
    prof = engine.obs.profiler
    compile_s = sum(e.dur_s for e in prof.compile_events)
    log(f"serve: {requests} requests, prompts "
        f"{sorted(r.prompt_len for r in done)}, "
        f"{sum(len(r.out_tokens) for r in done)} tokens served in "
        f"{engine.steps} ticks; {prof.compiles_total} step compiles "
        f"took {compile_s:.1f}s of {wall:.1f}s wall")
    return prof.compiled_hlo()


def _paged_case(key, *, B, H, KH, D, psize, P, maxp, C, int8):
    import jax
    import jax.numpy as jnp
    from repro.optim.compression import quantize_int8

    kq, kk, kv, ks, kc = jax.random.split(key, 5)
    q = jax.random.normal(kq, (B, C, H, D), jnp.bfloat16)
    kp = jax.random.normal(kk, (P, KH, psize, D), jnp.float32)
    vp = jax.random.normal(kv, (P, KH, psize, D), jnp.float32)
    bt = 1 + (jnp.arange(B)[:, None] * 31 + jnp.arange(maxp)[None]) % (P - 1)
    starts = jax.random.randint(ks, (B,), 0, maxp * psize - C + 1)
    clens = jax.random.randint(kc, (B,), 0, C + 1).at[0].set(C)
    scales = {}
    if int8:
        kp, k_scale = quantize_int8(kp, axis=(2, 3))
        vp, v_scale = quantize_int8(vp, axis=(2, 3))
        scales = dict(k_scale=k_scale[:, :, 0, 0], v_scale=v_scale[:, :, 0, 0])
    else:
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    return (q, kp, vp, bt.astype(jnp.int32), starts.astype(jnp.int32),
            clens.astype(jnp.int32)), scales


def _max_excess(out, ref, tol: float):
    """(max(|out - ref| - tol * (1 + |ref|)), max |out - ref|); the first
    is <= 0 within tolerance."""
    import numpy as np
    diff = np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32))
    bound = tol * (1.0 + np.abs(np.asarray(ref, np.float32)))
    return float(np.max(diff - bound)), float(np.max(diff))


def parity_phase(seed: int, *, B=8, H=16, KH=8, D=128, psize=16, P=256,
                 maxp=33, G=HORN_GROUPS, M=512, K=2048, N=6144, bn=128):
    """Compiled kernels against their oracles at qwen3-1.7b's widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.dropout_matmul.ops import dropout_matmul
    from repro.kernels.dropout_matmul.ref import dropout_matmul_ref
    from repro.kernels.paged_attention.ops import paged_chunk_attention
    from repro.kernels.paged_attention.ref import paged_chunk_attention_ref

    key = jax.random.key(seed)
    for C in (1, 16):
        for int8 in (False, True):
            key, sub = jax.random.split(key)
            args, scales = _paged_case(sub, B=B, H=H, KH=KH, D=D, psize=psize,
                                       P=P, maxp=maxp, C=C, int8=int8)
            out = paged_chunk_attention(*args, scale=D ** -0.5, **scales)
            ref = paged_chunk_attention_ref(*args, scale=D ** -0.5, **scales)
            err, diff = _max_excess(out, ref, ATTN_TOL)
            name = f"paged_chunk_attention C={C} {'int8' if int8 else 'bf16'}"
            if not err <= 0:
                raise AssertionError(f"parity: {name} off by {err:.3g} "
                                     f"beyond {ATTN_TOL}*(1+|ref|)")
            log(f"parity: {name} max |kernel - ref| {diff:.3g}, within "
                f"{ATTN_TOL}*(1+|ref|)")
    kx, kw, km = jax.random.split(key, 3)
    x = jax.random.normal(kx, (G, M, K), jnp.bfloat16)
    w = (jax.random.normal(kw, (K, N), jnp.float32) / math.sqrt(K)) \
        .astype(jnp.bfloat16)
    mask = 2.0 * jax.random.bernoulli(km, 0.5, (G, N // bn))
    out = dropout_matmul(x, w, mask, block_n=bn)
    ref = dropout_matmul_ref(x, w, mask, block_n=bn)
    err, diff = _max_excess(out, ref, MATMUL_TOL)
    dropped = np.repeat(np.asarray(mask) == 0, bn, axis=-1)[:, None, :]
    if not err <= 0 or np.any(np.asarray(out)[np.broadcast_to(
            dropped, out.shape)] != 0):
        raise AssertionError(f"parity: dropout_matmul off by {err:.3g} "
                             f"beyond {MATMUL_TOL}*(1+|ref|)")
    log(f"parity: dropout_matmul [{G},{M},{K}]x[{K},{N}] max |kernel - ref| "
        f"{diff:.3g}, within {MATMUL_TOL}*(1+|ref|), dropped blocks "
        f"exactly 0")


def train_run(cfg, mesh, seed: int, *, batch: int, seq: int, steps: int):
    """``steps`` Horn train steps on ``mesh``; returns (losses, compiled
    step, state)."""
    import jax
    from repro.configs.base import (HornConfig, RunConfig, ShapeConfig)
    from repro.core import steps as S
    from repro.data.pipeline import SyntheticTokenPipeline, \
        TokenPipelineConfig

    run = RunConfig(model=cfg, shape=ShapeConfig("smoke", "train", seq, batch),
                    horn=HornConfig(enabled=True, num_groups=HORN_GROUPS),
                    optimizer="adamw", learning_rate=3e-4, seed=seed)
    step_fn, sh = S.make_train_step(run, mesh)
    state = jax.jit(lambda k: S.init_state(k, run),
                    out_shardings=sh["state"])(jax.random.key(seed))
    pipe = SyntheticTokenPipeline(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch,
        seed=seed))
    batches = [jax.device_put(pipe.batch_at(i), sh["batch"])
               for i in range(steps)]
    t0 = time.monotonic()
    compiled = step_fn.lower(state, batches[0]).compile()
    compile_s = time.monotonic() - t0
    losses = []
    for b in batches:
        state, metrics = compiled(state, b)
        losses.append(float(metrics["loss"]))
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"train: non-finite loss {losses}")
    return losses, compiled, compile_s, state


def train_phase(seed: int, *, cfg=None, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                steps=TRAIN_STEPS, bytes_limit=None):
    """Horn train steps on one chip; returns the compiled step's HLO.
    With ``bytes_limit`` (the chip's memory), the depth cut must leave the
    step's arguments plus temporaries inside it."""
    from repro.configs.base import get_model_config
    from repro.launch.mesh import make_test_mesh

    cfg = cfg or dataclasses.replace(get_model_config(ARCH),
                                     num_layers=TRAIN_LAYERS)
    losses, compiled, compile_s, _ = train_run(
        cfg, make_test_mesh(1, 1), seed, batch=batch, seq=seq, steps=steps)
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    if bytes_limit is not None and need > bytes_limit:
        raise AssertionError(f"train: step needs {need / 2 ** 30:.2f} GiB, "
                             f"the chip has {bytes_limit / 2 ** 30:.2f}")
    log(f"train: {cfg.name} x{cfg.num_layers} layers, batch {batch} x "
        f"{seq}, Horn {HORN_GROUPS} groups; compile {compile_s:.1f}s; "
        f"memory args {mem.argument_size_in_bytes / 2 ** 30:.2f} GiB + "
        f"temp {mem.temp_size_in_bytes / 2 ** 30:.2f} GiB"
        + (f" of {bytes_limit / 2 ** 30:.2f} GiB" if bytes_limit else "")
        + "; "
        f"losses {[round(x, 4) for x in losses]}")
    return compiled.as_text()


def mnist_phase(seed: int, steps: int = 5):
    from repro.core.collective_trainer import train_mnist

    res = train_mnist(num_groups=HORN_GROUPS, batch_per_group=32,
                      num_steps=steps, eval_every=steps, n_train=2000,
                      seed=seed)
    if not all(math.isfinite(x) for x in res.loss):
        raise AssertionError(f"mnist: non-finite loss {res.loss}")
    log(f"mnist: {steps} steps ({res.data_source}), loss {res.loss[-1]:.4f}, "
        f"accuracy {res.final_accuracy:.3f}")


def four_chip_phase(seed: int, *, cfg=None, batch=TRAIN_BATCH,
                    seq=TRAIN_SEQ, steps: int = 2):
    """The Horn train step on (4, 1) and (2, 2) meshes against one chip:
    same global batch and seed, losses within LOSS_RTOL."""
    import jax
    from repro.configs.base import get_model_config
    from repro.launch.mesh import make_test_mesh

    cfg = cfg or dataclasses.replace(get_model_config(ARCH),
                                     num_layers=TRAIN_LAYERS)
    want, *_ = train_run(cfg, make_test_mesh(1, 1), seed, batch=batch,
                         seq=seq, steps=steps)
    log(f"mesh (1, 1): losses {want}")
    for shape in ((4, 1), (2, 2)):
        mesh = make_test_mesh(*shape)
        got, _, compile_s, state = train_run(cfg, mesh, seed, batch=batch,
                                             seq=seq, steps=steps)
        leaves = jax.tree.leaves(state["params"])
        devices = set().union(*(x.sharding.device_set for x in leaves))
        split = sum(not x.sharding.is_fully_replicated for x in leaves)
        if devices != set(mesh.devices.flat) or not split:
            raise AssertionError(f"mesh {shape}: params on {len(devices)} "
                                 f"devices, {split} leaves sharded")
        off = max(abs(g - w) / abs(w) for g, w in zip(got, want))
        if not off <= LOSS_RTOL:
            raise AssertionError(f"mesh {shape}: losses {got} vs one chip "
                                 f"{want} (rel {off:.3g} > {LOSS_RTOL:.3g})")
        log(f"mesh {shape}: losses {got}, rel diff {off:.2e} <= "
            f"{LOSS_RTOL:.2e}; {split} of {len(leaves)} param leaves "
            f"sharded over {len(devices)} devices; compile {compile_s:.1f}s")


def require_kernel(name: str, hlo: str) -> None:
    n = hlo.count("tpu_custom_call")
    if not n:
        raise AssertionError(f"{name}: no tpu_custom_call in the compiled "
                             f"step")
    log(f"{name}: {n} tpu_custom_call sites in the compiled step")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip Horn train comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: {SRC / 'repro'} not found; run from "
                         f"a checkout of the repository")
    sys.path.insert(0, str(SRC))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    dev = check_device()
    if args.chips == 4:
        four_chip_phase(args.seed)
    else:
        for label, hlo in serve_phase(SERVE_ARGV, args.seed,
                                      REQUESTS).items():
            require_kernel(f"serve {label}", hlo)
        gc.collect()                    # the engine's weights leave the chip
        log(f"serve: peak device memory {peak_gib(dev):.2f} GiB")
        parity_phase(args.seed)
        require_kernel("train", train_phase(
            args.seed, bytes_limit=(dev.memory_stats() or {}).get(
                "bytes_limit")))
        log(f"train: peak device memory {peak_gib(dev):.2f} GiB")
        mnist_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
