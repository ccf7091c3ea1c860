"""Time ``paged_chunk_attention`` per grid tiling on the chip.

At the serving cell's geometry (qwen3-1.7b: 16 query / 8 kv heads of 128;
20 slots, 1,601 pages of 16 tokens, tables 128 pages wide; bf16 pool, or
int8 with ``--int8``) each chunk width C in ``--chunks`` runs every
(pages_per_step, heads_per_step) pair of the sweep plus the tiling
``chunk_tiling`` derives.  Contexts are drawn like the cell's closed loop
(128 to 1,280 tokens); at C > 1 one slot carries a C-token chunk and the
others decode, as in a tick that prefills one refilled slot.

Per tiling: compile seconds, the device time per call (the kernel's
custom-call events in one profiler trace of ``--reps`` calls, read with
the benchmark's trace reader), the host-clock time per call, and whether
the output equals the one-page, one-head tiling bit for bit.

    python benchmarks/paged_tiling_sweep.py --out chiprun_out/sweep.json

Needs a TPU: the trace reader reads TPU planes only.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

B, H, KH, D, PSIZE, PAGES, MAXP = 20, 16, 8, 128, 16, 1601, 128
KERNEL = "paged_chunk_attention"


def inputs(C: int, int8: bool, seed: int):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    ctx = rng.integers(128, 1281, B)
    clens = np.ones(B, np.int32)
    starts = (ctx - 1).astype(np.int32)
    if C > 1:
        clens[0] = C
        starts[0] = 0
    bt = np.zeros((B, MAXP), np.int32)
    perm = 1 + rng.permutation(PAGES - 1)
    for b in range(B):
        n = -(-(int(starts[b]) + int(clens[b])) // PSIZE)
        bt[b, :n] = perm[b * 80:b * 80 + n]
    q = jnp.asarray(rng.normal(size=(B, C, H, D)), jnp.bfloat16)
    pool = rng.normal(size=(PAGES, KH, PSIZE, D)).astype(np.float32)
    kw = {}
    if int8:
        kp = jnp.asarray(np.clip(np.round(pool * 40), -127, 127), jnp.int8)
        vp = jnp.asarray(np.clip(np.round(pool[::-1] * 40), -127, 127),
                         jnp.int8)
        sc = jnp.asarray(rng.uniform(0.01, 0.03, (PAGES, KH)), jnp.float32)
        kw = dict(k_scale=sc, v_scale=sc[::-1])
    else:
        kp = jnp.asarray(pool, jnp.bfloat16)
        vp = jnp.asarray(pool[::-1], jnp.bfloat16)
    return (q, kp, vp, jnp.asarray(bt), jnp.asarray(starts),
            jnp.asarray(clens)), kw


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--chunks", default="1,8,256")
    ap.add_argument("--pps", default="1,2,4,8,16,32")
    ap.add_argument("--hps", default="1,8")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--int8", action="store_true")
    ap.add_argument("--seed", type=int, default=1234567891)
    args = ap.parse_args(argv)

    import jax
    from harness import tracing
    from repro.kernels.paged_attention.kernel import (chunk_tiling,
                                                      paged_chunk_attention)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    rows, plan = [], []
    for C in [int(c) for c in args.chunks.split(",")]:
        a, kw = inputs(C, args.int8, args.seed + C)
        derived = chunk_tiling(C, H // KH, KH, MAXP)
        pairs = [(p, h) for h in map(int, args.hps.split(","))
                 for p in map(int, args.pps.split(","))]
        if derived not in pairs:
            pairs.append(derived)
        base = None
        for pps, hps in [(1, 1)] + [p for p in pairs if p != (1, 1)]:
            # the kernel is jitted with its tiling static: one compile each
            fn = functools.partial(
                paged_chunk_attention, scale=D ** -0.5, pages_per_step=pps,
                heads_per_step=hps, **kw)
            row = {"C": C, "pps": pps, "hps": hps,
                   "derived": (pps, hps) == derived,
                   "grid_steps": B * (KH // hps) * -(-MAXP // pps)}
            t0 = time.perf_counter()
            try:
                out = fn(*a).block_until_ready()
            except Exception as e:          # a tiling the compiler refuses
                row["error"] = f"{type(e).__name__}: {str(e)[:400]}"
                rows.append(row)
                print(json.dumps(row), flush=True)
                continue
            row["compile_s"] = time.perf_counter() - t0
            host = np.asarray(out.astype(jax.numpy.float32))
            if base is None:
                base = host
            row["bitwise"] = bool(np.array_equal(host, base))
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = fn(*a)
            out.block_until_ready()
            row["host_ms"] = 1e3 * (time.perf_counter() - t0) / args.reps
            rows.append(row)
            plan.append((row, fn, a))

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _, fn, a in plan:
            for _ in range(args.reps):
                out = fn(*a)
            out.block_until_ready()
        jax.profiler.stop_trace()
        tr = tracing.load(tracing.latest_xplane(d))
    ops = sorted((o for ops in tr.devices.values() for o in ops
                  if tracing.is_kernel(o, KERNEL)), key=lambda o: o.start)
    if len(ops) != args.reps * len(plan):
        raise SystemExit(f"{len(ops)} {KERNEL} events for "
                         f"{args.reps * len(plan)} calls")
    for i, (row, _, _) in enumerate(plan):
        got = ops[i * args.reps:(i + 1) * args.reps]
        row["device_ms"] = 1e-6 * sum(o.end - o.start for o in got) \
            / args.reps
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "geometry": {"B": B, "H": H, "KH": KH, "D": D, "psize": PSIZE,
                           "pages": PAGES, "maxp": MAXP,
                           "kv": "int8" if args.int8 else "bfloat16"},
              "reps": args.reps, "rows": rows}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(result, indent=1))
    for r in rows:
        print(f"C={r['C']:4d} pps={r['pps']:3d} hps={r['hps']} "
              f"steps={r['grid_steps']:6d} "
              + (f"device={r['device_ms']:.4f}ms host={r['host_ms']:.3f}ms "
                 f"bitwise={r['bitwise']}{' derived' * r['derived']}"
                 if "device_ms" in r else r.get("error", "")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
