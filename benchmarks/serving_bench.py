"""Serving benchmark: continuous-batching engine under a fixed synthetic
load; emits ``BENCH_serving.json`` so the perf trajectory is recorded per PR.

    PYTHONPATH=src python benchmarks/serving_bench.py [--arch qwen3-1.7b]
        [--requests 32] [--long-frac 0.1] [--out BENCH_serving.json]

Nine phases:
  "default"        the log-uniform prompt mix (comparable across PRs)
  "long_mix"       the adversarial mix: ``--long-frac`` of prompts pinned
                   at ``max_prompt`` exactly.  Before chunked prefill,
                   every such admission stalled the whole decode batch for
                   a serial full-prompt prefill; now a tick is bounded by
                   the token budget, so ``stall_max_s`` should sit near
                   ``tick_p50_s`` instead of scaling with prompt length.
  "squeeze"        a deliberately undersized pool (13 x 4-token pages, 4
                   slots) under ``on_demand`` — the load that used to exit
                   2 with EngineOOM; records the throughput cost of
                   preempt + chunked re-prefill (``preemptions`` must be
                   > 0 here or the phase is not squeezing).
  "multi_submodel" the same default load served by a 4-circuit ModelBank
                   (least-loaded routing, 25% of requests fanned as
                   mean-logit ensembles): per-submodel tok/s and pool
                   pressure, TTFT, and the co-batch ratio — the fraction
                   of ticks whose ONE jitted call carried >= 2 distinct
                   sub-models (must be > 0 or nothing is co-batching).
                   An ensemble group counts ONCE in ttft/lat percentiles
                   and in ``delivered_tok_s`` (one user-visible stream);
                   ``decode_tok_s`` keeps counting per-member device
                   tokens, so the two diverge exactly by the ensemble
                   fan-out.
  "prefix_cache"   the same load served cold (--no-prefix-cache) and warm:
                   "shared_prompt_*" pins 3/4 of every prompt to one
                   system prefix (the millions-of-users mix — warm must
                   show a high ``prefix_hit_rate``, big
                   ``prefill_tok_saved``, and strictly lower TTFT p50);
                   "ensemble_*" fans every request across all circuits
                   (warm prefill_tok ~ 1/G of cold: the leader encodes
                   the shared context once, members fork its pages and
                   copy-on-write only their decode tails).
  "speculative"    a decode-heavy mix (short prompts, long generations)
                   served plain and with ``--speculate-k`` draft tokens
                   per decode tick: ``accept_rate`` (drafts surviving
                   verification), ``accepted_tok_per_tick`` (committed
                   tokens per speculating slot-tick; plain decode's
                   ceiling is 1.0), and decode tok/s against the
                   non-speculative baseline on the SAME mix.  Both runs
                   use the replay warmup (the measured load driven once,
                   compile-free clock) and no prefix cache, so the delta
                   is speculation alone.
  "kernel_bench"   roofline-style micro-bench of the paged chunk-attention
                   kernel variants: pages_per_step in {1, 2, 4} x
                   {f32, int8} pools, reporting per-variant wall time,
                   decode tok/s and achieved KV bytes/s (interpret mode on
                   CPU — a scheduling proxy; the compiled kernel on TPU).
  "int8"           the quantized paged-KV phase: effective capacity ratio
                   of int8 pages + f32 scale sidecars vs bf16 at equal
                   HBM, the squeeze load rerun with the page count that
                   budget affords under int8 (preemptions must drop), and
                   the greedy-decode divergence bound vs an f32 engine.
  "observability"  the decode-heavy closed-loop mix served with telemetry
                   fully off (no lifecycle tracer, no timeline) and fully
                   on (tracer + per-tick Perfetto timeline, unbounded
                   retention): ``overhead_frac`` is the decode tok/s cost
                   of full tracing, CI-gated at <= 3% — instrumentation
                   must stay on the host side of the jitted step.

Metrics (virtual arrival clock at --rate req/s, wall-clock service times):
  decode_tok_s   generated tokens / wall time of the measured phase
  tok_per_tick   mean decode-batch occupancy (continuous-batching win)
  ttft_p50/p99   arrival -> first token (s)
  lat_p50/p99    arrival -> completion (s)
  tick_p50_s     median unified-tick duration
  stall_p99_s /  per-tick wall time observed while >=1 already-running
  stall_max_s    request was decoding — the decode-latency spike an
                 admission injects (the number chunked prefill bounds)
  peak_util      page-pool peak utilization
  preemptions    pool-pressure evictions (on_demand policy)

A warmup pass (same chunk-width buckets) runs first so compile time doesn't
pollute the steady-state numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def run(arch: str = "qwen3-1.7b", requests: int = 32, rate: float = 16.0,
        slots: int = 8, pages: int = 512, page_size: int = 16,
        max_prompt: int = 64, gen: int = 16, budget: int = 64,
        long_frac: float = 0.0, stream: str = "poisson", seed: int = 0,
        submodels: int = 0, ensemble_frac: float = 0.0,
        prefix_cache: bool = True, shared_prefix: int = 0,
        speculate: int = 0, draft_keep: float = 0.875,
        warm_with_load: bool = False, observability: str = "default",
        keep_ticks: bool = False, kv_dtype: str = "bfloat16",
        pages_per_step: int = 1, _engine_cache={}):
    import jax
    from repro.configs.base import HornConfig, get_model_config, reduced
    from repro.launch.serve import build_draft, make_requests
    from repro.models import api
    from repro.serving import Engine, EngineConfig, ModelBank, Router, \
        Telemetry
    from repro.serving.observability import percentile_or_none

    cfg = reduced(get_model_config(arch))
    ecfg = EngineConfig(
        num_slots=slots, num_pages=pages, page_size=page_size,
        max_prompt_len=-(-max_prompt // page_size) * page_size,
        max_new_tokens=gen, token_budget=max(budget, slots), seed=seed,
        policy="on_demand", prefix_cache=prefix_cache,
        speculate_k=speculate, kv_dtype=kv_dtype,
        pages_per_step=pages_per_step)
    key = (arch, seed)
    if key not in _engine_cache:          # share params across phases
        _engine_cache.clear()
        _engine_cache[key] = api.model_init(jax.random.key(seed), cfg)
    params = _engine_cache[key]
    rng = np.random.default_rng(seed)
    bank = router = None
    if submodels:
        # slots >= submodels for ensembles is validated by Engine.submit
        bank = ModelBank(cfg, HornConfig(enabled=True, keep_hidden=0.5,
                                         keep_input=1.0, block_size=16),
                         submodels, seed=seed)
        router = Router(submodels)        # least-loaded
    draft = build_draft(cfg, params, bank, speculate=speculate,
                        draft_circuit=0, draft_keep=draft_keep,
                        mask_block=16, seed=seed)

    def load(n):
        return make_requests(n, cfg.vocab_size, rng, stream=stream,
                             rate=rate, max_prompt=max_prompt, gen=gen,
                             long_frac=long_frac,
                             shared_prefix=shared_prefix)

    def drive(engine, reqs):
        """Arrivals on the same wall clock as serve.py, except that when the
        engine fully drains the next future arrival is pulled forward —
        measures service, not idle waiting.  Returns (wall, ticks, stalls):
        per-tick durations, and the subset observed while at least one
        already-running request was decoding (the stall an admission
        injects into in-flight requests)."""
        t0 = time.monotonic()
        pending = list(reqs)
        ticks, stalls = [], []

        def _submit(at, prompt, g):
            ens = "mean_logit" if bank is not None \
                and rng.uniform() < ensemble_frac else None
            engine.submit(prompt, g, arrival_time=at, ensemble=ens)
            n_ensembles[0] += ens is not None

        while pending or engine.sched.has_work():
            now = time.monotonic() - t0
            while pending and pending[0][0] <= now:
                at, prompt, g = pending.pop(0)
                _submit(at, prompt, g)
            if not engine.sched.has_work() and pending:
                at, prompt, g = pending.pop(0)
                _submit(min(at, now), prompt, g)
            decoding = any(not r.in_prefill
                           for r in engine.sched.running.values())
            tt0 = time.monotonic()
            engine.step(time.monotonic() - t0,
                        tick_clock=lambda: time.monotonic() - t0)
            dt = time.monotonic() - tt0
            ticks.append(dt)
            if decoding:
                stalls.append(dt)
        return time.monotonic() - t0, ticks, stalls

    # warmup: compile every power-of-two chunk-width bucket the measured
    # phase can hit, on the SAME engine (each Engine owns a fresh jit cache,
    # so a throwaway warmup engine would not keep compile spikes out of the
    # stall numbers; a random load would miss rare widths).  The final
    # max-width prompt matters when the budget is not a power of two: a
    # 24-token chunk compiles the C=32 cell no pow2-length prompt reaches
    # "off" = no lifecycle tracer, no timeline (the overhead baseline);
    # "full" = tracer + per-tick timeline with unbounded retention (what
    # --trace-out costs); "default" = the engine's stock telemetry
    if observability == "off":
        telemetry = Telemetry(tracer=False)
    elif observability == "full":
        telemetry = Telemetry(timeline=True, trace_maxlen=None)
    else:
        telemetry = None
    engine = Engine(cfg, params, ecfg, bank=bank, router=router,
                    draft=draft, telemetry=telemetry)
    widths, w = [engine.max_chunk], 1
    while w < engine.max_chunk:
        widths.append(w)
        w <<= 1
    # warmup prompts are DISTINCT random streams (separate rng): identical
    # prompts would hit the prefix cache and skip the very chunk widths
    # the sweep exists to compile
    wrng = np.random.default_rng(seed + 10_007)
    for w in sorted(widths):
        engine.submit(wrng.integers(1, cfg.vocab_size, (w,)), 2)
        engine.run()
    if bank is not None and ensemble_frac > 0:
        # the combine path is a SEPARATE jit variant (ensembles=True): warm
        # it at every chunk-width bucket too, by co-batching an ensemble
        # with a bucket-width solo prompt (solo admits first -> its chunk
        # sets the tick's C bucket while the group is in flight)
        for w in sorted(widths):
            engine.submit(wrng.integers(1, cfg.vocab_size, (w,)), 2)
            engine.submit(wrng.integers(1, cfg.vocab_size, (4,)), 2,
                          ensemble="mean_logit")
            engine.run()
    engine.reset_stats()

    n_ensembles = [0]
    reqs = load(requests)
    if warm_with_load:
        # replay warmup: drive the EXACT measured load once first, so
        # every jit cell it hits — including the speculative verify-window
        # and draft catch-up buckets, whose (C, S_v) combinations a width
        # sweep cannot enumerate — is compiled before the clock starts.
        # Run with the prefix cache off, or warmup would seed the cache
        # and the measured run would hit different cells than it compiled.
        assert not prefix_cache, "replay warmup needs prefix_cache=False"
        drive(engine, reqs)
        engine.reset_stats()
        n_ensembles[0] = 0
    cpu0 = time.process_time()
    wall, ticks, stalls = drive(engine, reqs)
    cpu_s = time.process_time() - cpu0
    # an ensemble group delivers ONE token stream through G member slots:
    # latency/TTFT/delivered-throughput count each group once (its leader),
    # while decode_tok_s keeps counting member tokens (device throughput)
    done = engine.finished_streams()
    ttft = np.asarray([r.t_first_token - r.arrival_time for r in done])
    lat = np.asarray([r.t_done - r.arrival_time for r in done])
    total_new = sum(len(r.out_tokens) for r in engine.sched.finished)
    delivered = sum(len(r.out_tokens) for r in done)
    pct = percentile_or_none
    # one telemetry snapshot is the read surface for everything the engine
    # counted; request timestamps stay the ground truth for the exact
    # latency percentiles (the streaming histograms in m["latency"] are
    # the no-retention view of the same samples)
    m = engine.metrics()
    c, d = m["counters"], m["derived"]

    out = {
        "requests": requests, "long_frac": long_frac,
        "wall_s": round(wall, 3),
        "decode_tok_s": round(total_new / max(wall, 1e-9), 2),
        # process CPU time per generated token: the contention-immune
        # instrument the observability overhead gate compares on (wall
        # clock on a shared box jitters far more than a few percent)
        "cpu_us_per_tok": round(cpu_s / max(total_new, 1) * 1e6, 2),
        "tok_per_tick": round(c["generated_tokens"]
                              / max(c["steps"], 1), 2),
        "prefill_tok": c["prefill_tokens"],
        "ttft_p50_s": pct(ttft, 50), "ttft_p99_s": pct(ttft, 99),
        "lat_p50_s": pct(lat, 50), "lat_p99_s": pct(lat, 99),
        "tick_p50_s": pct(ticks, 50),
        "stall_p99_s": pct(stalls, 99), "stall_max_s": pct(stalls, 100),
        "peak_util": round(c["peak_utilization"], 4),
        "preemptions": d["preemptions"],
        "bt_rows_per_tick": round(c["bt_rows_synced"]
                                  / max(c["steps"], 1), 3),
    }
    if prefix_cache:
        hr = d["prefix_hit_rate"]        # None when nothing was eligible
        out.update({
            "prefix_hit_rate": None if hr is None else round(hr, 4),
            "prefill_tok_saved": c["prefill_tok_saved"],
            "cache_evictions": d["cache_evictions"],
            "cow_page_copies": c["cow_page_copies"],
        })
    if speculate:
        out.update({
            "speculate_k": speculate,
            "accept_rate": round(d["accept_rate"], 4),
            "accepted_tok_per_tick": round(d["accepted_tok_per_tick"], 4),
            "spec_drafted": c["spec_drafted"],
            "draft_calls": m["spec"]["draft_calls"],
            "draft_kept_frac": round(engine.spec.draft.kept_frac, 4),
        })
    if bank is not None:
        by_sub = c["tokens_by_submodel"]
        peak_sub = c["peak_util_by_submodel"]
        out.update({
            "submodels": submodels, "ensemble_frac": ensemble_frac,
            "ensemble_groups": n_ensembles[0],
            "delivered_tok_s": round(delivered / max(wall, 1e-9), 2),
            "cobatch_ratio": round(d["cobatch_ratio"], 4),
            "tok_s_by_submodel": {
                str(g): round(by_sub.get(g, 0) / max(wall, 1e-9), 2)
                for g in range(submodels)},
            "peak_util_by_submodel": {
                str(g): round(peak_sub.get(g, 0.0), 4)
                for g in range(submodels)},
        })
    if observability != "default":
        out["observability"] = observability
        if engine.obs.timeline is not None:
            out["timeline_events"] = engine.obs.timeline.num_events
        if engine.obs.tracer is not None:
            out["trace_events"] = engine.obs.tracer.num_events
    if keep_ticks:
        # raw per-tick durations for callers that pool samples across
        # runs (the observability phase); popped before the artifact
        out["_ticks_us"] = [t * 1e6 for t in ticks]
    return out


def observability_phase(args, repeats: int = 3) -> dict:
    """Telemetry fully off vs fully on (lifecycle tracer + per-tick
    Perfetto timeline, unbounded retention) on the same decode-heavy
    closed-loop mix the speculative phase uses — both replay-warmed, so
    ``overhead_frac`` is instrumentation cost alone.

    Estimator: both modes replay the identical batch load (same seed ->
    same tick-by-tick schedule, same tokens per tick), so per-mode tick
    duration is an inverse decode-throughput measure.  Shared-box
    contention only ever makes a tick *slower*, and the per-tick
    telemetry cost is uniform (every tick pays the same hook work), so
    the contention-free cost of a tick is its pooled *p10* across
    interleaved runs — the classic min-timing estimator, applied
    per-tick where hundreds of samples exist instead of per-run where
    three do.  ``overhead_frac`` is the pooled-p10 ratio minus one;
    run-level ``decode_tok_s`` stays in the artifact for reference but
    jitters by tens of percent at sub-second run lengths."""
    from repro.serving.observability import percentile
    kw = dict(arch=args.arch, requests=max(args.requests, 48), slots=4,
              pages=args.pages, page_size=args.page_size, max_prompt=16,
              gen=32, budget=args.budget, stream="batch",
              prefix_cache=False, warm_with_load=True)
    ticks = {"off": [], "full": []}
    runs = {"off": [], "full": []}
    for _ in range(repeats):
        for mode in ("off", "full"):
            r = run(**kw, observability=mode, keep_ticks=True)
            ticks[mode] += r.pop("_ticks_us")
            runs[mode].append(r)
    p10 = {m: percentile(ts, 10) for m, ts in ticks.items()}
    off, full = (max(runs[m], key=lambda r: r["decode_tok_s"])
                 for m in ("off", "full"))
    return {
        "off": off, "full": full,
        "tick_p10_us": {m: round(v, 2) for m, v in p10.items()},
        "tick_samples": {m: len(ts) for m, ts in ticks.items()},
        "overhead_frac": round(p10["full"] / p10["off"] - 1.0, 4),
    }


def kernel_bench_phase(args, reps: int = 3) -> dict:
    """Roofline-style micro-bench of the paged chunk-attention kernel
    (the unified tick's decode workhorse) across its new variants:
    pages_per_step x {f32, int8}.  Each variant reports best-of-``reps``
    wall time per call, decode tok/s (one token per batch row per call),
    and achieved KV bytes/s — the page bytes one layer's grid must move
    from HBM, ``kv_page_bytes`` per live page, so the f32-vs-int8 bytes/s
    gap shows the quantized pool shrinking the memory term, not the
    clock.  On CPU the kernels run in Pallas interpret mode, so absolute
    numbers are a scheduling proxy (per-grid-step overhead dominates:
    pages_per_step > 1 shows up directly as fewer, fatter steps); on TPU
    the same harness times the compiled kernel against the HBM roofline."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_model_config, reduced
    from repro.kernels.paged_attention.kernel import paged_chunk_attention
    from repro.optim.compression import quantize_int8
    from repro.serving.kv_cache import kv_page_bytes

    cfg = reduced(get_model_config(args.arch))
    H, KH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    B, psize, maxp = 4, args.page_size, 16
    interpret = jax.default_backend() == "cpu"
    rng = np.random.default_rng(0)
    P = B * maxp + 1
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, KH, psize, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, KH, psize, D)), jnp.float32)
    kq, ks = quantize_int8(kp, axis=(2, 3))
    vq, vs = quantize_int8(vp, axis=(2, 3))
    ks, vs = ks[:, :, 0, 0], vs[:, :, 0, 0]
    bt = np.zeros((B, maxp), np.int32)
    for b in range(B):                       # every row at full context
        bt[b] = 1 + b * maxp + np.arange(maxp)
    bt = jnp.asarray(bt)
    starts = jnp.full((B,), maxp * psize - 1, jnp.int32)
    clens = jnp.ones((B,), jnp.int32)

    def bench(pools, scales, dtype_name):
        kw = dict(scale=D ** -0.5, interpret=interpret, **scales)
        out = {}
        for pps in (1, 2, 4):
            fn = lambda: paged_chunk_attention(
                *pools, bt, starts, clens, pages_per_step=pps, **kw)
            jax.block_until_ready(fn())      # compile/trace warmup
            best = min(_timed(fn) for _ in range(reps))
            kv_bytes = B * maxp * kv_page_bytes(psize, KH, D, dtype_name)
            out[f"pps{pps}"] = {
                "wall_us": round(best * 1e6, 1),
                "tok_s": round(B / best, 2),
                "kv_gb_s": round(kv_bytes / best / 1e9, 4),
                # the page-axis extent one (slot, kv-head) pair walks —
                # what pages_per_step actually collapses (the DMA-overlap
                # win this buys is hardware-only; interpret wall time
                # pays python-level plumbing per extra BlockSpec instead)
                "grid_steps": -(-maxp // pps),
            }
        return out

    def _timed(fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return time.perf_counter() - t0

    return {
        "B": B, "heads": H, "kv_heads": KH, "head_dim": D,
        "page_size": psize, "pages_per_seq": maxp,
        "interpret": interpret,
        "f32": bench((q, kp, vp), {}, "float32"),
        "int8": bench((q, kq, vq),
                      dict(k_scale=ks, v_scale=vs), "int8"),
    }


def int8_phase(args, squeeze_f32: dict) -> dict:
    """The quantized-pool phase: (1) effective capacity — int8 pages +
    scale sidecars vs bf16 at equal HBM bytes (``capacity_ratio`` must
    clear ~2x); (2) the squeeze load rerun under int8 with the page count
    the SAME HBM budget now affords — pool pressure drops, so preemptions
    must come in strictly below the bf16 squeeze; (3) greedy-decode
    divergence vs an f32-pool engine on one load (quantize-on-append
    requantizes whole pages, so exact token match is not expected —
    ``greedy_match_frac`` documents the bound CI gates on)."""
    import jax
    from repro.configs.base import get_model_config, reduced
    from repro.models import api
    from repro.serving import Engine, EngineConfig
    from repro.serving.kv_cache import kv_page_bytes

    cfg = reduced(get_model_config(args.arch))
    KH, D = cfg.num_kv_heads, cfg.head_dim

    def ratio_at(psize):
        return (kv_page_bytes(psize, KH, D, "bfloat16")
                / kv_page_bytes(psize, KH, D, "int8"))

    # headline capacity at the serving default geometry (the ~2x claim
    # needs psize * head_dim to amortize the per-head scale sidecar; the
    # squeeze phase's deliberately tiny 4-token pages sit a bit lower and
    # get their own ratio for the equal-HBM page-count conversion)
    sq_psize, sq_pages = 4, 13               # the squeeze phase's geometry
    pages_int8 = int(sq_pages * ratio_at(sq_psize))
    squeeze_int8 = run(arch=args.arch, requests=args.requests,
                       rate=args.rate, slots=4, pages=pages_int8,
                       page_size=sq_psize, max_prompt=16, gen=12, budget=16,
                       stream="batch", kv_dtype="int8")

    params = api.model_init(jax.random.key(0), cfg)
    rng = np.random.default_rng(7)
    gen = 12
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in rng.integers(3, 13, size=8)]

    def greedy(kv_dtype):
        eng = Engine(cfg, params, EngineConfig(
            num_slots=4, num_pages=64, page_size=4, max_prompt_len=16,
            max_new_tokens=gen, token_budget=24, policy="on_demand",
            kv_dtype=kv_dtype, compute_dtype="float32"))
        for p in prompts:
            eng.submit(p, gen)
        fin = eng.run()
        return [list(r.out_tokens) for r in sorted(fin, key=lambda r: r.id)]

    f32_out, q8_out = greedy("float32"), greedy("int8")
    match = float(np.mean([np.mean([a == b for a, b in zip(x, y)])
                           for x, y in zip(f32_out, q8_out)]))
    return {
        "capacity_ratio": round(ratio_at(args.page_size), 4),
        "squeeze_capacity_ratio": round(ratio_at(sq_psize), 4),
        "squeeze_pages": {"bf16": sq_pages, "int8": pages_int8},
        "squeeze_preemptions": {"bf16": squeeze_f32["preemptions"],
                                "int8": squeeze_int8["preemptions"]},
        "squeeze_int8": squeeze_int8,
        "greedy_match_frac": round(match, 4),
        "greedy_requests": len(prompts), "greedy_gen": gen,
    }


def replay_phase() -> dict:
    """Replay every pinned trace under ``benchmarks/traces/`` and emit
    the baseline block ``benchmarks/regression.py`` gates against:
    per-trace token digest, virtual-clock TTFT/latency p99, pooled-p10
    decode tok/s, accept rate.  Regenerating BENCH_serving.json with
    this script therefore also rebaselines the regression gate."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import regression
    return {name: regression.baseline_entry(res)
            for name, res in regression.replay_phase().items()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=16.0)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--pages", type=int, default=512,
                    help="shrink (e.g. 16 4-token pages) to bench the "
                         "preemption path under real pool pressure")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-prompt", type=int, default=64)
    ap.add_argument("--budget", type=int, default=64)
    ap.add_argument("--long-frac", type=float, default=0.1,
                    help="fraction of long_mix prompts pinned at --max-prompt")
    ap.add_argument("--submodels", type=int, default=4,
                    help="ModelBank size for the multi_submodel phase")
    ap.add_argument("--ensemble-frac", type=float, default=0.25,
                    help="fraction of multi_submodel requests fanned across "
                         "all circuits (mean-logit)")
    ap.add_argument("--speculate-k", type=int, default=4,
                    help="draft tokens per decode tick in the speculative "
                         "phase")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()
    if args.ensemble_frac > 0 and args.submodels > args.slots:
        raise SystemExit(
            f"ensemble fan-out needs --slots >= --submodels "
            f"({args.slots} < {args.submodels})")
    common = dict(arch=args.arch, requests=args.requests, rate=args.rate,
                  slots=args.slots, pages=args.pages,
                  page_size=args.page_size, max_prompt=args.max_prompt,
                  budget=args.budget)
    res = {
        "arch": args.arch, "slots": args.slots, "budget": args.budget,
        "pages": args.pages, "page_size": args.page_size,
        "max_prompt": args.max_prompt,
        "default": run(**common),
        "long_mix": run(**common, long_frac=args.long_frac),
        "squeeze": run(arch=args.arch, requests=args.requests,
                       rate=args.rate, slots=4, pages=13, page_size=4,
                       max_prompt=16, gen=12, budget=16, stream="batch"),
        "multi_submodel": run(**common, submodels=args.submodels,
                              ensemble_frac=args.ensemble_frac),
        # the prefix-cache phase: identical loads served cold (cache off)
        # and warm (cache on).  shared_prompt pins 3/4 of every prompt to
        # one system prefix — hit rate must be well over 50% and TTFT p50
        # strictly lower than cold; ensemble fans every request across all
        # circuits — warm prefill must approach 1/G of cold
        "prefix_cache": {
            "shared_prompt_cold": run(**common, prefix_cache=False,
                                      shared_prefix=3 * args.max_prompt
                                      // 4),
            "shared_prompt_warm": run(**common, prefix_cache=True,
                                      shared_prefix=3 * args.max_prompt
                                      // 4),
            "ensemble_cold": run(**common, submodels=args.submodels,
                                 ensemble_frac=1.0, prefix_cache=False),
            "ensemble_warm": run(**common, submodels=args.submodels,
                                 ensemble_frac=1.0, prefix_cache=True),
        },
        # speculative decoding vs plain decode on an identical decode-heavy
        # closed-loop mix: short prompts, long generations, few slots (the
        # decode-bound regime where landing K+1 tokens per tick pays)
        "speculative": dict(
            (name, run(arch=args.arch, requests=args.requests,
                       slots=4, pages=args.pages,
                       page_size=args.page_size, max_prompt=16, gen=24,
                       budget=args.budget, stream="batch",
                       prefix_cache=False, warm_with_load=True,
                       speculate=k))
            for name, k in (("baseline", 0), ("speculate",
                                              args.speculate_k))),
        # full tracing vs telemetry-off on the identical decode-heavy
        # closed-loop mix (replay-warmed, compile-free): the decode tok/s
        # cost of observability, CI-gated at <= 3%
        "observability": observability_phase(args),
        # roofline-style kernel micro-bench: pages_per_step x {f32, int8}
        # variants of the paged chunk-attention kernel, tok/s + KV bytes/s
        "kernel_bench": kernel_bench_phase(args),
        # pinned-trace replay baselines (token digests, virtual-clock
        # TTFT/latency, pooled-p10 decode tok/s, accept rate) — the block
        # benchmarks/regression.py gates every CI run against
        "replay": replay_phase(),
    }
    # quantized-pool phase needs the squeeze result for its preemption
    # comparison at equal HBM budget
    res["int8"] = int8_phase(args, res["squeeze"])
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
        f.write("\n")
    print(json.dumps(res, indent=2))


if __name__ == "__main__":
    main()
