"""Serving launcher: continuous-batching engine under a synthetic load.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --stream poisson --requests 32

Drives ``repro.serving.Engine`` (paged KV cache + FCFS continuous batching
+ chunked prefill) from a synthetic request stream: Poisson arrivals with
mixed prompt lengths, each request joining the batch the moment a slot and
pages free up and leaving on completion.  Every engine tick is one unified
device call over a fixed token budget (``--budget``), so a long admission
never stalls the running batch for more than one tick.  Reports decode
tok/s, time-to-first-token, p50/p99 end-to-end latency, and preemptions
(pool pressure under ``--policy on_demand`` evicts the youngest sequence
back to the queue instead of killing the server).

``--stream batch`` submits everything at t=0 (a closed-loop throughput
measurement); ``--stream poisson`` is the open-loop latency measurement.
``--long-frac`` pins that fraction of prompts at ``--max-prompt`` — the
adversarial mix that used to stall decode for whole-prompt prefills.
Exits with status 2 only on a genuinely unservable request (EngineOOM:
one sequence can never fit the pool).

``--submodels G`` serves G Horn parallel circuits (a ModelBank of fixed
sub-model masks over one shared parent) behind the same engine: requests
are routed per ``--router`` and co-batch across circuits in every tick;
``--ensemble-frac`` of requests instead fan across ALL circuits and
combine logits on device (``--combine``).

``--prefix-cache`` (default on) content-addresses full KV pages so
identical prompt prefixes are prefilled once and adopted (refcounted,
copy-on-write) by later requests; an ensemble's shared prompt context is
prefilled once by its leader and forked into all G members.

``--speculate K`` turns on speculative decoding: a materialized Horn
small circuit (``--draft-circuit`` of the serving bank, or a draft-only
``--draft-keep`` bank when running the dense parent) proposes K tokens
per decode tick in one jitted draft call, and the parent verifies all
K+1 positions inside its one budgeted call — greedy output stays
byte-identical to non-speculative serving, it just lands up to K+1
tokens per tick.

``--kv-dtype int8`` stores the paged KV pools quantized (per-(page,
kv-head) f32 scale sidecars beside the pools, dequantized in-register
inside the kernels): ~2x sequences at equal HBM and fewer pool-pressure
preemptions, with a bounded greedy-decode divergence instead of the f32
path's byte-identity.  ``--pages-per-step N`` makes the paged kernels
fetch N KV pages per grid step (double-buffered page DMAs on TPU) —
bit-identical output for any N; by default the chunk kernel derives its
tiling from the call's shapes.

Observability (``repro.serving.observability``): ``--stats-every N``
prints a periodic stats line off the engine's telemetry snapshot;
``--trace-out trace.json`` records every tick's plan / host-prep /
device-step / commit phases plus one track per slot and writes Chrome
Trace Event JSON (open in https://ui.perfetto.dev); ``--slo-class
name:ttft:latency`` configures per-class SLO targets and reports
attainment at exit.

Continuous perf harness: ``--record-trace trace.jsonl`` writes the
exact request stream this run served (arrival offsets, prompt token
ids, budgets, ensemble decisions) as a versioned JSONL trace;
``--replay trace.jsonl`` re-serves a recorded stream on the
deterministic virtual clock (arrivals at their recorded offsets, each
tick advancing ``--tick-dt`` seconds) — greedy token streams are
byte-identical run-to-run, which is what ``benchmarks/regression.py``
gates on.  Live anomaly alerts (tick-duration spikes, SLO burn rate,
pool leaks, accept-rate collapse, post-warmup recompiles) print in the
exit report and land in the ``--trace-out`` export.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.configs.base import (MAMBA, HornConfig, get_model_config,
                                list_archs, reduced)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.models.params import cast_tree
from repro.serving import Engine, EngineConfig, EngineOOM, ModelBank, Router
from repro.serving.observability import (Telemetry, TraceRecorder,
                                         load_trace, parse_slo_class,
                                         percentile, replay)
from repro.serving.observability.replay import DEFAULT_TICK_DT


def build_draft(cfg, params, bank, *, speculate: int, draft_circuit: int,
                draft_keep: float, mask_block: int, seed: int):
    """The draft circuit for ``--speculate K``: cut from the serving bank
    when one exists (the drafted tokens are verified per-slot under each
    request's own circuit masks, so any bank circuit is a valid proposer),
    else from a throwaway draft-only bank over the same parent weights at
    ``--draft-keep`` (the dense parent is the verifier)."""
    if speculate <= 0:
        return None
    if bank is not None:
        return bank.draft_model(draft_circuit, params)
    horn = HornConfig(enabled=True, keep_hidden=draft_keep, keep_input=1.0,
                      block_size=mask_block)
    dbank = ModelBank(cfg, horn, draft_circuit + 1, seed=seed)
    return dbank.draft_model(draft_circuit, params)


def make_requests(n: int, vocab_size: int, rng: np.random.Generator, *,
                  stream: str = "poisson", rate: float = 16.0,
                  max_prompt: int = 64, gen: int = 16,
                  long_frac: float = 0.0, shared_prefix: int = 0):
    """(arrival_time, prompt, max_new) triples: Poisson arrivals (or all at
    t=0 for ``stream="batch"``), mixed prompt lengths (log-uniform between 4
    and ``max_prompt``), per-request max_new drawn in [gen/2, gen].
    ``long_frac`` of the prompts are pinned at ``max_prompt`` exactly — the
    adversarial long-prompt mix for chunked-prefill benchmarks.
    ``shared_prefix`` prepends one fixed system prompt of that many tokens
    to EVERY request (unique tails keep total length <= max_prompt) — the
    shared-system-prompt mix the prefix cache is built for.  Shared by the
    launcher and benchmarks/serving_bench.py so their loads stay
    comparable."""
    if not 0 <= shared_prefix <= max_prompt - 4:
        raise ValueError(
            f"shared_prefix ({shared_prefix}) must leave >= 4 tokens of "
            f"unique tail under max_prompt ({max_prompt})")
    out, t = [], 0.0
    system = rng.integers(0, vocab_size,
                          (shared_prefix,)).astype(np.int32)
    for _ in range(n):
        if stream == "poisson":
            t += rng.exponential(1.0 / rate)
        room = max_prompt - shared_prefix
        if long_frac > 0 and rng.uniform() < long_frac:
            plen = room
        else:
            lo, hi = np.log(min(4, room)), np.log(room)
            plen = int(np.exp(rng.uniform(lo, hi)))
        tail = rng.integers(0, vocab_size, (max(1, plen),)).astype(np.int32)
        prompt = np.concatenate([system, tail]) if shared_prefix else tail
        g = int(rng.integers(max(1, gen // 2), gen + 1))
        out.append((t, prompt, g))
    return out


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list_archs(),
                    help="model architecture; required unless --replay "
                         "(the trace header records the arch it was "
                         "recorded on)")
    ap.add_argument("--stream", choices=["poisson", "batch"], default="poisson")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=16.0,
                    help="poisson arrival rate (requests/s)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--pages", type=int, default=512)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-prompt", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16,
                    help="max new tokens (per-request draw in [gen/2, gen])")
    ap.add_argument("--budget", type=int, default=256,
                    help="tokens per unified tick (decode + prompt chunks)")
    ap.add_argument("--long-frac", type=float, default=0.0,
                    help="fraction of prompts pinned at --max-prompt")
    ap.add_argument("--policy", choices=["reserve", "on_demand"],
                    default="on_demand")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="content-addressed KV page reuse + COW: identical "
                         "prompt prefixes prefill once, ensembles share "
                         "their prompt pages across all circuits "
                         "(--no-prefix-cache re-prefills per request; "
                         "default: on, off for models with Mamba layers, "
                         "whose per-slot state no page carries)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-dtype", choices=["bfloat16", "float32", "int8"],
                    default="bfloat16",
                    help="paged KV pool dtype.  int8 stores quantized pages "
                         "plus per-(page, kv-head) f32 scale sidecars: "
                         "~2x sequences at equal HBM, bounded-error decode "
                         "(dequantized in-register inside the kernel)")
    ap.add_argument("--pages-per-step", type=int, default=None,
                    help="KV pages fetched per paged-attention grid step "
                         "(default: derived from the call's shapes; output "
                         "is bit-identical for any value)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative decoding: a materialized draft "
                         "circuit proposes K tokens per decode tick, the "
                         "parent verifies all K+1 positions in its one "
                         "budgeted call (0 = off)")
    ap.add_argument("--draft-circuit", type=int, default=0,
                    help="bank circuit the draft is materialized from")
    ap.add_argument("--draft-keep", type=float, default=0.875,
                    help="FFN keep rate of the draft-only bank when "
                         "--submodels 0 (acceptance tracks draft<->parent "
                         "agreement: keep it high for untrained parents, "
                         "Horn-trained circuits accept well lower)")
    ap.add_argument("--submodels", type=int, default=0,
                    help="serve G Horn circuits from one ModelBank "
                         "(0 = single dense parent)")
    ap.add_argument("--router", choices=["least_loaded", "hash"],
                    default="least_loaded")
    ap.add_argument("--ensemble-frac", type=float, default=0.0,
                    help="fraction of requests fanned across ALL circuits "
                         "with on-device logit combining")
    ap.add_argument("--combine", choices=["mean_logit", "majority_vote"],
                    default="mean_logit")
    ap.add_argument("--keep", type=float, default=0.5,
                    help="per-circuit FFN hidden keep rate (paper: 0.5)")
    ap.add_argument("--mask-block", type=int, default=16,
                    help="mask block size in hidden units (reduced configs "
                         "need <= d_ff/4 for distinct circuits)")
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--record-trace", metavar="PATH", default=None,
                    help="write the served request stream (arrivals, "
                         "prompt ids, budgets, ensemble decisions) as a "
                         "versioned JSONL traffic trace")
    ap.add_argument("--replay", metavar="PATH", default=None,
                    help="serve a recorded trace on the deterministic "
                         "virtual clock instead of a synthetic stream "
                         "(--requests/--stream/--rate are ignored)")
    ap.add_argument("--tick-dt", type=float, default=DEFAULT_TICK_DT,
                    help="virtual seconds per tick during --replay "
                         f"(default {DEFAULT_TICK_DT})")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="record the per-tick timeline (plan / host-prep / "
                         "device-step / commit phases + one track per slot) "
                         "and write Chrome Trace Event JSON here — open in "
                         "https://ui.perfetto.dev or chrome://tracing")
    ap.add_argument("--stats-every", type=int, default=0, metavar="TICKS",
                    help="print a periodic stats line every N engine ticks "
                         "(0 = off)")
    ap.add_argument("--slo-class", action="append", default=[],
                    metavar="NAME:TTFT:LAT",
                    help="SLO targets (seconds; '-' leaves a bound unset), "
                         "e.g. 'default:0.5:5'; repeatable.  Launcher "
                         "traffic is scored under class 'default'; "
                         "Engine.submit(slo_class=...) routes other "
                         "classes.  Attainment is reported at exit.")
    ap.add_argument("--sanitize", action="store_true",
                    help="runtime sanitizers (hornlint's dynamic twin): "
                         "jax_debug_nans, strict rank promotion, and "
                         "per-tick pool/block-table invariant checks.  "
                         "Pure-host overhead, excluded from bench gates; "
                         "exits 3 if any invariant alert fires.")
    return ap


def build_engine(args):
    """The model config, ModelBank (or None) and Engine the launcher serves
    with: random weights from ``--seed``, at published widths under
    ``--full-config``.  Raises SystemExit on a config the engine rejects."""
    cfg = get_model_config(args.arch)
    if not args.full_config:
        cfg = reduced(cfg)
    if cfg.family == "mlp":
        raise SystemExit("horn-mnist is a classifier; use launch.train")
    if args.prefix_cache is None:
        args.prefix_cache = MAMBA not in cfg.layer_pattern
    # float32 weights where they and the step's bf16 copy of them (6 bytes
    # a parameter) fit one 16 GB chip; else bf16, the compute dtype
    param_dtype = "float32" if 6 * cfg.param_count() <= 16e9 else "bfloat16"

    ecfg = EngineConfig(
        num_slots=args.slots, num_pages=args.pages, page_size=args.page_size,
        max_prompt_len=-(-args.max_prompt // args.page_size) * args.page_size,
        max_new_tokens=args.gen, token_budget=max(args.budget, args.slots),
        temperature=args.temperature, seed=args.seed, policy=args.policy,
        prefix_cache=args.prefix_cache, speculate_k=args.speculate,
        kv_dtype=args.kv_dtype, pages_per_step=args.pages_per_step)
    sanitizer = None
    if args.sanitize:
        from repro.analysis.sanitize import Sanitizer
        Sanitizer.install_jax_guards()      # before anything jits
        sanitizer = Sanitizer()
    import jax
    # made and cast in one program: a model kept in bf16 never holds its
    # float32 weights whole
    params = jax.jit(lambda k: cast_tree(api.model_init(k, cfg),
                                         param_dtype))(
        jax.random.key(args.seed))
    bank = router = None
    if args.submodels > 0:
        if args.submodels > args.slots and args.ensemble_frac > 0:
            raise SystemExit("ensemble mode needs --slots >= --submodels")
        horn = HornConfig(enabled=True, keep_hidden=args.keep,
                          keep_input=1.0, block_size=args.mask_block)
        bank = ModelBank(cfg, horn, args.submodels, seed=args.seed)
        router = Router(args.submodels, policy=args.router)
    try:
        telemetry = Telemetry(
            timeline=args.trace_out is not None,
            slo_classes=[parse_slo_class(s) for s in args.slo_class])
        draft = build_draft(cfg, params, bank, speculate=args.speculate,
                            draft_circuit=args.draft_circuit,
                            draft_keep=args.draft_keep,
                            mask_block=args.mask_block, seed=args.seed)
        engine = Engine(cfg, params, ecfg, bank=bank, router=router,
                        draft=draft, telemetry=telemetry)
    except ValueError as e:
        raise SystemExit(f"{args.arch}: {e}")
    if sanitizer is not None:
        sanitizer.attach(engine)
    return cfg, bank, engine


def main() -> None:
    ap = make_parser()
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch is None:
        if not args.replay:
            ap.error("--arch is required (unless --replay)")
        args.arch = load_trace(args.replay)[1].get("arch")
        if args.arch is None:
            ap.error(f"--arch: {args.replay} records no arch in its "
                     f"header meta; pass --arch explicitly")

    cfg, bank, engine = build_engine(args)

    if args.replay:
        records, meta = load_trace(args.replay)
        if meta.get("arch") not in (None, args.arch):
            print(f"WARNING: trace was recorded on arch "
                  f"{meta['arch']!r}, replaying on {args.arch!r}",
                  file=sys.stderr)
        print(f"replaying {len(records)} requests from {args.replay} "
              f"(virtual clock, {args.tick_dt * 1e3:g}ms/tick)")
        try:
            result = replay(engine, records, tick_dt=args.tick_dt)
        except EngineOOM as e:
            print(f"FATAL: unservable request — {e}", file=sys.stderr)
            sys.exit(2)
        s = result.summary()
        wall = sum(result.tick_wall_s)
        print(f"\n{result.requests} requests in {result.virtual_s:.2f} "
              f"virtual s ({wall:.2f}s host compute, "
              f"{result.ticks} ticks)")
        print(f"throughput: {s['decode_tok_s_p10'] or 0:.1f} tok/s "
              f"(pooled-p10 tick estimate)  "
              f"{result.generated_tokens} tokens  "
              f"digest {result.token_digest[:16]}")
        print(f"TTFT    p50 {s['ttft_p50_s']:.3f}s  "
              f"p99 {s['ttft_p99_s']:.3f}s  (virtual clock)")
        print(f"latency p50 {s['latency_p50_s']:.3f}s  "
              f"p99 {s['latency_p99_s']:.3f}s")
        _tail_report(engine, args, bank, wall)
        _exit_sanitize(engine)
        return

    rng = np.random.default_rng(args.seed)
    recorder = TraceRecorder(meta={
        "arch": args.arch, "seed": args.seed, "stream": args.stream,
        "rate": args.rate, "max_prompt": args.max_prompt,
        "gen": args.gen, "long_frac": args.long_frac,
        **engine.obs.engine_config,
    }) if args.record_trace else None
    pending = make_requests(args.requests, cfg.vocab_size, rng,
                            stream=args.stream, rate=args.rate,
                            max_prompt=args.max_prompt, gen=args.gen,
                            long_frac=args.long_frac)
    sub = f", {args.submodels} submodels ({args.router} routing, " \
          f"{args.ensemble_frac:.0%} ensemble)" if bank else ""
    print(f"serving {args.requests} requests ({args.stream} stream, "
          f"{args.slots} slots, {args.pages}x{args.page_size}-token pages, "
          f"budget {engine.ecfg.token_budget} tok/tick, "
          f"policy={args.policy}{sub})")

    t0 = time.monotonic()
    max_running = 0
    expected = 0
    next_stats = args.stats_every

    def stats_line() -> str:
        """One compact periodic line off the telemetry snapshot."""
        m = engine.metrics()
        c, tick = m["counters"], m["tick"]["tick_s"]
        wall = max(time.monotonic() - t0, 1e-9)
        hr = m["derived"]["prefix_hit_rate"]
        return (f"  [tick {c['steps']}] "
                f"{c['generated_tokens'] / wall:6.1f} tok/s  "
                f"run {len(engine.sched.running)}/{args.slots}  "
                f"wait {len(engine.sched.waiting)}  "
                f"pool {m['pool']['utilization']:.0%}  "
                f"tick p50 {(tick['p50'] or 0) * 1e3:.1f}ms  "
                f"hit {'n/a' if hr is None else format(hr, '.0%')}  "
                f"preempt {m['derived']['preemptions']}")

    try:
        while pending or engine.sched.has_work():
            now = time.monotonic() - t0
            while pending and pending[0][0] <= now:
                at, prompt, gen = pending.pop(0)
                ens = args.combine if bank is not None \
                    and rng.uniform() < args.ensemble_frac else None
                if recorder is not None:
                    # record the RESOLVED ensemble decision so replay
                    # does not depend on this loop's RNG state
                    recorder.add(at, prompt, gen, ensemble=ens)
                try:
                    engine.submit(prompt, gen, arrival_time=at, ensemble=ens)
                except ValueError as e:
                    print(f"FATAL: infeasible request — {e}", file=sys.stderr)
                    sys.exit(2)
                expected += args.submodels if ens else 1
            if not engine.sched.has_work():
                time.sleep(min(0.005, max(0.0, pending[0][0] - now)))
                continue
            for req in engine.step(time.monotonic() - t0,
                                   tick_clock=lambda: time.monotonic() - t0):
                pre = f"  ({req.num_preemptions}x preempted)" \
                    if req.num_preemptions else ""
                tag = f"  sub {req.submodel_id}" if bank else ""
                if req.group is not None:
                    tag = f"  ens {req.group.id}/{req.group.combine}" \
                          f" sub {req.submodel_id}"
                print(f"  req {req.id:3d} done: prompt {req.prompt_len:3d} "
                      f"+{len(req.out_tokens):3d} tok  "
                      f"ttft {req.t_first_token - req.arrival_time:6.3f}s  "
                      f"latency {req.t_done - req.arrival_time:6.3f}s"
                      f"{tag}{pre}")
            max_running = max(max_running, len(engine.sched.running))
            if args.stats_every and engine.steps >= next_stats:
                print(stats_line())
                next_stats = engine.steps + args.stats_every
    except EngineOOM as e:
        print(f"FATAL: unservable request — {e}", file=sys.stderr)
        sys.exit(2)
    wall = time.monotonic() - t0
    if recorder is not None:
        n = recorder.save(args.record_trace)
        print(f"recorded {n} requests -> {args.record_trace}")

    expected = expected if bank else args.requests
    assert len(engine.sched.finished) == expected, \
        (len(engine.sched.finished), expected)
    # an ensemble group delivers ONE stream: count it once (its leader) in
    # user-facing latency/throughput; device throughput counts members
    done = engine.finished_streams()
    ttft = [r.t_first_token - r.arrival_time for r in done]
    lat = [r.t_done - r.arrival_time for r in done]
    total_new = sum(len(r.out_tokens) for r in done)
    dev_new = sum(len(r.out_tokens) for r in engine.sched.finished)
    dev = f" ({dev_new / max(wall, 1e-9):.1f} device tok/s incl. ensemble " \
          f"members)" if dev_new != total_new else ""
    print(f"\n{len(done)} requests ({expected} sequences) in {wall:.2f}s  "
          f"(max {max_running}/{args.slots} slots concurrent)")
    print(f"throughput: {total_new / max(wall, 1e-9):.1f} tok/s{dev} "
          f"({engine.steps} ticks, "
          f"{engine.generated_tokens / max(engine.steps, 1):.1f} tok/tick, "
          f"{engine.prefill_tokens} prefill tok)")
    print(f"TTFT    p50 {percentile(ttft, 50):.3f}s  "
          f"p99 {percentile(ttft, 99):.3f}s")
    print(f"latency p50 {percentile(lat, 50):.3f}s  "
          f"p99 {percentile(lat, 99):.3f}s")
    _tail_report(engine, args, bank, wall)
    _exit_sanitize(engine)


def _exit_sanitize(engine) -> None:
    """Sanitizer verdict last, after every report section: a replay that
    served every token can still have leaked pages on the way."""
    san = getattr(engine, "_sanitizer", None)
    if san is None:
        return
    print(san.render_report())
    if san.alerts:
        sys.exit(3)


def _tail_report(engine, args, bank, wall: float) -> None:
    """Exit-report sections shared by the live and replay drive loops:
    pool / prefix-cache / speculative / bank / SLO state, anomaly
    alerts, compile attribution, and the trace export."""
    print(f"page-pool peak utilization: {engine.peak_utilization:.0%}  "
          f"preemptions: {engine.preemptions}  "
          f"block-table rows synced/tick: "
          f"{engine.bt_rows_synced / max(engine.steps, 1):.2f}")
    if args.prefix_cache:
        hr = engine.prefix_hit_rate     # None when nothing was eligible
        print(f"prefix cache: hit rate "
              f"{'n/a' if hr is None else format(hr, '.0%')}  "
              f"prefill tok saved {engine.prefill_tok_saved}  "
              f"evictions {engine.cache_evictions}  "
              f"COW copies {engine.cow_page_copies}")
    if args.speculate:
        print(f"speculative: accept rate {engine.accept_rate:.0%}  "
              f"accepted tok/tick {engine.accepted_tok_per_tick:.2f}  "
              f"drafted {engine.spec_drafted}  "
              f"draft calls {engine.spec.draft_calls}  "
              f"(K={args.speculate}, circuit {engine.spec.draft.circuit}, "
              f"kept {engine.spec.draft.kept_frac:.0%})")
    if bank is not None:
        per = "  ".join(
            f"sub{g}: {engine.tokens_by_submodel.get(g, 0) / max(wall, 1e-9):6.1f} tok/s"
            f" (peak util {engine.peak_util_by_submodel.get(g, 0.0):.0%})"
            for g in range(args.submodels))
        print(f"co-batch ratio: {engine.cobatch_ratio:.0%}  {per}")
    if args.slo_class:
        for name, rep in engine.obs.slo.report().items():
            att = rep["attainment"]
            tt = rep["ttft_target_s"]
            lt = rep["latency_target_s"]
            print(f"SLO [{name}] attainment "
                  f"{'n/a' if att is None else format(att, '.0%')} "
                  f"({rep['met']}/{rep['finished']}; targets "
                  f"ttft {'-' if tt is None else f'{tt:g}s'} "
                  f"latency {'-' if lt is None else f'{lt:g}s'}; "
                  f"violations ttft {rep['ttft_violations']} "
                  f"latency {rep['latency_violations']})")
    prof = engine.obs.profiler
    if prof is not None and prof.compiles_post_warm:
        print(f"compiles: {prof.compiles_post_warm} post-warmup "
              f"(of {prof.compiles_total} observed) — late jit compiles "
              f"are a perf regression signal")
    mon = engine.obs.anomaly
    if mon is not None and mon.counts:
        print("alerts: " + "  ".join(f"{k} x{n}"
                                     for k, n in sorted(mon.counts.items())))
        for a in list(mon.alerts)[-5:]:
            print(f"  [{a.kind}] tick {a.tick} t={a.t:.2f}s: {a.message}")
    else:
        print("alerts: none")
    if args.trace_out:
        n = engine.obs.timeline.export(args.trace_out)
        print(f"trace: {n} events over {engine.obs.timeline.ticks} ticks "
              f"-> {args.trace_out} (open in https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
