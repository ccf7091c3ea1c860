"""Hybrid serving cells: the program's engine serving a Mamba-2/attention
hybrid (granite-4.0-h-micro) under traffic.

The engine is the program's ``Engine`` over the unified paged step, built
with the configuration's settings and the traffic's length limits, and
handed the benchmark's weights (``harness/weights_hybrid.py``, bf16):
the Mamba layers keep a per-slot state beside the attention layers' page
pool.  Set-up and the window are ``serve.py``'s: every chunk bucket
warmed, then a closed loop with every slot busy, opened once every slot
decodes.

The traced run keeps the program's tick records (phases and work counts:
``prefill_tokens``, ``ssm_state_bytes``, ``ssm_tokens``) for the readers.
Once the window has closed and the engine is freed, a sample of finished
requests drawn from the seed, the longest among them, is run through the
plain reference (``harness/reference_hybrid.py``): the number compared is
the widest gap by which a served token's logit lies below the reference's
best at its position.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

import numpy as np

from harness import common, tracing
from harness import reference_hybrid as R
from harness import traffic as TR
from harness import weights_hybrid as WH
from harness.serve import Clock, outcome, pick_sample, traced_chunks, warm

# the program's setting for each of the file's keys that are not sizes
SETTINGS = {"rms_norm_eps": "norm_eps", "attention_multiplier": "query_scale",
            "embedding_multiplier": "embedding_multiplier",
            "residual_multiplier": "residual_multiplier",
            "logits_scaling": "logits_scaling",
            "mamba_conv_bias": "ssm_conv_bias_bc",
            "tie_word_embeddings": "tie_embeddings", "hidden_act": "act",
            "attention_bias": "qkv_bias",
            "mamba_expand": "ssm_expand", "mamba_chunk_size": "ssm_chunk"}


def program_config(arch: str, m: dict):
    """The program's registered configuration for ``arch`` at the sizes
    the benchmark's file states; refuses an architecture whose other
    settings, or whose layer kinds, differ from the file's."""
    from repro.configs.base import ATTN, MAMBA, get_model_config
    k = WH.dims(m)
    per = WH._period(m)
    cfg = dataclasses.replace(
        get_model_config(arch), num_layers=len(WH.kinds(m)),
        d_model=k["d"], num_heads=k["h"], num_kv_heads=k["kh"],
        head_dim=k["hd"], d_ff=k["ff"], vocab_size=k["V"],
        ssm_state=k["N"], ssm_head_dim=k["P"], ssm_conv_width=k["W"],
        layer_pattern=tuple(MAMBA if t == "mamba" else ATTN for t in per))
    bad = {f: (m[f], getattr(cfg, p)) for f, p in SETTINGS.items()
           if m[f] != getattr(cfg, p)}
    if cfg.use_rope != (m["position_embedding_type"] != "nope") \
            or cfg.ssm_expand * k["d"] != k["d_in"] \
            or m["num_local_experts"] or m["mamba_n_groups"] != 1 \
            or m["mamba_proj_bias"] or not cfg.mlp_gated:
        bad["mixers"] = "rope, expand, groups, biases or experts"
    if bad:
        raise SystemExit(f"the program's {arch} differs from the "
                         f"benchmark's configuration (file, program): {bad}")
    return cfg


def engine_config(cell, jseed: int):
    from repro.serving import EngineConfig
    c, mix = cell.config, cell.traffic
    e = c["engine"]
    ps = e["page_size"]
    return EngineConfig(
        num_slots=e["slots"], num_pages=e["pages"], page_size=ps,
        max_prompt_len=-(-TR.longest_prompt(mix) // ps) * ps,
        max_new_tokens=mix["output"]["max"], token_budget=e["token_budget"],
        temperature=0.0, seed=jseed, policy=e["policy"],
        prefix_cache=e["prefix_cache"], kv_dtype=e["kv_dtype"],
        compute_dtype=c["program"]["compute_dtype"])


def build(cell, seed: int, timeline: bool, fault: str = "", keep=None):
    """The engine over the benchmark's weights, its step wrapped to record
    (when, C, starts, chunk_lens) of every tick.  With ``keep`` (a
    one-item list the caller sets around traced ticks) the per-slot
    device arrays are kept only where ``keep[0]`` holds: the readers need
    them for the traced ticks alone, and kept for every tick they pin two
    more device allocations a tick for the whole run."""
    import jax
    from repro.models import api
    from repro.serving import Engine
    from repro.serving.observability import Telemetry

    m = cell.config["model"]
    _, jseed = TR.seeded(seed)
    cfg = program_config(cell.config["program"]["arch"], m)
    WH.check_layout(jax.eval_shape(lambda: api.model_init(
        jax.random.key(0), cfg)), m)
    wkey = WH.key(jseed)
    # the key goes in as an argument: every seed finds the program cached
    params = jax.jit(lambda k: WH.to_program(WH.make(k, m), m))(wkey)
    engine = Engine(cfg, params, engine_config(cell, jseed),
                    telemetry=Telemetry(timeline=timeline))
    ticks = []
    step = engine._step

    def recorded(*a, **kw):
        # (when, C, starts, chunk_lens) of every tick; the device arrays
        # are read back after the run
        held = keep is None or keep[0]
        ticks.append((time.monotonic(), a[2].shape[1],
                      a[3] if held else None, a[4] if held else None))
        out = step(*a, **kw)
        if fault == "token" and len(ticks) % 10 == 0:
            # a planted fault: every slot's token of every tenth tick is
            # altered where it is produced
            out = ((out[0] + 1) % m["vocab_size"],) + tuple(out[1:])
        return out

    engine._step = recorded
    return engine, ticks, wkey


def run(cell, args, devices, t_start: float, fault: str = "") -> dict:
    import jax
    c, mix = cell.config, cell.traffic
    m = c["model"]
    counter = common.CompileCounter()
    rng, _ = TR.seeded(args.seed)
    keep = [False]
    engine, ticks, wkey = build(cell, args.seed, bool(args.trace), fault,
                                keep)
    warm(engine, np.random.default_rng(0), m["vocab_size"])

    live = {}                # request id -> (request, its record)
    sink = {}                # request id -> record, once finished

    def note_tokens(now: float) -> None:
        for rid, (req, rec) in list(live.items()):
            k = len(req.out_tokens)
            if k > len(rec["times"]):
                rec["times"] += [now] * (k - len(rec["times"]))
            if req.finished:
                rec["out"] = np.asarray(req.out_tokens, np.int32)
                sink[rid] = rec
                del live[rid]

    def submit(r: TR.Req, now: float) -> None:
        req = engine.submit(r.prompt, r.max_new, arrival_time=now)
        live[req.id] = (req, {"prompt": r.prompt, "times": [],
                              "max_new": r.max_new})

    anchors, traced_ticks = [], []
    tc = c["trace"]

    def tick(clk, traced: bool) -> None:
        if traced:
            pc = time.perf_counter()
            keep[0] = True
            with jax.profiler.TraceAnnotation("bench:tick"):
                engine.step(clk(), tick_clock=clk)
            keep[0] = False
            anchors.append(pc)
            traced_ticks.append(len(ticks) - 1)
        else:
            engine.step(clk(), tick_clock=clk)

    with common.traced(bool(args.trace)) as cap:
        first, queue = TR.closed_loop(mix, args.seed, m["vocab_size"])
        clk = Clock()
        q = 0

        def refill() -> None:
            nonlocal q
            while len(live) < mix["slots"]:
                if q == len(queue):
                    raise RuntimeError("the mix's queue ran dry: raise its "
                                       "'queue'")
                submit(queue[q], clk())
                q += 1

        for r in first:
            submit(r, 0.0)
        for _ in range(c["ramp_ticks"]):
            if all(req.out_tokens for req, _ in live.values()):
                break
            engine.step(clk(), tick_clock=clk)
            note_tokens(clk())
            refill()
        ws = clk()
        w0 = time.monotonic()
        counter.armed = True
        while clk() - ws < args.seconds:
            now = clk() - ws
            in_trace = args.trace and tc["after_s"] <= now \
                < tc["after_s"] + tc["seconds"]
            if in_trace:
                cap["start"]()
            elif args.trace and now >= tc["after_s"] + tc["seconds"]:
                cap["stop"]()
            tick(clk, in_trace)
            with jax.profiler.TraceAnnotation("bench:harness"):
                note_tokens(clk())
                refill()
        window = (ws, clk())
        counter.armed = False
        cap["stop"]()

    from harness.device import memory_peak_bytes
    peak = memory_peak_bytes(devices)
    setup_s = w0 - t_start
    tick_phases = tick_records = None
    if engine.obs.timeline is not None:
        from repro.serving.observability.trace import TICK_PHASES
        tl = engine.obs.timeline
        tick_phases = [(n, a, b) for n, tid, a, b, _ in tl._spans
                       if tid == 0 and n in TICK_PHASES]
        tick_records = [(t0, t1, dict(counts)) for _, t0, t1, _, counts
                        in tl.tick_records()]
    recs = list(sink.values()) + [rec for _, rec in live.values()]
    tick_rows = [(t, C, None if s is None else np.asarray(s),
                  None if n is None else np.asarray(n))
                 for t, C, s, n in ticks]
    ecfg = engine.ecfg
    stats = engine.stats.as_dict()
    for x in jax.tree.leaves((engine.params, engine.cache)):
        x.delete()
    del engine
    gc.collect()

    tr = common.load_trace(cap)
    sample = pick_sample(list(sink.values()), rng,
                         c["checks"]["served_tokens"],
                         c["checks"]["served_requests"])
    gaps = served_gaps(cell, wkey, sample, TR.longest_context(mix))
    checks, ok = common.limit_checks({"served_logit_gap": gaps["program"]},
                                     c["checks"]["limits"])
    obs = {"window": window, "window_s": window[1] - window[0],
           "window_mono": (w0, w0 + window[1] - window[0]),
           "requests": recs, "ticks": tick_rows,
           "num_slots": ecfg.num_slots, "page_size": ecfg.page_size,
           "window_compiles": counter.count, "engine_stats": stats,
           "served_sample": [(len(r["prompt"]), len(r["out"]))
                             for r in sample]}
    if tick_records is not None:
        obs["tick_records"] = tick_records
    reduced = None
    if tr is not None:
        host = [h for h in tr.host if h[0] == "bench:tick"]
        tr.host += tracing.align(tick_phases or [], [
            (h[1], pc) for h, pc in zip(sorted(host, key=lambda h: h[1]),
                                        anchors)])
        reduced = tracing.reduce(tr)
        obs.update(trace=tr, reduced=reduced, traced_ticks=traced_ticks,
                   tick_phases=tick_phases)
    elif tick_phases is not None:
        obs["tick_phases"] = tick_phases
    attempted, failed = outcome(obs, mix)
    return {"setup_s": setup_s, "attempted": attempted, "failed": failed,
            "peak_bytes": peak, "obs": obs, "checks": checks, "correct": ok,
            "reduced": reduced, "sample": sample, "wkey": wkey}


def served_gaps(cell, wkey, sample, longest: int,
                control: bool = False) -> dict:
    """The widest gap of a served token below the reference's best logit
    (and, with ``control``, of the fp8 control's own first choice)."""
    import jax
    m = cell.config["model"]
    if not sample:
        return {"program": math.inf, "control": math.inf}
    w = jax.jit(lambda k: WH.make(k, m))(wkey)
    pad = -(-longest // 128) * 128
    prog, ctl = [], []
    for r in sample:
        prog.append(float(np.max(R.served_gaps(w, m, r["prompt"], r["out"],
                                               pad))))
        if control:
            picks = R.control_picks(w, m, r["prompt"], r["out"], pad)
            ctl.append(float(np.max(R.served_gaps(
                w, m, r["prompt"], r["out"], pad, picks=picks))))
    out = {"program": max(prog)}
    if control:
        out["control"] = max(ctl)
    del w
    return out


def tick_flops(m: dict, obs: dict) -> float:
    from harness import counts_hybrid
    return sum(counts_hybrid.serve_tick_flops(m, ch)
               for ch in traced_chunks(obs))
