"""Serving cells: the program's continuous-batching engine under traffic.

The engine is the program's ``Engine`` over the unified paged step, built
with the configuration's settings and the traffic's length limits, and
handed the benchmark's weights (float32 masters; the step computes in
bf16).  Set-up warms the step's chunk buckets C = 1, 2, 4, ..., up to the
largest chunk the traffic can make (non-ensemble variant only) and the
block-table row scatter for every row count, then the window runs a
closed loop: every slot busy, from first requests caught part-way through
their outputs (``traffic.closed_loop``); the window opens once every slot
decodes, and a finished request is replaced from the seeded queue.

Once the window has closed and the engine is freed, a sample of finished
requests drawn from the seed, the longest among them, is run through the
plain reference: the number compared is the widest gap by which a served
token's logit lies below the reference's best at its position.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from harness import common, counts, reference as R, tracing
from harness import traffic as TR
from harness import weights as W

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


def build(cell, seed: int, timeline: bool, fault: str = ""):
    import jax
    from repro.models import api
    from repro.serving import Engine
    from repro.serving.observability import Telemetry

    m = cell.config["model"]
    _, jseed = TR.seeded(seed)
    cfg = common.program_config(cell.config["program"]["arch"], m)
    W.check_layout(jax.eval_shape(lambda: api.model_init(
        jax.random.key(0), cfg)), m)
    wkey = W.key(jseed)
    # the key goes in as an argument: every seed finds the program cached
    params = jax.jit(lambda k: W.to_program(W.make(k, m)))(wkey)
    engine = Engine(cfg, params, engine_config(cell, jseed),
                    telemetry=Telemetry(timeline=timeline))
    ticks = []
    step = engine._step

    def recorded(*a, **kw):
        # (when, C, starts, chunk_lens) of every tick; the device arrays
        # are read back after the run
        ticks.append((time.monotonic(), a[2].shape[1], a[3], a[4]))
        out = step(*a, **kw)
        if fault == "token" and len(ticks) % 10 == 0:
            # a planted fault: every slot's token of every tenth tick is
            # altered where it is produced
            out = ((out[0] + 1) % m["vocab_size"],) + tuple(out[1:])
        return out

    engine._step = recorded
    return engine, ticks, wkey


def warm(engine, rng, vocab: int) -> None:
    """Compile (or load from the cache) every chunk bucket the traffic
    can reach, and the block-table scatter for every dirty-row count."""
    import jax.numpy as jnp
    C = 1
    while C <= engine.max_chunk:
        if C > 1:
            engine.submit(rng.integers(0, vocab, C).astype(np.int32), 2)
            engine.run()
        C *= 2
    bt = engine._bt
    for n in range(1, engine.ecfg.num_slots + 1):
        idx = np.arange(n, dtype=np.int32)
        bt.dev.at[jnp.asarray(idx)].set(jnp.asarray(bt.host[idx])) \
            .block_until_ready()
    engine.reset_stats()


class Clock:
    def __init__(self):
        self.epoch = time.monotonic()

    def __call__(self) -> float:
        return time.monotonic() - self.epoch


def run(cell, args, devices, t_start: float, fault: str = "") -> dict:
    import jax
    c, mix = cell.config, cell.traffic
    m = c["model"]
    counter = common.CompileCounter()
    rng, _ = TR.seeded(args.seed)
    engine, ticks, wkey = build(cell, args.seed, bool(args.trace), fault)
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
            with jax.profiler.TraceAnnotation("bench:tick"):
                engine.step(clk(), tick_clock=clk)
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
    tick_phases = None
    if engine.obs.timeline is not None:
        from repro.serving.observability.trace import TICK_PHASES
        tick_phases = [(n, a, b) for n, tid, a, b, _ in
                       engine.obs.timeline._spans
                       if tid == 0 and n in TICK_PHASES]
    recs = list(sink.values()) + [rec for _, rec in live.values()]
    tick_rows = [(t, C, np.asarray(s), np.asarray(n))
                 for t, C, s, n in ticks]
    ecfg = engine.ecfg
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
           "window_compiles": counter.count,
           "served_sample": [(len(r["prompt"]), len(r["out"]))
                             for r in sample]}
    reduced = None
    if tr is not None:
        marks = [(n, a, b) for n, a, b in (tick_phases or [])]
        host = [h for h in tr.host if h[0] == "bench:tick"]
        tr.host += tracing.align(marks, [(h[1], pc) for h, pc in
                                         zip(sorted(host, key=lambda h: h[1]),
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


def outcome(obs: dict, mix: dict):
    """(requests attempted, failed): those that produced tokens in the
    window; a closed loop refuses none."""
    w0, w1 = obs["window"]
    return sum(1 for r in obs["requests"]
               if any(w0 <= t <= w1 for t in r["times"])), 0


def pick_sample(done, rng, target_tokens: int, target_requests: int):
    """Finished requests drawn from the seed, the longest first, until
    they hold ``target_tokens`` served tokens and number at least
    ``target_requests``."""
    if not done:
        return []
    order = sorted(range(len(done)), key=lambda i: -len(done[i]["out"]))
    rest = list(rng.permutation(order[1:])) if len(order) > 1 else []
    pick, n = [], 0
    for i in [order[0]] + rest:
        pick.append(done[i])
        n += len(done[i]["out"])
        if n >= target_tokens and len(pick) >= target_requests:
            break
    return pick


def served_gaps(cell, wkey, sample, longest: int,
                control: bool = False) -> dict:
    """The widest gap of a served token below the reference's best logit
    (and, with ``control``, of the fp8 control's own first choice)."""
    import jax
    m = cell.config["model"]
    if not sample:
        return {"program": math.inf}
    w = jax.jit(lambda k: W.make(k, m))(wkey)
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


def traced_chunks(obs: dict):
    """(start, length) of each live slot's chunk, per traced tick."""
    rows = [obs["ticks"][i] for i in obs.get("traced_ticks", [])]
    return [[(int(s), int(n)) for s, n in zip(st, cl) if n > 0]
            for _, _, st, cl in rows]


def tick_flops(m: dict, obs: dict) -> float:
    return sum(counts.serve_tick_flops(m, ch) for ch in traced_chunks(obs))
