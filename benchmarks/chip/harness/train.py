"""Training cells: the program's compiled Horn train step, closed loop.

Set-up builds one object -- the step compiled by ``make_train_step`` with
its state (the benchmark's weights, AdamW's zero moments) -- and drives it
through three steps on distinct staged batches, reading what the checks
compare: each step's loss, the first gradient as AdamW received it (its
first moment after one step, over 1 - beta1) and, after three steps, each
parameter's change.  The same object then runs the window: one step per
staged batch, up to ``AHEAD`` steps dispatched before the oldest is
waited for.  Once the window has closed and the state is freed, the plain
reference (``harness/reference.py``) repeats the three steps in float32.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Dict

from harness import common, counts, reference as R, tracing
from harness import weights as W
from harness.traffic import seeded

# Steps dispatched ahead of the one waited for: a few seconds of work, so
# that a stall of the host does not leave the chip idle.
AHEAD = 16


def build(cell, seed: int):
    """The program's run configuration, compiled step and state, on a
    data-parallel mesh over the cell's chips."""
    import dataclasses

    import jax
    from repro.configs.base import HornConfig, RunConfig, ShapeConfig
    from repro.core import steps as S
    from repro.launch.mesh import make_test_mesh

    c, mix = cell.config, cell.traffic
    m, horn, opt = c["model"], c["horn"], c["optimizer"]
    _, jseed = seeded(seed)
    cfg = common.program_config(c["program"]["arch"], m)
    run = RunConfig(
        model=cfg, shape=ShapeConfig("bench", "train", mix["seq_len"],
                                     mix["batch"]),
        horn=HornConfig(enabled=True, num_groups=horn["groups"],
                        keep_input=horn["keep_input"],
                        keep_hidden=horn["keep_hidden"],
                        block_size=horn["block"],
                        seed_salt=horn["mask_rule"]["salt"]),
        optimizer="adamw", learning_rate=opt["lr"],
        weight_decay=opt["weight_decay"], remat=c["program"]["remat"],
        param_dtype=c["program"]["param_dtype"],
        compute_dtype=c["program"]["compute_dtype"], seed=jseed)
    mesh = make_test_mesh(cell.chips, 1)
    step_fn, sh = S.make_train_step(run, mesh)
    W.check_layout(jax.eval_shape(lambda: S.init_state(
        jax.random.key(0), run))["params"], m)
    wkey = W.key(jseed)
    # keys and seeds go in as arguments, not as constants of the program,
    # so that every seed finds the same compiled programs in the cache
    unseeded = dataclasses.replace(run, seed=0)

    def init(k, wk, rng):
        st = S.init_state(k, unseeded)
        st["params"] = W.to_program(W.make(wk, m))
        st["rng"] = rng
        return st

    state = jax.jit(init, out_shardings=sh["state"])(
        jax.random.key(0), wkey, jax.random.key_data(jax.random.key(jseed)))
    return run, step_fn, sh, state, jseed, wkey


def stage_batches(mix: dict, vocab: int, jseed: int, shardings):
    """``staged_batches`` distinct token batches made on the device in one
    call: uniform ids, labels the next token."""
    import jax
    n, B, T = mix["staged_batches"], mix["batch"], mix["seq_len"]

    def make(k):
        t = jax.random.randint(k, (n, B, T + 1), 0, vocab)
        return [{"tokens": t[i, :, :-1], "labels": t[i, :, 1:]}
                for i in range(n)]

    return jax.jit(make, out_shardings=[shardings] * n)(
        jax.random.fold_in(jax.random.key(jseed), 2))


def leaf_norms(tree_of: Dict[str, object]):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda t: {n: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for n, x in t.items()})(tree_of)


def program_readings(compiled, state, batches, m, wkey, beta1: float,
                     fault: str = ""):
    """Three steps through the window's own call; returns the state and
    the readings.  ``fault`` plants a fault for the harness's tests."""
    import jax
    import jax.numpy as jnp
    losses = []
    grad = None
    for i in range(3):
        if fault == "frozen":
            # a planted fault: the step returns its state unchanged
            copy = jax.tree.map(jnp.copy, state)
            met = {"loss": compiled(copy, batches[i])[1]["loss"]}
        else:
            state, met = compiled(state, batches[i])
        losses.append(float(met["loss"]))
        if i == 0:
            g = leaf_norms({n: W.program_leaf(state["opt"]["m"], n)
                            for n in W.LEAVES}) \
                if fault != "frozen" else None
            grad = {n: float(v) / (1 - beta1) for n, v in g.items()} \
                if g is not None else {n: 0.0 for n in W.LEAVES}

    def delta(p, wk):
        p0 = W.to_program(W.make(wk, m))
        return {n: jnp.sqrt(jnp.sum(jnp.square(
            W.program_leaf(p, n) - W.program_leaf(p0, n))))
            for n in W.LEAVES}

    delta = jax.jit(delta)(state["params"], wkey)
    change = {n: float(v) for n, v in delta.items()}
    return state, {"loss": losses, "grad": grad, "change": change}


def reference_readings(cell, jseed: int, wkey, batches, precision="f32",
                       rows=None):
    """The reference's three steps on the same batches (device arrays of
    token ids) and masks."""
    import jax
    import jax.numpy as jnp
    c = cell.config
    m, horn, opt = c["model"], c["horn"], dict(c["optimizer"])

    def one(w, mo, vo, tokens, labels, step, seed):
        im, fm = R.horn_masks(seed, step, m, horn)
        loss, g = jax.value_and_grad(R.train_loss)(
            w, tokens, labels, im, fm, m, precision, rows)
        g = R.clip(g, opt["clip_norm"])
        gn = {n: jnp.sqrt(jnp.sum(g[n] * g[n])) for n in g}
        w, mo, vo = R.adamw(w, g, mo, vo, (step + 1).astype(jnp.float32),
                            opt)
        return w, mo, vo, loss, gn

    step = jax.jit(one, donate_argnums=(0, 1, 2))
    w, mo, vo = jax.jit(lambda k: (lambda w: (
        w, jax.tree.map(jnp.zeros_like, w), jax.tree.map(jnp.zeros_like, w)))(
        W.make(k, m)))(wkey)
    losses, grad = [], None
    for i in range(3):
        b = batches[i]
        w, mo, vo, loss, gn = step(w, mo, vo, b["tokens"], b["labels"],
                                   jnp.int32(i), jnp.int32(jseed))
        losses.append(float(loss))
        if i == 0:
            grad = {n: float(v) for n, v in gn.items()}
    del mo, vo

    def delta(w, wk):
        w0 = W.make(wk, m)
        return {n: jnp.sqrt(jnp.sum(jnp.square(w[n] - w0[n]))) for n in w}

    change = jax.jit(delta)(w, wkey)
    return {"loss": losses, "grad": grad,
            "change": {n: float(v) for n, v in change.items()}}


def compare(prog: dict, ref: dict, skip_rel: float) -> Dict[str, float]:
    """The three numbers the checks hold to their limits."""
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"],
                                                  ref["loss"]))
    grad = common.worst_leaf_gap(prog["grad"], ref["grad"])
    med = sorted(ref["grad"].values())[len(ref["grad"]) // 2]
    still = [n for n, v in ref["grad"].items() if v < skip_rel * med]
    change = common.worst_leaf_gap(prog["change"], ref["change"], still)
    return {"loss_rel_gap": loss, "grad_norm_gap": grad,
            "change_norm_gap": change}


def run(cell, args, devices, t_start: float, fault: str = "") -> dict:
    import jax
    c, mix = cell.config, cell.traffic
    m = c["model"]
    counter = common.CompileCounter()
    _, step_fn, sh, state, jseed, wkey = build(cell, args.seed)
    batches = stage_batches(mix, m["vocab_size"], jseed, sh["batch"])
    if fault == "half_batch":
        # the step sees only the first half of each batch's rows
        import jax.numpy as jnp
        half = mix["batch"] // 2
        batches = [{k: jnp.concatenate([v[:half], v[:half]]) for k, v in
                    b.items()} for b in batches]
    compiled = step_fn.lower(state, batches[0]).compile()
    state, prog = program_readings(compiled, state, batches, m, wkey,
                                   c["optimizer"]["b1"], fault)
    jax.block_until_ready(state)

    tokens_per_step = mix["batch"] * mix["seq_len"]
    trace_steps = c["trace_steps"]
    traced_from = None
    with common.traced(bool(args.trace)) as cap:
        counter.armed = True
        t0 = time.monotonic()
        setup_s = t0 - t_start
        inflight = collections.deque()      # losses of dispatched steps
        n, i = 0, 3
        while True:
            if args.trace and n == c["trace_after_steps"]:
                if inflight:                # no earlier step in the trace
                    inflight[-1].block_until_ready()
                cap["start"]()
                traced_from = i
            with jax.profiler.TraceAnnotation("bench:dispatch"):
                state, met = compiled(state, batches[i % len(batches)])
            inflight.append(met["loss"])
            i, n = i + 1, n + 1
            if len(inflight) > AHEAD:
                with jax.profiler.TraceAnnotation("bench:wait"):
                    inflight.popleft().block_until_ready()
            if args.trace and traced_from is not None \
                    and i - traced_from == trace_steps:
                inflight[-1].block_until_ready()
                cap["stop"]()
            if time.monotonic() - t0 >= args.seconds:
                break
        # nothing more is sent; the window closes once all sent has ended
        inflight[-1].block_until_ready()
        window_s = time.monotonic() - t0
        counter.armed = False
    from harness.device import memory_peak_bytes
    peak = memory_peak_bytes(devices)
    for x in jax.tree.leaves((state, batches)):
        x.delete()
    del state, compiled
    gc.collect()

    tr = common.load_trace(cap)
    ref_batches = stage_batches(mix, m["vocab_size"], jseed, sh["batch"])[:3]
    ref = reference_readings(cell, jseed, wkey, ref_batches)
    readings = compare(prog, ref, c["checks"]["skip_still_leaves"])
    checks, ok = common.limit_checks(readings, c["checks"]["limits"])

    obs = {"window_s": window_s, "steps": n,
           "tokens_per_step": tokens_per_step,
           "window_compiles": counter.count,
           "flops_per_token": counts.train_flops_per_token(
               m, mix["seq_len"], c["horn"]["keep_hidden"])}
    reduced = None
    if tr is not None:
        reduced = tracing.reduce(tr)
        obs["trace"] = tr
        obs["reduced"] = reduced
        obs["traced_steps"] = trace_steps
        obs["kept_blocks"] = kept_blocks(cell, jseed, traced_from,
                                         trace_steps)
    return {"setup_s": setup_s, "attempted": n, "failed": 0,
            "peak_bytes": peak, "obs": obs, "checks": checks, "correct": ok,
            "reduced": reduced}


def kept_blocks(cell, jseed: int, first: int, steps: int):
    """Per traced step, per layer, each group's kept FFN blocks."""
    import numpy as np
    c = cell.config
    m, horn = c["model"], c["horn"]
    out = []
    for s in range(first, first + steps):
        _, fm = R.horn_masks(jseed, s, m, horn)
        out.append((np.asarray(fm)[:, :, ::horn["block"]] > 0)
                   .sum(-1).tolist())
    return out
