"""The engine's tick on the profiler's clock: ``engine:*`` trace
annotations per tick and phase, the work counts the tick span carries,
``TickTimeline.tick_records`` and the padding counters.

Each profiled test runs a tiny engine under ``jax.profiler.start_trace``
and reads the host events back with ``ProfileData.from_file``."""
import glob

import jax
import numpy as np
import pytest

from repro.configs.base import HornConfig, get_model_config, reduced
from repro.models import api
from repro.serving import Engine, EngineConfig, ModelBank
from repro.serving.observability import (TICK_PHASES, EngineStats,
                                         Telemetry, TickTimeline)

CFG = reduced(get_model_config("qwen3-1.7b"), dtype="float32")
PHASES = ["engine:plan", "engine:host_prep", "engine:device_step",
          "engine:commit"]
DEVICE_PARTS = ["engine:h2d", "engine:dispatch", "engine:sync"]


@pytest.fixture(scope="module")
def params():
    return api.model_init(jax.random.key(0), CFG)


def _engine(params, draft=None, **over):
    ec = dict(num_slots=3, num_pages=64, page_size=4, max_prompt_len=32,
              max_new_tokens=12, token_budget=24, policy="on_demand",
              kv_dtype="float32", compute_dtype="float32")
    ec.update(over)
    return Engine(CFG, params, EngineConfig(**ec), draft=draft,
                  telemetry=Telemetry(timeline=True))


def _record_calls(engine):
    """Wrap the engine's step: (C, chunk_lens, starts) of every device
    call."""
    calls = []
    step = engine._step

    def recorded(*a, **kw):
        calls.append((a[2].shape[1], np.asarray(a[4]), np.asarray(a[3])))
        return step(*a, **kw)

    engine._step = recorded
    return calls


def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler session; the ``engine:`` host events
    as (name, start_ns, end_ns, stats), sorted by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine:"):
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns),
                                {k: v for k, v in e.stats}))
    return sorted(out, key=lambda x: (x[1], -x[2]))


def _children(events, parent, names):
    """The events named ``names`` lying within ``parent``, by start."""
    _, s0, e0, _ = parent
    return [ev for ev in events
            if ev[0] in names and s0 <= ev[1] and ev[2] <= e0]


def _attn_steps(engine, C, starts, lens):
    """The grid steps of one layer's paged-attention call at chunk width
    C, recomputed from the call's starts and chunk lengths: all of them,
    and those whose page group holds a live page (pages before
    ``start + chunk_len``)."""
    from repro.kernels.paged_attention.kernel import chunk_tiling
    KH, psize = CFG.num_kv_heads, engine.ecfg.page_size
    maxp = engine._bt.dev.shape[1]
    pps, hps = chunk_tiling(C, CFG.num_heads // KH, KH, maxp)
    if engine.ecfg.pages_per_step is not None:
        pps = engine.ecfg.pages_per_step
    live = 0
    for n in starts + lens:
        for g in range(-(-maxp // pps)):
            live += g * pps * psize < n
    return {"attn_grid_steps": len(starts) * (KH // hps) * -(-maxp // pps),
            "attn_live_steps": live * (KH // hps)}


def _prompts(lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def test_every_device_tick_has_one_span_with_its_counts(params, tmp_path):
    engine = _engine(params)
    calls = _record_calls(engine)
    a, b, c = _prompts((9, 6, 21))

    def drive():
        engine.submit(a, 6)
        engine.submit(b, 6)
        for _ in range(4):              # both decode by now
            engine.step()
        engine.submit(c, 4)             # a mixed prefill + decode tick
        engine.run()

    events = _profiled(tmp_path, drive)
    ticks = [ev for ev in events if ev[0] == "engine:tick"]
    assert len(ticks) == engine.steps == len(calls) > 0
    B = engine.ecfg.num_slots
    mixed = 0
    for i, (tick, (C, lens, starts)) in enumerate(zip(ticks, calls)):
        st = tick[3]
        assert st == {"tick": i, "real_tokens": int(lens.sum()),
                      "prefill_tokens": st["prefill_tokens"],
                      "padded_tokens": B * C, "chunk": C,
                      "live_slots": int((lens > 0).sum()),
                      **_attn_steps(engine, C, starts, lens)}
        assert 0 <= st["prefill_tokens"] <= st["real_tokens"]
        if 0 < st["prefill_tokens"] < st["real_tokens"]:
            mixed += 1
            assert st["padded_tokens"] == B * C > st["real_tokens"]
        # the phases nest in the tick, in order and without overlap
        phases = _children(events, tick, PHASES)
        assert [p[0] for p in phases] == PHASES
        for p, q in zip(phases, phases[1:]):
            assert p[2] <= q[1]
        dev = phases[2]
        parts = _children(events, dev, DEVICE_PARTS)
        assert [p[0] for p in parts] == DEVICE_PARTS
        for p, q in zip(parts, parts[1:]):
            assert p[2] <= q[1]
    assert mixed >= 1
    assert not any(ev[0] == "engine:draft" for ev in events)

    # the timeline's tick records carry the same counts
    recs = engine.obs.timeline.tick_records()
    assert [r[4] for r in recs] == [t[3] for t in ticks]
    assert [r[0] for r in recs] == list(range(len(ticks)))
    for _, t0, t1, phases, _ in recs:
        assert set(phases) == set(TICK_PHASES)
        assert phases["plan"][0] == t0 and phases["commit"][1] == t1
    # and the engine's cumulative padding counters their sums
    snap = engine.metrics()
    m = snap["counters"]
    assert m["real_tokens"] == sum(int(l.sum()) for _, l, _ in calls)
    assert m["padded_tokens"] == sum(B * C for C, _, _ in calls)
    assert snap["tick"]["tokens_per_tick"]["count"] == len(calls)
    assert engine.obs.tokens_per_tick.max == max(int(l.sum())
                                                 for _, l, _ in calls)
    engine.reset_stats()
    assert engine.real_tokens == engine.padded_tokens == 0
    assert engine.obs.timeline.tick_records() == []


@pytest.mark.parametrize("pages_per_step", [None, 3])
def test_tick_counts_carry_the_attention_grid_steps(params, pages_per_step):
    """Every tick record's ``attn_grid_steps`` / ``attn_live_steps`` equal
    the paged call's grid steps and live steps recomputed from that tick's
    starts and chunk lengths, under the derived tiling and an explicit
    ``pages_per_step``; short contexts in 8-page tables leave dead steps."""
    engine = _engine(params, pages_per_step=pages_per_step)
    calls = _record_calls(engine)
    for p in _prompts((9, 6, 21)):
        engine.submit(p, 6)
    engine.run()
    recs = engine.obs.timeline.tick_records()
    assert len(recs) == len(calls) > 0
    dead = 0
    for (_, _, _, _, counts), (C, lens, starts) in zip(recs, calls):
        want = _attn_steps(engine, C, starts, lens)
        assert {k: counts[k] for k in want} == want
        assert 0 < counts["attn_live_steps"] <= counts["attn_grid_steps"]
        dead += counts["attn_grid_steps"] - counts["attn_live_steps"]
    assert dead > 0


def test_speculative_tick_nests_its_draft_span(params, tmp_path):
    horn = HornConfig(enabled=True, keep_hidden=0.875, keep_input=1.0,
                      block_size=16)
    draft = ModelBank(CFG, horn, 1, seed=0).draft_model(0, params)
    engine = _engine(params, speculate_k=3, draft=draft)

    def drive():
        for p in _prompts((7, 12)):
            engine.submit(p, 8)
        engine.run()

    events = _profiled(tmp_path, drive)
    drafts = [ev for ev in events if ev[0] == "engine:draft"]
    assert drafts
    preps = [ev for ev in events if ev[0] == "engine:host_prep"]
    for d in drafts:
        assert sum(p[1] <= d[1] and d[2] <= p[2] for p in preps) == 1
    phases = [r[3] for r in engine.obs.timeline.tick_records()]
    assert sum("draft" in p for p in phases) == len(drafts)


def test_tick_that_returns_early_closes_its_spans(params, tmp_path):
    engine = _engine(params)
    events = _profiled(tmp_path, engine.step)     # nothing to run
    assert [(ev[0], ev[3]) for ev in events] == [("engine:tick", {}),
                                                 ("engine:plan", {})]
    tick, plan = events
    assert tick[1] <= plan[1] and plan[2] <= tick[2]
    assert engine.steps == 0 and engine.obs.timeline.tick_records() == []


def test_tick_records_hold_phases_extra_spans_and_counts():
    tl = TickTimeline()
    counts = {"tick": 0, "real_tokens": 5, "prefill_tokens": 3,
              "padded_tokens": 16, "chunk": 4, "live_slots": 3}
    tl.add_tick(0, [1.0, 1.1, 1.2, 1.5, 1.6],
                extra_spans=[("draft", 1.12, 1.18)], counts=counts)
    tl.add_tick(1, [2.0, 2.1, 2.2, 2.5, 2.6])
    counts["real_tokens"] = 99          # the record keeps its own copy
    (t, t0, t1, phases, c), rec1 = tl.tick_records()
    assert (t, t0, t1) == (0, 1.0, 1.6)
    assert phases == {"plan": (1.0, 1.1), "host_prep": (1.1, 1.2),
                      "device_step": (1.2, 1.5), "commit": (1.5, 1.6),
                      "draft": (1.12, 1.18)}
    assert c["real_tokens"] == 5 and c["padded_tokens"] == 16
    assert rec1[0] == 1 and rec1[4] == {}
    # the reader hands out a copy; clear() drops the records
    tl.tick_records().clear()
    assert len(tl.tick_records()) == 2
    tl.clear()
    assert tl.tick_records() == [] and tl.ticks == 0


def test_tick_records_follow_the_phase_spans():
    tl = TickTimeline()
    for k in range(3):
        t = 10.0 * k
        tl.add_tick(k, [t, t + 1, t + 2, t + 3, t + 4],
                    counts={"tick": k})
    spans = [(n, a, b) for n, tid, a, b, _ in tl._spans if tid == 0]
    for k, t0, t1, phases, counts in tl.tick_records():
        assert counts == {"tick": k}
        assert [(n, *phases[n]) for n in TICK_PHASES] == \
            [s for s in spans if s[1] >= t0 and s[2] <= t1]


def test_padding_counters_reset_with_the_other_stats():
    s = EngineStats(real_tokens=7, padded_tokens=64, steps=2)
    s.reset()
    assert (s.real_tokens, s.padded_tokens, s.steps) == (0, 0, 0)
    assert {"real_tokens", "padded_tokens"} <= set(s.as_dict())
