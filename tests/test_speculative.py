"""Speculative decoding: materialized small-circuit drafts verified by the
parent in one budgeted call.

The load-bearing guarantee is BYTE-IDENTITY: greedy speculative decode must
emit exactly the token stream non-speculative greedy decode emits — solo,
routed over a ModelBank, co-batched with ensembles, under preemption, and
with the prefix cache adopting pages — because the parent verifies every
position it commits (the draft only decides how many positions one tick
can commit).  Temperature > 0 is rejection sampling: distributionally the
parent, not byte-equal to sequential sampling, but byte-REPRODUCIBLE
run-to-run per (req_id, sample_step) fold_in.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs.base import HornConfig, get_model_config, reduced
from repro.models import api
from repro.serving import (Engine, EngineConfig, ModelBank, Router,
                           speculative_draft_len)

CFG = reduced(get_model_config("qwen3-1.7b"), dtype="float32")
# high-keep draft: with UNTRAINED weights, agreement (and so acceptance)
# tracks how much of the FFN the circuit keeps — see ModelBank.draft_model
HORN = HornConfig(enabled=True, keep_hidden=0.875, keep_input=1.0,
                  block_size=16)


@pytest.fixture(scope="module")
def params():
    return api.model_init(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def draft(params):
    return ModelBank(CFG, HORN, 1, seed=0).draft_model(0, params)


def mk(params, *, spec_k=0, draft=None, bank=None, router=None, **over):
    ec = dict(num_slots=3, num_pages=64, page_size=4, max_prompt_len=32,
              max_new_tokens=12, token_budget=24, policy="on_demand",
              kv_dtype="float32", compute_dtype="float32",
              speculate_k=spec_k)
    ec.update(over)
    return Engine(CFG, params, EngineConfig(**ec), bank=bank,
                  router=router, draft=draft)


def prompts(lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def outs(engine):
    return {r.id: list(r.out_tokens) for r in engine.sched.finished}


def drain(engine, reqs, gen=10, **kw):
    for p in reqs:
        engine.submit(p, gen, **kw)
    engine.run()
    return outs(engine)


# ---------------------------------------------------------------------------
# byte-identity (greedy)
# ---------------------------------------------------------------------------
def test_greedy_solo_byte_identical_and_fewer_ticks(params, draft):
    reqs = prompts((7, 13, 5))
    base = mk(params)
    spec = mk(params, spec_k=4, draft=draft)
    assert drain(base, reqs) == drain(spec, reqs)
    # the whole point: >1 committed token per speculating slot-tick, and
    # strictly fewer engine ticks than sequential decode
    assert spec.accepted_tok_per_tick > 1.0
    assert spec.spec_accepted > 0
    assert spec.steps < base.steps
    spec.pool.check_invariants()
    spec.spec.pool.check_invariants()
    assert spec.spec.pool.num_seqs == 0      # all draft state released


def test_greedy_routed_byte_identical(params):
    reqs = prompts((7, 13, 5, 9))
    bank = ModelBank(CFG, HORN, 3, seed=0)
    base = mk(params, bank=ModelBank(CFG, HORN, 3, seed=0),
              router=Router(3, policy="explicit"))
    spec = mk(params, spec_k=4, bank=bank,
              router=Router(3, policy="explicit"),
              draft=bank.draft_model(0, params))
    for eng in (base, spec):
        for i, p in enumerate(reqs):
            eng.submit(p, 8, submodel_id=i % 3)
        eng.run()
    assert outs(base) == outs(spec)
    assert spec.spec_accepted >= 0           # drafts verified under each
    assert spec.accepted_tok_per_tick >= 1.0  # slot's own circuit masks


def test_greedy_under_preemption_byte_identical(params, draft):
    # a pool tight enough that the SPECULATING engine preempts too: the
    # rollback/truncate path and the preempt path must compose
    reqs = prompts((6, 9, 7, 8), seed=3)
    kw = dict(num_pages=12, max_prompt_len=16, token_budget=16,
              max_new_tokens=10)
    base = mk(params, **kw)
    spec = mk(params, spec_k=3, draft=draft, **kw)
    assert drain(base, reqs, gen=9) == drain(spec, reqs, gen=9)
    assert spec.preemptions > 0, "pool not tight enough to test preemption"
    spec.pool.check_invariants()
    assert spec.spec.pool.num_seqs == 0


def test_greedy_with_prefix_cache_and_shared_prompts(params, draft):
    # prefix-cache adoption (mid-prompt prefill start) + verify rollback
    # interleave: truncated draft tails must never reach the publishable
    # region, and cached pages must never leak into a verify chunk
    rng = np.random.default_rng(5)
    system = rng.integers(1, CFG.vocab_size, (12,)).astype(np.int32)
    reqs = [np.concatenate([system,
                            rng.integers(1, CFG.vocab_size, (4 + i,))
                            .astype(np.int32)]) for i in range(3)]
    base = mk(params, prefix_cache=True)
    spec = mk(params, spec_k=4, draft=draft, prefix_cache=True)
    for eng in (base, spec):
        eng.submit(reqs[0], 10)
        eng.run()                  # publish the system prefix first
        for p in reqs[1:]:
            eng.submit(p, 10)
        eng.run()
    assert outs(base) == outs(spec)
    assert spec.cache_hit_tokens > 0, "shared prompts never hit the cache"
    spec.pool.check_invariants()


def test_greedy_cobatched_with_ensemble(params):
    # ensemble members decode in lockstep (never speculate) while a solo
    # slot in the same tick verifies drafts — one jitted call carries both
    bank = ModelBank(CFG, HORN, 3, seed=0)
    rng = np.random.default_rng(7)
    pe = rng.integers(1, CFG.vocab_size, (9,)).astype(np.int32)
    ps = rng.integers(1, CFG.vocab_size, (6,)).astype(np.int32)
    streams = []
    for spec_k in (0, 4):
        eng = mk(params, spec_k=spec_k, bank=ModelBank(CFG, HORN, 3, seed=0),
                 router=Router(3),
                 draft=bank.draft_model(0, params) if spec_k else None,
                 num_slots=5, num_pages=96, token_budget=40)
        g = eng.submit(pe, 8, ensemble="mean_logit")
        eng.submit(ps, 8)
        eng.run()
        streams.append((list(g.out_tokens), outs(eng)))
    assert streams[0] == streams[1]


def test_eos_mid_verify_window_stops_exactly(params, draft):
    # pick an EOS the baseline emits mid-stream, then check the
    # speculative engine truncates its commits at exactly that token
    reqs = prompts((7,), seed=1)
    probe = mk(params)
    stream = drain(probe, reqs)[0]
    eos = stream[len(stream) // 2]
    base = mk(params, eos_id=eos)
    spec = mk(params, spec_k=4, draft=draft, eos_id=eos)
    assert drain(base, reqs) == drain(spec, reqs)
    done = spec.sched.finished[0]
    assert done.out_tokens[-1] == eos
    assert eos not in done.out_tokens[:-1]


# ---------------------------------------------------------------------------
# temperature > 0: reproducible rejection sampling
# ---------------------------------------------------------------------------
def test_temperature_reproducible_and_clean(params, draft):
    reqs = prompts((7, 13, 5))
    runs = []
    for _ in range(2):
        eng = mk(params, spec_k=4, draft=draft, temperature=0.8)
        runs.append(drain(eng, reqs, gen=8))
        eng.pool.check_invariants()
        eng.spec.pool.check_invariants()
    assert runs[0] == runs[1], "same seeds must replay the same stream"
    assert eng.spec_drafted > 0


def test_temperature_nonspec_path_unchanged_by_plumbing(params):
    # the S_v == 1 window with temperature > 0 must be the classic
    # (req_id, step) fold_in categorical — two fresh engines agree
    reqs = prompts((7, 5))
    a = drain(mk(params, temperature=0.8), reqs, gen=6)
    b = drain(mk(params, temperature=0.8), reqs, gen=6)
    assert a == b


# ---------------------------------------------------------------------------
# budget accounting + validation
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("spec_k", [0, 4])
def test_tick_pulls_accepted_counts_only_where_a_slot_drafts(
        params, draft, spec_k, monkeypatch):
    """A tick's one host pull carries the accepted counts only where a
    slot drafted (only the speculative commit reads them); any other tick
    pulls the sampled tokens alone, one device-to-host transfer."""
    import sys
    real, pulls = jax.device_get, []

    def spy(x):
        if sys._getframe(1).f_code.co_name == "step":
            pulls.append(isinstance(x, tuple))
        return real(x)

    eng = mk(params, spec_k=spec_k, draft=draft if spec_k else None)
    monkeypatch.setattr(jax, "device_get", spy)
    out = drain(eng, prompts((7, 13, 5)))
    assert len(pulls) == eng.steps
    if spec_k:
        assert any(pulls) and not all(pulls)    # prefill ticks draft nothing
        assert out == drain(mk(params), prompts((7, 13, 5)))
    else:
        assert not any(pulls)


def test_speculative_budget_split():
    # each decode slot costs its pending token; the rest splits across
    # speculating slots, clamped to k and floored at plain decode
    assert speculative_draft_len(4, 24, 3, 3) == 4
    assert speculative_draft_len(4, 6, 3, 3) == 1
    assert speculative_draft_len(4, 3, 3, 3) == 0
    assert speculative_draft_len(4, 24, 3, 0) == 0
    assert speculative_draft_len(0, 24, 3, 3) == 0


def test_budget_pressure_degrades_gracefully(params, draft):
    # token_budget == num_slots: a full decode batch has zero headroom
    # (those ticks run plain decode), but the moment slots free up the
    # leftover budget drafts again — byte-identical throughout
    reqs = prompts((5, 7, 6))
    kw = dict(token_budget=3, num_slots=3)
    base = mk(params, **kw)
    spec = mk(params, spec_k=4, draft=draft, **kw)
    assert drain(base, reqs, gen=6) == drain(spec, reqs, gen=6)
    # every drafted token obeyed the budget: 1 + dl <= budget per slot
    assert spec.accepted_tok_per_tick >= 1.0


def test_engine_validates_draft_config(params, draft):
    with pytest.raises(ValueError, match="needs a DraftModel"):
        mk(params, spec_k=4)
    with pytest.raises(ValueError, match="speculate_k > 0"):
        mk(params, draft=draft)
    import dataclasses
    bad = dataclasses.replace(draft, cfg=dataclasses.replace(
        draft.cfg, vocab_size=CFG.vocab_size + 1))
    with pytest.raises(ValueError, match="vocab"):
        mk(params, spec_k=4, draft=bad)


def test_draft_model_is_materialized_small(params):
    # a low-keep circuit materializes at a genuinely smaller width (the
    # high-keep default may pad back to d_ff when some layer keeps every
    # block — layers share one stacked shape)
    half = HornConfig(enabled=True, keep_hidden=0.5, keep_input=1.0,
                      block_size=16)
    dm = ModelBank(CFG, half, 2, seed=0).draft_model(1, params)
    assert dm.cfg.d_ff < CFG.d_ff
    assert 0.0 < dm.kept_frac < 1.0
    assert dm.circuit == 1
