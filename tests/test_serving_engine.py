"""Serving-subsystem tests: page-pool + scheduler invariants, the Pallas
paged-attention kernels (decode + chunk-append) vs their pure-jnp refs
(interpret mode, CPU), the continuous-batching engine reproducing
dense-cache greedy decode exactly through chunked prefill, and preemption
producing byte-identical output to an uninterrupted run.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.paged_attention.kernel import (paged_attention,
                                                  paged_chunk_attention)
from repro.kernels.paged_attention.ops import (paged_pool_append,
                                               paged_pool_update)
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_chunk_attention_ref)
from repro.serving.kv_cache import PagePool, PagePoolOOM
from repro.serving.scheduler import FCFSScheduler, Request


# ---------------------------------------------------------------------------
# page pool
# ---------------------------------------------------------------------------
def test_pool_alloc_free_roundtrip():
    pool = PagePool(num_pages=9, page_size=4)
    t1 = pool.alloc(1, 10)          # 3 pages
    t2 = pool.alloc(2, 4)           # 1 page
    pool.check_invariants()
    assert len(t1) == 3 and len(t2) == 1
    assert pool.used_pages == 4 and pool.free_pages == 4
    assert pool.utilization() == pytest.approx(0.5)
    assert 0 not in t1 + t2         # null page never handed out
    pool.free_seq(1)
    pool.check_invariants()
    assert pool.used_pages == 1
    pool.free_seq(2)
    assert pool.used_pages == 0 and pool.free_pages == 8


def test_pool_oom_leaves_allocation_intact():
    pool = PagePool(num_pages=5, page_size=4)   # 4 allocatable
    pool.alloc(1, 12)                           # 3 pages
    with pytest.raises(PagePoolOOM):
        pool.alloc(2, 8)                        # needs 2, only 1 free
    pool.check_invariants()
    # seq 2's failed attempt must not leak pages or stay registered
    assert pool.num_seqs == 1
    pool.alloc(2, 4)                            # retry at a size that fits
    pool.check_invariants()


def test_pool_ensure_grows_on_demand():
    pool = PagePool(num_pages=6, page_size=2)
    pool.alloc(7, 2)                            # 1 page covers 2 tokens
    assert len(pool.table(7)) == 1
    pool.ensure(7, 3)                           # crosses page boundary
    assert len(pool.table(7)) == 2
    pool.ensure(7, 3)                           # idempotent
    assert len(pool.table(7)) == 2
    pool.check_invariants()


def test_pool_double_alloc_rejected():
    pool = PagePool(num_pages=6, page_size=2)
    pool.alloc(1, 2)
    with pytest.raises(ValueError):
        pool.alloc(1, 2)
    with pytest.raises(ValueError):
        pool.alloc_pages(1, 1)


def test_pool_alloc_pages():
    pool = PagePool(num_pages=6, page_size=2)
    t = pool.alloc_pages(1, 3)
    assert len(t) == 3 and 0 not in t
    pool.check_invariants()
    with pytest.raises(PagePoolOOM):
        pool.alloc_pages(2, 3)                      # only 2 free
    assert pool.num_seqs == 1                       # failed alloc not registered
    pool.check_invariants()
    pool.free_seq(1)
    assert pool.free_pages == 5


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------
def _req(i, plen, max_new=4):
    return Request(id=i, prompt=np.zeros(plen, np.int32), max_new_tokens=max_new)


def test_scheduler_fcfs_admission_and_eviction():
    pool = PagePool(num_pages=64, page_size=4)
    sched = FCFSScheduler(2, pool, policy="reserve")
    for i in range(4):
        sched.submit(_req(i, plen=4))
    admitted = sched.admit(now=0.0)
    assert [r.id for r in admitted] == [0, 1]       # FCFS, slot-bounded
    assert not sched.admit(now=0.0)                 # no free slots
    # finish request 0 -> slot + pages free -> 2 joins mid-flight
    for t in range(4):
        sched.record_token(admitted[0].slot, 11, now=1.0)
    done = sched.evict_finished(now=2.0)
    assert [r.id for r in done] == [0]
    pool.check_invariants()
    joined = sched.admit(now=3.0)
    assert [r.id for r in joined] == [2]
    assert {r.id for r in sched.running.values()} == {1, 2}


def test_scheduler_no_head_of_line_bypass():
    pool = PagePool(num_pages=4, page_size=4)       # 3 allocatable pages
    sched = FCFSScheduler(4, pool, policy="reserve")
    sched.submit(_req(0, plen=12, max_new=4))       # needs 4 pages > 3 free
    sched.submit(_req(1, plen=1, max_new=1))        # would fit, must wait
    assert sched.admit(now=0.0) == []
    assert [r.id for r in sched.waiting] == [0, 1]


def test_scheduler_reserve_policy_never_grows():
    pool = PagePool(num_pages=16, page_size=2)
    sched = FCFSScheduler(1, pool, policy="reserve")
    req = _req(0, plen=3, max_new=5)
    sched.submit(req)
    sched.admit(now=0.0)
    before = len(pool.table(0))
    for _ in range(5):
        sched.record_token(req.slot, 1, now=0.0)
        pool.ensure(req.id, req.context_len)        # the engine's decode grow
    assert len(pool.table(0)) == before             # worst case pre-reserved


def test_scheduler_preempt_youngest_to_queue_head():
    pool = PagePool(num_pages=64, page_size=4)
    sched = FCFSScheduler(3, pool, policy="on_demand")
    for i in range(3):
        sched.submit(_req(i, plen=4))
    sched.admit(now=0.0)
    sched.submit(_req(3, plen=4))                   # waits behind the batch
    for slot, r in sched.running.items():           # give each some progress
        sched.record_token(slot, 7, now=1.0)
        r.prefill_pos = r.prompt_len
    victim = sched.preempt_youngest()
    assert victim.id == 2                           # youngest admission
    assert victim.slot is None and victim.prefill_pos == 0
    assert victim.num_preemptions == 1 and sched.preemptions == 1
    assert [r.id for r in sched.waiting] == [2, 3]  # head, before later work
    pool.check_invariants()
    # its pages are gone; its next chunked prefill must rebuild prompt+output
    assert victim.id not in pool._tables
    assert list(victim.kv_tokens) == list(victim.prompt)  # 1 tok: all pending
    sched.record_token(sched.admit(now=2.0)[0].slot, 8, now=2.0)
    # two running left -> preemption still possible; one left -> refused
    assert sched.preempt_youngest() is not None
    assert sched.preempt_youngest() is not None
    assert sched.preempt_youngest() is None         # sole survivor protected


def test_request_kv_tokens_carries_generated_prefix():
    req = _req(0, plen=3, max_new=8)
    req.out_tokens = [11, 12, 13]
    # the last generated token's KV is written by the decode step that
    # consumes it, so re-prefill covers prompt + out[:-1] only
    assert req.num_kv_tokens == 5
    assert list(req.kv_tokens) == [0, 0, 0, 11, 12]
    assert req.in_prefill                           # prefill_pos == 0 < 5
    req.prefill_pos = 5
    assert not req.in_prefill


# ---------------------------------------------------------------------------
# paged-attention kernel vs ref (Pallas interpret mode on CPU)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,KH,D,psize,maxp", [
    (2, 4, 4, 16, 8, 3),     # MHA
    (3, 4, 2, 32, 16, 4),    # GQA
    (1, 8, 1, 16, 8, 5),     # MQA
])
@pytest.mark.parametrize("variant", ["plain", "window", "softcap"])
def test_paged_attention_kernel_vs_ref(B, H, KH, D, psize, maxp, variant):
    # str hashes are randomized per interpreter; keep the data reproducible
    vid = {"plain": 1, "window": 2, "softcap": 3}[variant]
    rng = np.random.default_rng((B, H, KH, psize, vid))
    P = B * maxp + 1
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, KH, psize, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, KH, psize, D)), jnp.float32)
    # each seq owns a disjoint page range; lengths straddle page boundaries
    bt = np.zeros((B, maxp), np.int32)
    lengths = np.zeros((B,), np.int32)
    for b in range(B):
        lengths[b] = int(rng.integers(1, maxp * psize + 1))
        npg = -(-int(lengths[b]) // psize)
        bt[b, :npg] = 1 + b * maxp + np.arange(npg)
    kw = {}
    if variant == "window":
        kw["window"] = psize + 3
    elif variant == "softcap":
        kw["softcap"] = 30.0
    out = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths),
                          scale=D ** -0.5, interpret=True, **kw)
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(bt),
                              jnp.asarray(lengths), scale=D ** -0.5, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)


def test_paged_attention_empty_slot_emits_zeros():
    B, H, KH, D, psize, maxp = 2, 2, 2, 16, 8, 2
    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.normal(size=(5, KH, psize, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    bt = jnp.asarray([[1, 2], [0, 0]], jnp.int32)
    ln = jnp.asarray([11, 0], jnp.int32)
    out = paged_attention(q, kp, kp, bt, ln, scale=0.25, interpret=True)
    assert np.all(np.asarray(out)[1] == 0)
    assert np.all(np.isfinite(np.asarray(out)))


# ---------------------------------------------------------------------------
# chunk-append kernel vs ref (the unified serving step's workhorse)
# ---------------------------------------------------------------------------
PSIZE = 8


@pytest.mark.parametrize("B,H,KH,D,maxp", [
    (2, 4, 4, 16, 4),        # MHA
    (3, 4, 2, 32, 5),        # GQA
])
@pytest.mark.parametrize("C", [1, PSIZE, 3 * PSIZE - 1])
@pytest.mark.parametrize("variant", ["plain", "window", "softcap"])
def test_paged_chunk_attention_kernel_vs_ref(B, H, KH, D, maxp, C, variant):
    # str hashes are randomized per interpreter; keep the data reproducible
    vid = {"plain": 1, "window": 2, "softcap": 3}[variant]
    rng = np.random.default_rng((B, H, KH, C, vid))
    P = B * maxp + 1
    q = jnp.asarray(rng.normal(size=(B, C, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, KH, PSIZE, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, KH, PSIZE, D)), jnp.float32)
    # each seq owns a disjoint page range; chunks start mid-page, straddle
    # page boundaries, and one row is a partial chunk (right-padded)
    bt = np.zeros((B, maxp), np.int32)
    starts = np.zeros((B,), np.int32)
    clens = np.zeros((B,), np.int32)
    for b in range(B):
        starts[b] = int(rng.integers(0, maxp * PSIZE - C + 1))
        clens[b] = C if b == 0 else int(rng.integers(0, C + 1))
        npg = max(1, -(-(int(starts[b]) + int(clens[b])) // PSIZE))
        bt[b, :npg] = 1 + b * maxp + np.arange(npg)
    kw = {}
    if variant == "window":
        kw["window"] = PSIZE + 3
    elif variant == "softcap":
        kw["softcap"] = 30.0
    args = (q, kp, vp, jnp.asarray(bt), jnp.asarray(starts),
            jnp.asarray(clens))
    out = paged_chunk_attention(*args, scale=D ** -0.5, interpret=True, **kw)
    ref = paged_chunk_attention_ref(*args, scale=D ** -0.5, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)
    # padding rows emit exact zeros (idle slots, partial chunks)
    for b in range(B):
        assert np.all(np.asarray(out)[b, clens[b]:] == 0)


def test_paged_chunk_attention_c1_bitwise_matches_decode():
    """Chunk width 1 IS the decode path — bit-for-bit, so the unified step's
    decode-only ticks are compatible with the classic paged-decode cell."""
    B, H, KH, D, psize, maxp = 3, 4, 2, 16, 8, 4
    rng = np.random.default_rng(7)
    P = B * maxp + 1
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, KH, psize, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, KH, psize, D)), jnp.float32)
    bt = np.zeros((B, maxp), np.int32)
    lengths = np.zeros((B,), np.int32)
    for b in range(B):
        lengths[b] = int(rng.integers(1, maxp * psize + 1))
        npg = -(-int(lengths[b]) // psize)
        bt[b, :npg] = 1 + b * maxp + np.arange(npg)
    for kw in ({}, {"window": 5}, {"softcap": 20.0}):
        dec = paged_attention(q[:, 0], kp, vp, jnp.asarray(bt),
                              jnp.asarray(lengths), scale=D ** -0.5,
                              interpret=True, **kw)
        chk = paged_chunk_attention(q, kp, vp, jnp.asarray(bt),
                                    jnp.asarray(lengths - 1),
                                    jnp.ones((B,), jnp.int32),
                                    scale=D ** -0.5, interpret=True, **kw)
        assert np.array_equal(np.asarray(dec), np.asarray(chk)[:, 0]), kw


def test_paged_pool_append_scatter():
    psize = 4
    pool = jnp.zeros((6, 2, psize, 8), jnp.float32)
    new = jnp.arange(2 * 5 * 2 * 8, dtype=jnp.float32).reshape(2, 5, 2, 8)
    bt = jnp.asarray([[1, 2, 0], [3, 4, 0]], jnp.int32)
    # seq 0: 5 valid tokens from position 2 (straddles pages 1 -> 2);
    # seq 1: 3 valid of 5 from position 0 (padding must hit the null page)
    out = np.asarray(paged_pool_append(pool, new, bt,
                                       jnp.asarray([2, 0], jnp.int32),
                                       jnp.asarray([5, 3], jnp.int32)))
    out = out.transpose(0, 2, 1, 3)           # [P, psize, KH, D] for indexing
    n = np.asarray(new)
    assert np.array_equal(out[1, 2], n[0, 0]) and \
        np.array_equal(out[1, 3], n[0, 1])
    assert np.array_equal(out[2, 0], n[0, 2]) and \
        np.array_equal(out[2, 2], n[0, 4])
    assert np.array_equal(out[3, :3], n[1, :3])
    assert np.all(out[3, 3] == 0) and np.all(out[4] == 0)  # padding nulled


def test_paged_pool_update_scatter():
    psize = 4
    pool = jnp.zeros((6, 2, psize, 8), jnp.float32)
    new = jnp.ones((3, 2, 8), jnp.float32) * jnp.asarray([1., 2., 3.])[:, None, None]
    bt = jnp.asarray([[1, 2], [3, 0], [0, 0]], jnp.int32)
    pos = jnp.asarray([5, 2, 0], jnp.int32)   # page 2 slot 1, page 3 slot 2, null
    out = np.asarray(paged_pool_update(pool, new, bt, pos))
    out = out.transpose(0, 2, 1, 3)           # [P, psize, KH, D] for indexing
    assert np.all(out[2, 1] == 1.0)           # seq 0 -> 2nd page, offset 1
    assert np.all(out[3, 2] == 2.0)           # seq 1 -> 1st page, offset 2
    assert np.all(out[0, 0] == 3.0)           # empty slot lands in null page
    assert out.sum() == (1.0 + 2.0 + 3.0) * 2 * 8


# ---------------------------------------------------------------------------
# engine end-to-end: continuous batching == dense-cache greedy decode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch,budget", [
    ("qwen3-1.7b", 256),     # whole prompts fit one chunk
    ("qwen3-1.7b", 3),       # prompts split into 1-3 token chunks per tick
    ("gemma2-27b", 256),
    ("gemma2-27b", 5),
])
def test_engine_matches_dense_decode(arch, budget):
    # gemma2 covers the sliding-window (local) + softcap paged path; its
    # reduced window (16) is shorter than the 11-token+generated context of
    # the second prompt once pages are crossed.  The small budgets force the
    # unified tick to interleave prompt chunks with running decode tokens —
    # output must not depend on how prefill is chunked
    from repro.configs.base import get_model_config, reduced
    from repro.core.steps import make_ctx
    from repro.models import api
    from repro.models import transformer as T
    from repro.serving import Engine, EngineConfig

    cfg = reduced(get_model_config(arch))
    params = api.model_init(jax.random.key(0), cfg)
    ctx = make_ctx(cfg, None)
    max_new = 4

    def ref_generate(prompt):
        L = len(prompt)
        lg, cache, _ = api.prefill(
            params, {"tokens": jnp.asarray([prompt], jnp.int32)}, cfg, ctx)
        buf = T.init_cache(cfg, 1, L + max_new, dtype=jnp.float32)

        def splice(b, p):
            ax = b.ndim - 3
            pad = [(0, 0)] * b.ndim
            pad[ax] = (0, b.shape[ax] - p.shape[ax])
            return jnp.pad(p, pad).astype(b.dtype)

        cache = jax.tree.map(splice, buf, cache)
        toks = [int(jnp.argmax(lg[0]))]
        for i in range(max_new - 1):
            lg, cache = api.decode_step(
                params, cache, jnp.asarray([[toks[-1]]], jnp.int32),
                jnp.asarray(L + i, jnp.int32), cfg, ctx)
            toks.append(int(jnp.argmax(lg[0])))
        return toks

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 11, 3)]
    refs = [ref_generate(list(map(int, p))) for p in prompts]

    # 2 slots, 3 requests: the third joins mid-flight after an eviction
    eng = Engine(cfg, params,
                 EngineConfig(num_slots=2, num_pages=32, page_size=8,
                              max_prompt_len=16, max_new_tokens=max_new,
                              token_budget=budget, policy="on_demand",
                              kv_dtype="float32", compute_dtype="float32"))
    for p in prompts:
        eng.submit(p, max_new)
    t = [0.0]

    def clk():
        t[0] += 1.0
        return t[0]

    fin = eng.run(clock=clk)
    got = {r.id: r.out_tokens for r in fin}
    for i, ref in enumerate(refs):
        assert got[i] == ref, f"request {i}: {got[i]} != {ref}"
    eng.pool.check_invariants()
    assert eng.pool.used_pages == 0                 # everything freed
    assert all(r.t_first_token is not None and r.t_done is not None
               for r in fin)


# ---------------------------------------------------------------------------
# preemption: evict mid-decode, re-admit, byte-identical output
# ---------------------------------------------------------------------------
def _run_engine(cfg, params, prompts, max_new, *, num_pages,
                temperature=0.0, budget=16):
    from repro.serving import Engine, EngineConfig

    eng = Engine(cfg, params,
                 EngineConfig(num_slots=2, num_pages=num_pages, page_size=4,
                              max_prompt_len=8, max_new_tokens=max_new,
                              token_budget=budget, temperature=temperature,
                              policy="on_demand", kv_dtype="float32",
                              compute_dtype="float32"))
    for p in prompts:
        eng.submit(p, max_new)
    t = [0.0]

    def clk():
        t[0] += 1.0
        return t[0]

    fin = eng.run(clock=clk)
    return eng, {r.id: list(r.out_tokens) for r in fin}


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_preempted_request_output_is_byte_identical(temperature):
    """A sequence evicted mid-decode and re-admitted (KV recomputed through
    chunked prefill) must reproduce the uninterrupted run exactly — greedy
    and sampled: per-(request, step) fold_in keys survive preemption."""
    from repro.configs.base import get_model_config, reduced
    from repro.models import api

    cfg = reduced(get_model_config("qwen3-1.7b"))
    params = api.model_init(jax.random.key(0), cfg)
    prompts = [np.arange(1, 9, dtype=np.int32),
               np.arange(1, 6, dtype=np.int32)]
    # 6 allocatable pages: both admit (3 + 2 pages on_demand), then decode
    # growth runs the pool dry -> the younger sequence is preempted and
    # re-admitted after the older finishes
    tight, got = _run_engine(cfg, params, prompts, 8, num_pages=7,
                             temperature=temperature)
    assert tight.preemptions >= 1, "pool was never squeezed"
    tight.pool.check_invariants()
    assert tight.pool.used_pages == 0
    assert all(r.t_first_token is not None and r.t_done is not None
               for r in tight.sched.finished)

    roomy, want = _run_engine(cfg, params, prompts, 8, num_pages=64,
                              temperature=temperature)
    assert roomy.preemptions == 0
    assert got == want, f"preemption changed output: {got} != {want}"


def test_poisson_squeeze_completes_with_preemption():
    """The load that used to exit 2 with EngineOOM under on_demand now
    drains completely, recording preemptions instead."""
    from repro.configs.base import get_model_config, reduced
    from repro.launch.serve import make_requests
    from repro.models import api
    from repro.serving import Engine, EngineConfig

    cfg = reduced(get_model_config("qwen3-1.7b"))
    params = api.model_init(jax.random.key(0), cfg)
    eng = Engine(cfg, params,
                 EngineConfig(num_slots=4, num_pages=13, page_size=4,
                              max_prompt_len=16, max_new_tokens=12,
                              token_budget=16, policy="on_demand",
                              kv_dtype="float32", compute_dtype="float32"))
    rng = np.random.default_rng(0)
    reqs = make_requests(8, cfg.vocab_size, rng, max_prompt=16, gen=12)
    for _, prompt, g in reqs:
        eng.submit(prompt, g)
    fin = eng.run(clock=iter(np.arange(1e6)).__next__)
    assert len(fin) == 8                        # nothing lost, no EngineOOM
    assert eng.preemptions >= 1
    eng.pool.check_invariants()
    assert eng.pool.used_pages == 0


def test_engine_oom_only_when_unservable():
    """EngineOOM survives solely for genuinely unservable states: one
    sequence whose context can never fit the pool, even alone."""
    from repro.configs.base import get_model_config, reduced
    from repro.models import api
    from repro.serving import Engine, EngineConfig, EngineOOM

    cfg = reduced(get_model_config("qwen3-1.7b"))
    params = api.model_init(jax.random.key(0), cfg)
    # 3 allocatable pages of 4 tokens; the 8-token prompt admits on_demand
    # but needs 4 pages by token 13 — no other sequence to preempt
    eng = Engine(cfg, params,
                 EngineConfig(num_slots=2, num_pages=4, page_size=4,
                              max_prompt_len=8, max_new_tokens=8,
                              policy="on_demand", kv_dtype="float32",
                              compute_dtype="float32"))
    eng.submit(np.arange(1, 9, dtype=np.int32), 8)
    eng.submit(np.arange(1, 5, dtype=np.int32), 8)
    with pytest.raises(EngineOOM):
        for _ in range(64):
            eng.step(0.0)
    eng.pool.check_invariants()                     # state stays consistent


@pytest.mark.parametrize("speculate", [0, 3])
def test_engine_oom_leaks_no_pages_and_releases_router(speculate):
    """Every EngineOOM raise path must leave the engine consistent: the
    raising step allocates no pages it keeps (used_pages unchanged across
    it), pool invariants hold, and router loads still count exactly the
    live (unfinished) requests — finished work released, nothing double-
    released."""
    from repro.configs.base import HornConfig, get_model_config, reduced
    from repro.models import api
    from repro.serving import (Engine, EngineConfig, EngineOOM, ModelBank,
                               Router)

    cfg = reduced(get_model_config("qwen3-1.7b"), dtype="float32")
    params = api.model_init(jax.random.key(0), cfg)
    horn = HornConfig(enabled=True, keep_hidden=0.875, keep_input=1.0,
                      block_size=16)
    bank = ModelBank(cfg, horn, 2, seed=0)
    router = Router(2)
    draft = bank.draft_model(0, params) if speculate else None
    eng = Engine(cfg, params,
                 EngineConfig(num_slots=2, num_pages=4, page_size=4,
                              max_prompt_len=8, max_new_tokens=8,
                              policy="on_demand", kv_dtype="float32",
                              compute_dtype="float32",
                              speculate_k=speculate),
                 bank=bank, router=router, draft=draft)
    # 3 allocatable pages: the 8-token prompt admits on_demand but needs a
    # 4th page mid-decode with nothing left to preempt
    eng.submit(np.arange(1, 9, dtype=np.int32), 8)
    eng.submit(np.arange(1, 5, dtype=np.int32), 8)
    raised = False
    for _ in range(64):
        used = eng.pool.used_pages
        try:
            eng.step(0.0)
        except EngineOOM:
            raised = True
            assert eng.pool.used_pages == used, \
                "the raising step leaked pool pages"
            break
    assert raised, "pool was never exhausted"
    eng.pool.check_invariants()
    live = len(eng.sched.running) + len(eng.sched.waiting)
    assert sum(router.loads) == live, \
        f"router loads {router.loads} out of sync with {live} live requests"
    if speculate:
        eng.spec.pool.check_invariants()
        assert eng.spec.pool.num_seqs <= len(eng.sched.running)


def test_engine_oom_unadmittable_head_releases_and_keeps_pool():
    """The empty-batch raise path (a waiting head whose recompute stream
    can never fit, e.g. after preemption grew it): no allocation, loads
    consistent, and the raise repeats deterministically without corrupting
    state."""
    from repro.configs.base import get_model_config, reduced
    from repro.models import api
    from repro.serving import Engine, EngineConfig, EngineOOM

    cfg = reduced(get_model_config("qwen3-1.7b"), dtype="float32")
    params = api.model_init(jax.random.key(0), cfg)
    eng = Engine(cfg, params,
                 EngineConfig(num_slots=1, num_pages=6, page_size=4,
                              max_prompt_len=8, max_new_tokens=24,
                              policy="on_demand", kv_dtype="float32",
                              compute_dtype="float32"))
    a = eng.submit(np.arange(1, 5, dtype=np.int32), 2)
    b = eng.submit(np.arange(1, 9, dtype=np.int32), 24)   # waits: 1 slot
    # simulate the state preemption leaves behind: b evicted after 16
    # generated tokens, so its recompute stream (8 + 16 kv tokens) needs
    # more pages than the whole pool holds
    b.out_tokens.extend(range(100, 116))
    with pytest.raises(EngineOOM):
        for _ in range(64):
            eng.step(0.0)
    assert a.finished                      # earlier work completed cleanly
    assert eng.pool.used_pages == 0        # nothing admitted, nothing kept
    eng.pool.check_invariants()
    used = eng.pool.used_pages
    with pytest.raises(EngineOOM):         # deterministic, not corrupting
        eng.step(0.0)
    assert eng.pool.used_pages == used


def test_engine_rejects_infeasible_request():
    """A request that could never be admitted must fail at submit, not pin
    the FCFS head and spin the drive loop forever."""
    from repro.configs.base import get_model_config, reduced
    from repro.models import api
    from repro.serving import Engine, EngineConfig

    cfg = reduced(get_model_config("qwen3-1.7b"))
    params = api.model_init(jax.random.key(0), cfg)
    eng = Engine(cfg, params,
                 EngineConfig(num_slots=1, num_pages=3, page_size=4,
                              max_prompt_len=8, max_new_tokens=8,
                              policy="reserve"))
    with pytest.raises(ValueError, match="num_pages"):
        eng.submit(np.arange(1, 9, dtype=np.int32), 8)   # needs 4 > 2 pages
    assert not eng.sched.has_work()                      # nothing enqueued


def test_engine_rejects_unsupported_arch():
    from repro.configs.base import get_model_config, reduced
    from repro.serving import Engine, EngineConfig

    cfg = reduced(get_model_config("whisper-base"))   # encoder-decoder
    with pytest.raises(ValueError):
        Engine(cfg, params=None, ecfg=EngineConfig())

# ---------------------------------------------------------------------------
# roofline-push kernel features: pages_per_step, dead-entry clamp, fused
# verify windows, int8 quantized pools
# ---------------------------------------------------------------------------
def _chunk_case(B, H, KH, D, maxp, C, seed, *, int8=False):
    """Disjoint-page chunk-attention fixture; int8 mode quantizes the pools
    with per-(page, kv-head) scales (compression.quantize_int8 layout)."""
    from repro.optim.compression import quantize_int8

    rng = np.random.default_rng(seed)
    P = B * maxp + 1
    q = jnp.asarray(rng.normal(size=(B, C, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, KH, PSIZE, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, KH, PSIZE, D)), jnp.float32)
    bt = np.zeros((B, maxp), np.int32)
    starts = np.zeros((B,), np.int32)
    clens = np.zeros((B,), np.int32)
    for b in range(B):
        starts[b] = int(rng.integers(0, maxp * PSIZE - C + 1))
        clens[b] = C if b == 0 else int(rng.integers(0, C + 1))
        npg = max(1, -(-(int(starts[b]) + int(clens[b])) // PSIZE))
        bt[b, :npg] = 1 + b * maxp + np.arange(npg)
    scales = {}
    if int8:
        kq, ks = quantize_int8(kp, axis=(2, 3))
        vq, vs = quantize_int8(vp, axis=(2, 3))
        kp, vp = kq, vq
        scales = dict(k_scale=ks[:, :, 0, 0], v_scale=vs[:, :, 0, 0])
    return (q, kp, vp, jnp.asarray(bt), jnp.asarray(starts),
            jnp.asarray(clens)), scales


_SWEEP_BASE = {}


def _sweep_case(variant, C, dtype):
    """(args, kwargs, one-page one-head output) of a sweep-matrix case,
    the output checked against the pure-jnp ref once per case."""
    key = (variant, C, dtype)
    if key not in _SWEEP_BASE:
        vid = {"plain": 1, "window": 2, "softcap": 3, "gqa": 4}[variant]
        H, KH = (4, 2) if variant == "gqa" else (4, 4)
        D, maxp = 16, 4
        args, scales = _chunk_case(2, H, KH, D, maxp, C, (vid, C),
                                   int8=dtype == "int8")
        kw = dict(scales, scale=D ** -0.5)
        if variant == "window":
            kw["window"] = PSIZE + 3
        elif variant == "softcap":
            kw["softcap"] = 30.0
        ref = paged_chunk_attention_ref(*args, **kw)
        base = paged_chunk_attention(*args, interpret=True, pages_per_step=1,
                                     heads_per_step=1, **kw)
        np.testing.assert_allclose(np.asarray(base), np.asarray(ref),
                                   atol=2e-5, rtol=1e-5)
        _SWEEP_BASE[key] = args, kw, np.asarray(base)
    return _SWEEP_BASE[key]


@pytest.mark.parametrize("variant", ["plain", "window", "softcap", "gqa"])
@pytest.mark.parametrize("C", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_paged_chunk_pages_per_step_sweep(variant, C, dtype):
    """The full kernel matrix: every (masking variant, chunk width, pool
    dtype) must be allclose to the pure-jnp ref, and pages_per_step in
    {2, 4} must be *bit-for-bit* identical to pages_per_step=1 (the grid
    restructure only changes DMA scheduling, never the op sequence)."""
    args, kw, base = _sweep_case(variant, C, dtype)
    for pps in (2, 4):
        out = paged_chunk_attention(*args, interpret=True, pages_per_step=pps,
                                    heads_per_step=1, **kw)
        assert np.array_equal(np.asarray(out), base), \
            f"pages_per_step={pps} changed bits ({variant}, C={C}, {dtype})"


@pytest.mark.parametrize("tiling", ["all-heads", "derived"])
@pytest.mark.parametrize("variant", ["plain", "window", "softcap", "gqa"])
@pytest.mark.parametrize("C", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_paged_chunk_tiling_sweep(tiling, variant, C, dtype):
    """The same matrix with every kv head in one grid step (pages_per_step
    1 and a ragged 3 over 4-page tables) and with the tiling
    ``chunk_tiling`` derives: bit-for-bit the one-page, one-head kernel."""
    args, kw, base = _sweep_case(variant, C, dtype)
    KH = args[1].shape[1]
    tilings = [(1, KH), (3, KH)] if tiling == "all-heads" else [(None, None)]
    for pps, hps in tilings:
        out = paged_chunk_attention(*args, interpret=True, pages_per_step=pps,
                                    heads_per_step=hps, **kw)
        assert np.array_equal(np.asarray(out), base), \
            f"tiling ({pps}, {hps}) changed bits ({variant}, C={C}, {dtype})"


def test_chunk_tiling_at_the_serving_cell():
    """The tiling the serving benchmark's geometry derives (qwen3-1.7b: G 2,
    8 kv heads; 128-page tables): decode and prompt chunks up to C = 256
    take 2 pages of all 8 kv heads a step; only wider chunks split the
    heads, and a 1-page table takes 1 page."""
    from repro.kernels.paged_attention.kernel import chunk_tiling

    assert chunk_tiling(1, 2, 8, 128) == (2, 8)
    assert chunk_tiling(8, 2, 8, 128) == (2, 8)
    assert chunk_tiling(256, 2, 8, 128) == (2, 8)
    assert chunk_tiling(512, 2, 8, 128) == (2, 4)
    assert chunk_tiling(4096, 2, 8, 128) == (1, 1)
    assert chunk_tiling(1, 2, 8, 1) == (1, 8)
    assert chunk_tiling(1, 1, 4, 128) == (4, 4)


def _edge_case(case):
    """Chunk-attention inputs (B 3, KH 4, tables 5 pages wide) whose live
    lengths hit a tiling edge: ``ragged`` random lengths over a table
    width that 2 and 3 do not divide, ``empty-slot`` a slot with no
    tokens at all, ``group-boundary`` live pages that end exactly where a
    2- or 4-page group ends (and one token past it)."""
    B, H, KH, D, maxp, C = 3, 8, 4, 16, 5, 2
    rng = np.random.default_rng({"ragged": 41, "empty-slot": 42,
                                 "group-boundary": 43}[case])
    starts = {"ragged": rng.integers(0, maxp * PSIZE - C + 1, B),
              "empty-slot": np.asarray([9, 0, 13]),
              "group-boundary": np.asarray([2 * PSIZE - C, 4 * PSIZE - C,
                                            4 * PSIZE - C + 1])}[case]
    clens = np.asarray([0, C, C] if case == "empty-slot"
                       else [C, 1, C - 1] if case == "ragged" else [C] * B)
    P = B * maxp + 1
    bt = np.zeros((B, maxp), np.int32)
    for b in range(B):
        npg = -(-(int(starts[b]) + int(clens[b])) // PSIZE)
        bt[b, :npg] = 1 + b * maxp + np.arange(npg)
    args = (jnp.asarray(rng.normal(size=(B, C, H, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(P, KH, PSIZE, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(P, KH, PSIZE, D)), jnp.float32),
            jnp.asarray(bt), jnp.asarray(starts, jnp.int32),
            jnp.asarray(clens, jnp.int32))
    return args, D ** -0.5


@pytest.mark.parametrize("case", ["ragged", "empty-slot", "group-boundary"])
def test_paged_chunk_tiling_edges(case):
    """Tables whose width the page group does not divide, a slot of
    length 0 (zeros out, its neighbours untouched) and live pages ending
    on a group boundary: every tiling — per head or all heads, 1 to 4
    pages, derived — bit-for-bit the one-page, one-head kernel, which
    matches the ref."""
    args, scale = _edge_case(case)
    base = np.asarray(paged_chunk_attention(
        *args, scale=scale, interpret=True, pages_per_step=1,
        heads_per_step=1))
    ref = paged_chunk_attention_ref(*args, scale=scale)
    np.testing.assert_allclose(base, np.asarray(ref), atol=2e-5, rtol=1e-5)
    if case == "empty-slot":
        assert not base[0].any() and base[1].any()
    for pps, hps in ((2, 1), (3, 1), (2, 4), (3, 2), (4, 4), (None, None)):
        out = paged_chunk_attention(*args, scale=scale, interpret=True,
                                    pages_per_step=pps, heads_per_step=hps)
        assert np.array_equal(np.asarray(out), base), (case, pps, hps)


def test_paged_decode_pages_per_step_bitwise():
    """Same invariant for the [B, H, D] decode kernel."""
    B, H, KH, D, psize, maxp = 3, 4, 2, 16, 8, 4
    rng = np.random.default_rng(11)
    P = B * maxp + 1
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, KH, psize, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, KH, psize, D)), jnp.float32)
    bt = np.zeros((B, maxp), np.int32)
    lengths = np.asarray([psize * maxp, 5, psize + 1], np.int32)
    for b in range(B):
        npg = -(-int(lengths[b]) // psize)
        bt[b, :npg] = 1 + b * maxp + np.arange(npg)
    base = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths),
                           scale=D ** -0.5, interpret=True, pages_per_step=1)
    for pps in (2, 3, 4):
        out = paged_attention(q, kp, vp, jnp.asarray(bt),
                              jnp.asarray(lengths), scale=D ** -0.5,
                              interpret=True, pages_per_step=pps)
        assert np.array_equal(np.asarray(out), np.asarray(base)), pps


def _poisoned_tables_case(tilings):
    """Chunk attention over a clean block table and over the same table
    with every dead entry poisoned: bit-for-bit equal at each (pps, hps)
    of ``tilings``."""
    B, H, KH, D, maxp, C = 2, 4, 2, 16, 4, 4
    args, _ = _chunk_case(B, H, KH, D, maxp, C, 23)
    q, kp, vp, bt, starts, clens = args
    bt_np = np.asarray(bt).copy()
    live = -(-(np.asarray(starts) + np.asarray(clens)) // PSIZE)
    poisoned = bt_np.copy()
    for b in range(B):
        poisoned[b, max(1, live[b]):] = 999_999      # way out of range
    assert not np.array_equal(poisoned, bt_np)
    for pps, hps in tilings:
        kw = dict(scale=D ** -0.5, interpret=True, pages_per_step=pps,
                  heads_per_step=hps)
        clean = paged_chunk_attention(q, kp, vp, jnp.asarray(bt_np), starts,
                                      clens, **kw)
        dirty = paged_chunk_attention(q, kp, vp, jnp.asarray(poisoned),
                                      starts, clens, **kw)
        assert np.array_equal(np.asarray(clean), np.asarray(dirty)), pps
    return q, kp, vp, bt_np, poisoned, starts, clens, D


def test_dead_block_table_entries_never_gathered():
    """Block-table rows past a sequence's live length may hold stale or
    out-of-range page ids (freed pages, preemption leftovers) — the kernels
    must clamp their gathers to the null page, never dereference them.
    Poisoning every dead entry with an id far outside the pool must leave
    the output bit-for-bit unchanged."""
    q, kp, vp, bt_np, poisoned, starts, clens, D = _poisoned_tables_case(
        [(1, 1), (2, 1)])
    # decode kernel too
    lengths = jnp.asarray(np.asarray(starts) + np.asarray(clens), jnp.int32)
    dec_c = paged_attention(q[:, 0], kp, vp, jnp.asarray(bt_np), lengths,
                            scale=D ** -0.5, interpret=True)
    dec_d = paged_attention(q[:, 0], kp, vp, jnp.asarray(poisoned), lengths,
                            scale=D ** -0.5, interpret=True)
    assert np.array_equal(np.asarray(dec_c), np.asarray(dec_d))


@pytest.mark.parametrize("tiling", [(2, 2), (3, 2), (None, None)])
def test_dead_block_table_entries_never_gathered_all_heads(tiling):
    """The poisoned-table invariant with every kv head in one step: the
    (1, KH, psize, D) blocks' null-page clamp holds for whole and ragged
    page groups and for the derived tiling."""
    _poisoned_tables_case([tiling])


def _verify_window_case(tilings):
    """The fused verify window equals the gathered rows of the full-width
    output, and that output is unchanged by it, at each (pps, hps)."""
    B, H, KH, D, maxp, C = 2, 4, 2, 16, 4, 6
    S_w = 3
    args, _ = _chunk_case(B, H, KH, D, maxp, C, 31)
    rng = np.random.default_rng(32)
    widx = jnp.asarray(rng.integers(0, C, size=(B, S_w)), jnp.int32)
    base = None
    for pps, hps in tilings:
        kw = dict(scale=D ** -0.5, interpret=True, pages_per_step=pps,
                  heads_per_step=hps)
        full = paged_chunk_attention(*args, **kw)
        out, win = paged_chunk_attention(*args, logit_index=widx, **kw)
        assert np.array_equal(np.asarray(out), np.asarray(full)), pps
        want = jnp.take_along_axis(full, widx[:, :, None, None], axis=1)
        assert np.array_equal(np.asarray(win), np.asarray(want)), pps
        base = np.asarray(full) if base is None else base
        assert np.array_equal(np.asarray(full), base), (pps, hps)
    return args, widx, D


def test_fused_verify_window_matches_post_gather():
    """logit_index mode: the kernel's fused window output must equal
    gathering the same rows from the full-width output — bitwise — and the
    full-width output itself must be unchanged by the extra operand."""
    args, widx, D = _verify_window_case([(1, 1), (2, 1)])
    # ref agrees with its own gather
    rout, rwin = paged_chunk_attention_ref(*args, scale=D ** -0.5,
                                           logit_index=widx)
    want = jnp.take_along_axis(rout, widx[:, :, None, None], axis=1)
    assert np.array_equal(np.asarray(rwin), np.asarray(want))


@pytest.mark.parametrize("tiling", [(2, 2), (3, 2), (None, None)])
def test_fused_verify_window_all_heads(tiling):
    """The fused window's epilogue loops over every head a step holds:
    window rows and full-width output bit-for-bit the one-head kernel's."""
    _verify_window_case([(1, 1), tiling])


def test_paged_pool_append_quant_matches_f32_within_scale():
    """Quantize-on-append: the dequantized int8 pool must track the f32
    append within each touched page's quantization step (amax / 127), and
    untouched pages keep their bytes and scales."""
    from repro.kernels.paged_attention.ops import paged_pool_append_quant
    from repro.optim.compression import quantize_int8

    psize, KH, D = 4, 2, 8
    rng = np.random.default_rng(5)
    fpool = jnp.asarray(rng.normal(size=(8, KH, psize, D)), jnp.float32)
    qp, sc = quantize_int8(fpool, axis=(2, 3))
    sc = sc[:, :, 0, 0]
    new = jnp.asarray(rng.normal(size=(2, 5, KH, D)), jnp.float32)
    bt = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    starts = jnp.asarray([2, 0], jnp.int32)
    clens = jnp.asarray([5, 3], jnp.int32)
    fref = paged_pool_append(fpool, new, bt, starts, clens)
    qpool, qsc = paged_pool_append_quant(qp, sc, new, bt, starts, clens)
    deq = np.asarray(qpool, np.float32) * np.asarray(qsc)[:, :, None, None]
    fref = np.asarray(fref)
    for page in (1, 2, 3, 4, 5):                    # touched pages
        step = np.abs(fref[page]).max(axis=(1, 2)) / 127.0 + 1e-6
        err = np.abs(deq[page] - fref[page]).max(axis=(1, 2))
        assert (err <= step).all(), (page, err, step)
    for page in (6, 7):                             # untouched pages
        assert np.array_equal(np.asarray(qpool)[page], np.asarray(qp)[page])
        assert np.array_equal(np.asarray(qsc)[page], np.asarray(sc)[page])


def test_kv_page_bytes_int8_capacity_ratio():
    """The int8 pool (pages + f32 scale sidecars) must fit >= 1.9x the
    sequences of the bf16 pool at equal HBM for realistic page geometry."""
    from repro.serving.kv_cache import kv_page_bytes

    for psize, KH, D in [(16, 8, 128), (4, 2, 8), (16, 2, 64)]:
        bf16 = kv_page_bytes(psize, KH, D, "bfloat16")
        i8 = kv_page_bytes(psize, KH, D, "int8")
        assert bf16 == 2 * psize * KH * D * 2
        assert i8 == 2 * (psize * KH * D + KH * 4)
    # the >= 1.9x claim needs the sidecar amortized over a realistic page
    # (psize * head_dim >= ~128 elements per head); toy test pages sit lower
    for psize, KH, D in [(16, 8, 128), (16, 2, 64), (8, 4, 32), (4, 2, 64)]:
        ratio = (kv_page_bytes(psize, KH, D, "bfloat16")
                 / kv_page_bytes(psize, KH, D, "int8"))
        assert ratio >= 1.9, (psize, KH, D, ratio)


_ENGINE_RUNS = {}


def _engine_run(kv_dtype="float32", pages_per_step=None):
    """(engine, tokens per request) of three prompts served greedily by a
    reduced qwen3-1.7b engine, once per (kv_dtype, pages_per_step)."""
    from repro.configs.base import get_model_config, reduced
    from repro.models import api
    from repro.serving import Engine, EngineConfig

    key = (kv_dtype, pages_per_step)
    if key not in _ENGINE_RUNS:
        cfg = reduced(get_model_config("qwen3-1.7b"))
        params = api.model_init(jax.random.key(0), cfg)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
                   for n in (5, 9, 3)]
        ecfg = EngineConfig(num_slots=2, num_pages=32, page_size=4,
                            max_prompt_len=12, max_new_tokens=6,
                            token_budget=16, policy="on_demand",
                            kv_dtype=kv_dtype, compute_dtype="float32",
                            pages_per_step=pages_per_step)
        eng = Engine(cfg, params, ecfg)
        for p in prompts:
            eng.submit(p, 6)
        fin = eng.run()
        assert eng.pool.used_pages == 0
        _ENGINE_RUNS[key] = eng, [list(r.out_tokens)
                                  for r in sorted(fin, key=lambda r: r.id)]
    return _ENGINE_RUNS[key]


def test_engine_int8_and_pages_per_step():
    """End-to-end engine invariants of the new modes: pages_per_step > 1 is
    bit-identical to the classic engine; int8 pools build the 4-tuple
    (pages + scale sidecar) cache, stay pps-invariant, and greedy decode
    tracks the f32 engine within the documented divergence bound (exact
    token match is NOT expected: appends requantize whole pages)."""
    run = _engine_run
    _, base = run(pages_per_step=1)
    _, pps2 = run(pages_per_step=2)
    assert pps2 == base, "pages_per_step changed f32 engine output"
    q8_eng, q8 = run(kv_dtype="int8", pages_per_step=1)
    _, q8pps = run(kv_dtype="int8", pages_per_step=4)
    assert q8pps == q8, "pages_per_step changed int8 engine output"
    leaves = jax.tree.leaves(q8_eng.cache)
    assert any(l.dtype == jnp.int8 for l in leaves), "no int8 pool leaf"
    assert any(l.dtype == jnp.float32 and l.ndim in (2, 3)
               for l in leaves), "no scale sidecar leaf"
    match = np.mean([np.mean([a == b for a, b in zip(x, y)])
                     for x, y in zip(base, q8)])
    # documented bound: an untrained random-weight model is the worst case
    # (near-uniform logits flip argmax on tiny perturbations); trained
    # checkpoints sit far above this
    assert match >= 0.5, f"int8 greedy diverged too far: {match:.2f}"


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_engine_derived_tiling_matches_one_page(kv_dtype):
    """The engine's default (the tiling chunk_tiling derives per chunk
    bucket, all kv heads per step) serves the same tokens as one page per
    step, for f32 and int8 pools."""
    eng, derived = _engine_run(kv_dtype=kv_dtype)
    assert eng.ecfg.pages_per_step is None
    assert derived == _engine_run(kv_dtype=kv_dtype, pages_per_step=1)[1]
