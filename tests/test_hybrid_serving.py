"""The serving engine on a Mamba-2/attention hybrid (a granite-4.0-h-micro
cut to one period of 10 layers at small widths, float32) against the plain
reference in ``hybrid_reference.py``: every position the engine computes,
across chunked prefill, mixed prefill/decode ticks, padded slots, slot
reuse and a preemption with recompute, gives the reference's logits.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_reference as HR
from repro.configs.base import MAMBA, get_model_config
from repro.core.steps import make_ctx
from repro.models import api
from repro.models import ssm
from repro.models.params import cast_tree
from repro.serving import Engine, EngineConfig

# one whole period of granite's layer pattern (layer 5 is attention), at
# widths that keep a tick to milliseconds: 8 Mamba heads of 16, state 16,
# scan chunks of 8 (so a 16-token prefill chunk crosses a scan chunk)
CFG = dataclasses.replace(
    get_model_config("granite-4.0-h-micro"), name="granite-tiny",
    num_layers=10, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256, ssm_state=16, ssm_head_dim=16, ssm_chunk=8,
    query_scale=1 / 16, dtype="float32")

M = {"hidden_size": CFG.d_model, "num_attention_heads": CFG.num_heads,
     "num_key_value_heads": CFG.num_kv_heads,
     "layer_types": ["mamba" if k == MAMBA else "attention"
                     for k in CFG.layer_kinds()],
     "mamba_n_heads": CFG.ssm_expand * CFG.d_model // CFG.ssm_head_dim,
     "mamba_d_head": CFG.ssm_head_dim, "mamba_d_state": CFG.ssm_state,
     "mamba_d_conv": CFG.ssm_conv_width, "rms_norm_eps": CFG.norm_eps,
     "attention_multiplier": CFG.query_scale,
     "embedding_multiplier": CFG.embedding_multiplier,
     "residual_multiplier": CFG.residual_multiplier,
     "logits_scaling": CFG.logits_scaling}

# Both sides compute in float32; what separates them is the order of the
# sums (the chunked dual form against the position-by-position recurrence,
# the paged online softmax against one softmax): the sound runs read
# 8.5e-7 of the largest logit, with the jnp path and with the interpreted
# kernels alike.  2e-4 leaves two hundredfold room for that and lies far
# below what an unmasked padding position does to a slot's state (a whole
# extra token of input: 0.46 of the largest logit).
RTOL = 2e-4


def _params():
    """Seeded weights with every leaf perturbed, so no bias, norm or
    decay sits at its initial constant."""
    p = api.model_init(jax.random.key(7), CFG)
    leaves, tree = jax.tree.flatten(p)
    keys = jax.random.split(jax.random.key(8), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.2 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def _reference_weights(p):
    """The program's parameter tree in the reference's layout."""
    pat = CFG.layer_pattern
    layers = []
    for li, kind in enumerate(CFG.layer_kinds()):
        blk = jax.tree.map(lambda a: a[li // len(pat)],
                           p["blocks"][f"l{li % len(pat)}"])
        lay = {"input_norm": 1 + blk["pre_norm"]["scale"],
               "post_norm": 1 + blk["ffn_norm"]["scale"],
               "gate": blk["mlp"]["wg"], "up": blk["mlp"]["wi"],
               "down": blk["mlp"]["wo"]}
        if kind == MAMBA:
            mb = blk["mamba"]
            lay.update(
                in_z=mb["wz"], in_x=mb["wx"], in_B=mb["wB"], in_C=mb["wC"],
                in_dt=mb["wdt"], dt_bias=mb["dt_bias"], A_log=mb["A_log"],
                D=mb["D"], gnorm=1 + mb["gnorm"], out=mb["wo"],
                conv_w=jnp.concatenate(
                    [mb["conv_x"], mb["conv_B"], mb["conv_C"]], -1),
                conv_b=jnp.concatenate(
                    [mb["conv_x_bias"], mb["conv_B_bias"],
                     mb["conv_C_bias"]]))
        else:
            lay.update({k: blk["attn"][k] for k in ("wq", "wk", "wv", "wo")})
        layers.append(lay)
    return {"embed": p["embed"]["embedding"],
            "final_norm": 1 + p["final_norm"]["scale"], "layers": layers}


def _serve(params, ecfg, prompts, max_new, sit_out=False):
    """Serve ``prompts`` through the engine; besides each tick's sampled
    tokens, the logits of every real chunk position are computed from the
    cache the tick starts from (the step's own paged forward, undonated).
    With ``sit_out`` every tick is preceded by a device step in which each
    slot sits out, as a live slot with no entry in a tick does (the engine
    fills its ``starts`` and ``chunk_lens`` with 0).
    Returns (finished requests by id, {request id: [(position, logits)]},
    the engine)."""
    engine = Engine(CFG, params, ecfg)
    ctx = make_ctx(CFG, None)
    seen = {}
    engine.tick_mix = []          # (prefill slots, decode slots, padded)

    @jax.jit
    def all_logits(params, cache, tokens, starts, clens, bt):
        C = tokens.shape[1]
        idx = jnp.broadcast_to(jnp.arange(C)[None], tokens.shape)
        lg, _ = api.paged_step(cast_tree(params, "float32"), cache, tokens,
                               starts, clens, bt, CFG, ctx, logit_index=idx)
        return lg

    step = engine._step

    def recorded(params, cache, tokens, starts, clens, bt, *rest, **kw):
        if sit_out:
            none = jnp.zeros_like(starts)
            _, _, cache = step(params, cache, jnp.zeros_like(tokens), none,
                               none, bt, *rest, **kw)
        lg = np.asarray(all_logits(params, cache, tokens, starts, clens, bt))
        st, cl = np.asarray(starts), np.asarray(clens)
        live = [(slot, req) for slot, req in engine.sched.running.items()
                if cl[slot]]
        engine.tick_mix.append((
            sum(r.in_prefill for _, r in live),
            sum(not r.in_prefill for _, r in live),
            int(np.sum((cl > 0) & (cl < tokens.shape[1])))))
        for slot, req in engine.sched.running.items():
            for j in range(int(cl[slot])):
                seen.setdefault(req.id, []).append((int(st[slot]) + j,
                                                    lg[slot, j]))
        out = step(params, cache, tokens, starts, clens, bt, *rest, **kw)
        # the served token is the argmax of the last real position's logits
        for slot in engine.sched.running:
            if cl[slot]:
                assert int(out[0][slot]) == int(np.argmax(
                    lg[slot, cl[slot] - 1]))
        return out

    engine._step = recorded
    reqs = [engine.submit(p, n) for p, n in zip(prompts, max_new)]
    engine.run()
    return {r.id: r for r in reqs}, seen, engine


def _worst_gap(params, reqs, seen):
    """The largest |engine - reference| logit over every position the
    engine computed, relative to the largest reference logit."""
    w = _reference_weights(params)
    worst = 0.0
    for rid, rows in seen.items():
        r = reqs[rid]
        stream = np.concatenate([r.prompt, np.asarray(r.out_tokens,
                                                      np.int32)])
        ref = np.asarray(HR.logits(w, stream[:-1], M))
        scale = np.max(np.abs(ref))
        for pos, lg in rows:
            worst = max(worst, float(np.max(np.abs(lg - ref[pos]))) / scale)
    return worst


ECFG = EngineConfig(
    num_slots=3, num_pages=17, page_size=4, max_prompt_len=40,
    max_new_tokens=12, token_budget=16, temperature=0.0, seed=0,
    policy="on_demand", prefix_cache=False, kv_dtype="float32",
    compute_dtype="float32")


def _traffic():
    rng = np.random.default_rng(11)
    lens = [37, 5, 19, 9, 26, 3]
    return ([rng.integers(0, CFG.vocab_size, n).astype(np.int32)
             for n in lens], [12, 10, 12, 7, 12, 9])


@pytest.fixture(scope="module")
def served():
    params = _params()
    prompts, max_new = _traffic()
    reqs, seen, engine = _serve(params, ECFG, prompts, max_new)
    return params, reqs, seen, engine


def test_engine_matches_reference_logits(served):
    params, reqs, seen, engine = served
    assert all(r.finished for r in reqs.values())
    assert _worst_gap(params, reqs, seen) < RTOL


def test_traffic_covers_the_state_paths(served):
    """The run above went through what the per-slot state has to survive:
    prefill chunks longer than a scan chunk and split across ticks, ticks
    mixing prefill and decode, padded slots, more requests than slots
    (slot reuse) and a preemption whose recompute starts from zero."""
    _, reqs, seen, engine = served
    assert engine.preemptions >= 1
    assert any(r.num_preemptions for r in reqs.values())
    assert len(reqs) > ECFG.num_slots
    long = reqs[0]
    assert len(long.prompt) > ECFG.token_budget > CFG.ssm_chunk
    positions = sorted(p for p, _ in seen[0])
    assert positions[0] == 0 and positions.count(0) >= 1
    assert engine.stats.ssm_tokens == engine.stats.real_tokens > 0
    assert any(p and d for p, d, _ in engine.tick_mix)       # mixed ticks
    assert any(pad for _, _, pad in engine.tick_mix)     # padded chunks


def test_kernel_path_matches_reference_logits():
    """The same comparison with the Pallas kernels (SSD scan, paged
    attention) interpreted on the CPU in the step's place: the SSD kernel
    advances its layer's row of the state pool in place and zeroes a
    fresh slot's state as it reads it."""
    from repro.kernels import backend
    was = backend.get_backend()
    backend.set_backend("interpret")
    try:
        params = _params()
        prompts, max_new = _traffic()
        reqs, seen, _ = _serve(params, ECFG, prompts[:3], [6, 5, 6])
    finally:
        backend.set_backend(was)
    assert _worst_gap(params, reqs, seen) < RTOL


def test_a_live_slot_sitting_out_keeps_its_state():
    """A tick in which a started slot has no work (starts and chunk_lens
    0) leaves its state as it was: with such a tick before every real
    one, the served logits still match the reference."""
    params = _params()
    prompts, max_new = _traffic()
    reqs, seen, _ = _serve(params, ECFG, prompts[:3], max_new[:3],
                           sit_out=True)
    assert _worst_gap(params, reqs, seen) < RTOL


def test_unmasked_padding_is_caught(monkeypatch):
    """With dt left nonzero at padded positions a slot's state absorbs the
    padding, and the comparison above fails by far."""
    monkeypatch.setattr(ssm, "padding_masked", lambda dt, chunk_lens: dt)
    params = _params()
    prompts, max_new = _traffic()
    reqs, seen, _ = _serve(params, ECFG, prompts[:3], max_new[:3])
    assert _worst_gap(params, reqs, seen) > 100 * RTOL


def test_ssm_state_bytes_per_tick():
    """``ssm_state_bytes``: live slots x Mamba layers x (f32 SSM state +
    conv tail) x 2, summed over the ticks."""
    params = _params()
    engine = Engine(CFG, params, ECFG)
    engine.submit(np.arange(5, dtype=np.int32), 3)
    engine.submit(np.arange(9, dtype=np.int32), 2)
    engine.step()
    d_in, H, P, N = ssm.ssm_dims(CFG)
    slot = H * P * N * 4 + (CFG.ssm_conv_width - 1) * (d_in + 2 * N) * 4
    assert engine.stats.ssm_state_bytes == 2 * 9 * slot * 2
    assert engine.stats.ssm_tokens == 14
    assert engine.metrics()["counters"]["ssm_state_bytes"] \
        == engine.stats.ssm_state_bytes


@pytest.mark.parametrize("change", [
    {"prefix_cache": True}, {"speculate_k": 2}])
def test_engine_refuses_state_it_cannot_carry(change):
    with pytest.raises(ValueError, match="Mamba"):
        Engine(CFG, None, dataclasses.replace(ECFG, **change))


def test_engine_refuses_a_model_bank_for_mamba():
    from repro.configs.base import HornConfig
    from repro.serving.model_bank import ModelBank

    class Bank:                     # a ModelBank refuses SSM configs itself
        cfg = CFG
        num_submodels = 2

        def device_masks(self):
            return None

    with pytest.raises(ValueError, match="ModelBank"):
        Engine(CFG, None, ECFG, bank=Bank())
    with pytest.raises(ValueError):
        ModelBank(CFG, HornConfig(), 2)
