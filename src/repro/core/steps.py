"""Step factories: pjit train_step / prefill / decode for every architecture.

This is the production entry point used by the launcher, the multi-pod
dry-run, and the benchmarks.  All distribution is expressed as logical-axis
shardings (launch/mesh.py); Horn parallel dropout is threaded through as a
first-class training feature; topology decides how group updates merge.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.core.parallel_dropout import make_horn_state
from repro.launch.mesh import ShardingCtx, sharding_rules, tree_shardings
from repro.models import api
from repro.models import transformer as T
from repro.models.params import cast_tree, param_axes
from repro.optim.sgd import clip_by_global_norm, make_optimizer

f32 = jnp.float32


def make_ctx(model_cfg: ModelConfig, mesh, shape=None) -> ShardingCtx:
    return ShardingCtx(mesh=mesh,
                       rules=sharding_rules(model_cfg, mesh, shape))


# ---------------------------------------------------------------------------
# Train state
# ---------------------------------------------------------------------------
def init_state(key, run: RunConfig):
    """{"params", "opt", "step", "rng"} — call under jit w/ out_shardings
    (or inside jax.eval_shape for the dry run)."""
    params = api.model_init(key, run.model)
    params = cast_tree(params, run.param_dtype)
    opt_init, _ = make_optimizer(run.optimizer)
    return {
        "params": params,
        "opt": opt_init(params),
        "step": jnp.zeros((), jnp.int32),
        "rng": jax.random.key_data(jax.random.key(run.seed)),
    }


def state_axes(run: RunConfig):
    paxes = api.model_axes(run.model)
    opt_init, _ = make_optimizer(run.optimizer)
    # optimizer-state leaves mirror param sharding (ZeRO-style: the "parameter
    # server" state lives wherever the param shard lives)
    if run.optimizer == "sgdm":
        opt_axes = {"mom": paxes}
    else:
        opt_axes = {"m": paxes, "v": paxes, "t": ()}
    return {"params": paxes, "opt": opt_axes, "step": (), "rng": (None,)}


def state_shardings(run: RunConfig, mesh):
    ctx = make_ctx(run.model, mesh, run.shape)
    return tree_shardings(state_axes(run), ctx)


# ---------------------------------------------------------------------------
# Input specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------
def batch_axes(run: RunConfig) -> Dict[str, Tuple]:
    cfg = run.model
    ax: Dict[str, Tuple] = {"tokens": ("batch", "seq"),
                            "labels": ("batch", "seq")}
    if cfg.is_encoder_decoder:
        ax["frames"] = ("batch", None, None)
    if cfg.num_patches:
        ax["patch_embeds"] = ("batch", None, None)
    return ax


def input_specs(run: RunConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    """Weak-type-correct, shardable stand-ins; no device allocation."""
    cfg, shape = run.model, run.shape
    B, S = shape.global_batch, shape.seq_len
    text = S - (cfg.num_patches if cfg.num_patches else 0)
    sd = jax.ShapeDtypeStruct
    specs = {"tokens": sd((B, text), jnp.int32),
             "labels": sd((B, text), jnp.int32)}
    if cfg.is_encoder_decoder:
        specs["frames"] = sd((B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    if cfg.num_patches:
        specs["patch_embeds"] = sd((B, cfg.num_patches, cfg.d_model),
                                   jnp.bfloat16)
    if shape.kind != "train":
        specs.pop("labels")
    return specs


def batch_shardings(run: RunConfig, mesh):
    ctx = make_ctx(run.model, mesh, run.shape)
    ax = batch_axes(run)
    if run.shape.kind != "train":
        ax.pop("labels", None)
    return {k: ctx.sharding(*v) for k, v in ax.items()}


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------
def make_train_step(run: RunConfig, mesh):
    """Returns (jitted_step, shardings dict) — step(state, batch) -> (state, metrics)."""
    cfg = run.model
    ctx = make_ctx(cfg, mesh, run.shape)
    _, opt_update = make_optimizer(run.optimizer)
    dp = ctx.dp_size

    def loss_fn(params, batch, rng, step):
        horn = make_horn_state(jax.random.wrap_key_data(rng), run.horn, dp, step)
        return api.model_loss(params, batch, cfg, ctx, horn=horn,
                              remat=run.remat != "none")

    def train_step(state, batch):
        params, rng, step = state["params"], state["rng"], state["step"]
        cparams = cast_tree(params, run.compute_dtype)
        M = max(1, run.microbatches)
        if M == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(cparams, batch, rng, step)
        else:
            def split(x):
                return x.reshape((M, x.shape[0] // M) + x.shape[1:])
            mb = jax.tree.map(split, batch)

            def acc_fn(carry, mb_i):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    cparams, mb_i, rng, step)
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), m

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, f32), cparams)
            (grads, loss), metrics = jax.lax.scan(
                acc_fn, (g0, jnp.zeros((), f32)), mb)
            grads = jax.tree.map(lambda g: g / M, grads)
            loss = loss / M
            metrics = jax.tree.map(lambda x: jnp.mean(x, 0), metrics)

        grads, gnorm = clip_by_global_norm(grads, 1.0)
        new_params, new_opt = opt_update(
            grads, state["opt"], params, lr=run.learning_rate,
            momentum=run.momentum, weight_decay=run.weight_decay
        ) if run.optimizer == "sgdm" else opt_update(
            grads, state["opt"], params, lr=run.learning_rate,
            weight_decay=run.weight_decay)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        new_state = {"params": new_params, "opt": new_opt,
                     "step": step + 1, "rng": rng}
        return new_state, metrics

    s_shard = tree_shardings(state_axes(run), ctx)
    b_shard = batch_shardings(run, mesh)
    jitted = jax.jit(train_step,
                     in_shardings=(s_shard, b_shard),
                     out_shardings=(s_shard, None),
                     donate_argnums=(0,))
    return jitted, {"state": s_shard, "batch": b_shard}


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------
def make_prefill_step(run: RunConfig, mesh):
    cfg = run.model
    ctx = make_ctx(cfg, mesh, run.shape)

    def prefill_step(params, batch):
        cparams = cast_tree(params, run.compute_dtype)
        logits, cache, enc = api.prefill(cparams, batch, cfg, ctx)
        return logits, cache, enc

    paxes = api.model_axes(cfg)
    p_shard = tree_shardings(paxes, ctx)
    b_shard = batch_shardings(run, mesh)
    jitted = jax.jit(prefill_step, in_shardings=(p_shard, b_shard),
                     out_shardings=None)
    return jitted, {"params": p_shard, "batch": b_shard}


def decode_cache_specs(run: RunConfig):
    """ShapeDtypeStructs for the decode cache at this shape cell."""
    cfg, shape = run.model, run.shape
    return jax.eval_shape(
        lambda: T.init_cache(cfg, shape.global_batch, shape.seq_len))


def make_decode_step(run: RunConfig, mesh):
    cfg = run.model
    ctx = make_ctx(cfg, mesh, run.shape)

    def decode_step(params, cache, tokens, pos, encoder_out=None):
        cparams = cast_tree(params, run.compute_dtype)
        return api.decode_step(cparams, cache, tokens, pos, cfg, ctx,
                               encoder_out=encoder_out)

    paxes = api.model_axes(cfg)
    p_shard = tree_shardings(paxes, ctx)
    from repro.launch.mesh import is_axes_leaf
    cache_struct = decode_cache_specs(run)
    c_axes = T.cache_logical_axes(cfg, cache_struct)
    c_shard = jax.tree.map(lambda ax: ctx.sharding(*ax), c_axes,
                           is_leaf=is_axes_leaf)
    tok_shard = ctx.sharding("batch", None)
    enc_shard = ctx.sharding("batch", None, None) if cfg.is_encoder_decoder else None
    in_sh = (p_shard, c_shard, tok_shard, None) + (
        (enc_shard,) if cfg.is_encoder_decoder else ())
    jitted = jax.jit(decode_step, in_shardings=in_sh,
                     out_shardings=(None, c_shard),
                     donate_argnums=(1,))
    return jitted, {"params": p_shard, "cache": c_shard,
                    "cache_struct": cache_struct}


# ---------------------------------------------------------------------------
# Unified paged serving step (continuous batching — see repro.serving)
# ---------------------------------------------------------------------------
def make_unified_paged_step(run: RunConfig, mesh, *, num_pages: int,
                            page_size: int, temperature: float = 0.0,
                            bank_masks=None, kv_dtype=jnp.bfloat16):
    """THE serving step: one jitted call per engine tick, whatever the tick
    holds.  The scheduler packs a token budget with a mix of decode tokens
    (one per running slot) and prompt chunks from admitting requests; the
    step appends every token's K/V to the page pool in place, runs chunked
    paged attention over the pool, and samples the next token for every
    slot on device (vectorized fold_in per (request, step) keys — no
    per-slot host loop, one device round-trip per tick).

    step(params, cache, tokens [B, C], starts [B], chunk_lens [B],
         block_tables [B, maxp], req_ids [B], sample_steps [B],
         submodel_ids [B], seg_ids [B], vote_flags [B], root_key)
      -> (sampled [B] int32, cache)

    Only the sampled tokens leave the step — returning the [B, V] logits
    would materialize a multi-MB output buffer per tick that no caller
    reads (at vocab 150k+ it would dwarf the transfer of everything else).

    ``C`` is the tick's chunk width: a decode-only tick runs at C == 1 (the
    classic paged-decode cell, bit-compatible with it); ticks carrying
    prompt chunks run at power-of-two C buckets (jit caches one executable
    per width).  The pool is donated so the K/V append is in-place; so is
    a hybrid's per-slot Mamba state (the cache's "ssm" entry), which each
    Mamba layer's SSD scan continues from and writes back in place (zero
    state where a slot's chunk starts at position 0).  Greedy
    when ``temperature <= 0``; otherwise categorical with per-slot keys
    ``fold_in(fold_in(root_key, req_id), step)`` — no key is ever reused
    across requests or steps.  Idle slots (chunk_len 0) and mid-prompt
    chunks produce samples the engine simply discards.

    Multi-submodel serving (``bank_masks`` = a ModelBank's mask tensors,
    leading axis G): each slot's circuit masks are gathered by
    ``submodel_ids`` *inside* the step, so decode tokens and prompt chunks
    from different sub-models co-batch in one jitted call — no per-submodel
    step, no recompile on routing decisions.  ``seg_ids`` [B] groups slots
    into ensembles (each slot carries its group leader's slot index; solo
    slots carry their own): per-step logits are segment-combined on device
    before sampling — mean-logit (members share the leader's sampling key,
    so one categorical draw decides the group) or, where ``vote_flags`` is
    set, a majority vote over member samples (ties -> lowest token id).
    Solo slots pass through both paths unchanged (a segment of one).

    ``ensembles`` is a static per-tick flag (two jit-compiled variants,
    dispatched host-side): ticks with no ensemble group in flight
    (routing-only serving, the common case) skip the combine machinery
    entirely — no [B, V] one-hot, no second sampling pass — at the cost of
    one extra compile per chunk-width bucket the first time an ensemble
    tick hits it.

    Speculative verify (``draft_lens``/``draft_probs``): a speculating
    slot's chunk is [pending token, d_1 .. d_dl] — the last committed
    token plus ``draft_lens[b]`` tokens a draft circuit proposed — and the
    step scores a *verify window* of S_v = draft_probs.shape[1] + 1
    positions per slot in the same single call (S_v is static via the
    ``draft_probs`` shape; the non-speculative engine always passes
    S_v == 1, which reduces bit-exactly to the classic last-position
    sampling path).  Greedy (temperature <= 0) accepts the longest prefix
    of drafts matching the parent argmax and emits the parent's token at
    the first mismatch (or the bonus token after d_dl when all match).
    With temperature > 0 the step runs standard rejection sampling against
    the draft distribution ``draft_probs`` (accept d_j with prob
    min(1, p_j(d_j)/q_j(d_j)); on rejection resample from
    norm(max(p - q, 0))) — byte-reproducible: every random draw folds in
    (req_id, sample_step + j) exactly like plain sampling, with a further
    fold_in(1)/fold_in(2) separating the accept-uniform and the resample
    from the bonus categorical.  Returns (sampled [B], accepted [B],
    cache): ``accepted[b]`` drafts are good, ``sampled[b]`` is the one
    verified-not-drafted token that follows them.  Non-speculating slots
    (draft_lens == 0, including every ensemble member) report accepted 0
    and sample at their last valid position as always.
    """
    cfg = run.model
    ctx = make_ctx(cfg, mesh, run.shape)

    def sample(logits, req_ids, sample_steps, root_key):
        if temperature > 0:
            keys = jax.vmap(lambda r, s: jax.random.fold_in(
                jax.random.fold_in(root_key, r), s))(req_ids, sample_steps)
            return jax.vmap(jax.random.categorical)(
                keys, logits.astype(f32) / temperature)
        return jnp.argmax(logits, axis=-1)

    def verify(logits_w, tokens, draft_lens, draft_probs, req_ids,
               sample_steps, root_key):
        """Accept/advance every slot against its verify window.

        logits_w: [B, S_v, V] — window position j holds the parent's
        distribution for the token AFTER chunk position j (speculating
        slots: chunk == window; plain slots: the window right-aligns on
        the last valid position, only j == S_v - 1 is meaningful).
        Returns (sampled [B], accepted [B])."""
        B, S_v, _ = logits_w.shape
        dl = draft_lens
        drafts = tokens[:, 1:S_v]                          # [B, S_v-1]
        tgt = jnp.argmax(logits_w, axis=-1)                # [B, S_v]
        if temperature <= 0:
            ok = (tgt[:, :S_v - 1] == drafts) \
                & (jnp.arange(S_v - 1)[None, :] < dl[:, None])
            acc = jnp.cumprod(ok.astype(jnp.int32), axis=-1).sum(axis=-1)
            # acc accepted drafts put the next decision at window position
            # acc — the correction when acc < dl, the bonus when acc == dl
            pick = jnp.where(dl > 0, acc, S_v - 1)
            sampled = jnp.take_along_axis(tgt, pick[:, None], axis=1)[:, 0]
            return sampled, acc
        lw = logits_w.astype(f32) / temperature
        kb = jax.vmap(lambda r: jax.random.fold_in(root_key, r))(req_ids)
        if S_v == 1:              # no drafts anywhere: classic sampling
            kp = jax.vmap(jax.random.fold_in)(kb, sample_steps)
            return jax.vmap(jax.random.categorical)(kp, lw[:, 0]), \
                jnp.zeros((B,), jnp.int32)
        p_w = jax.nn.softmax(lw, axis=-1)                  # [B, S_v, V]
        # the accept-uniform for draft j folds in the step the token would
        # occupy (sample_step + j), then salt 1 — never colliding with the
        # categorical draw at that step (no salt) or the resample (salt 2)
        jj = jnp.arange(S_v - 1)
        ukeys = jax.vmap(jax.vmap(
            lambda k, s: jax.random.fold_in(jax.random.fold_in(k, s), 1),
            in_axes=(None, 0)))(kb, sample_steps[:, None] + jj[None, :])
        u = jax.vmap(jax.vmap(jax.random.uniform))(ukeys)  # [B, S_v-1]
        pd = jnp.take_along_axis(p_w[:, :S_v - 1], drafts[..., None],
                                 axis=-1)[..., 0]
        qd = jnp.take_along_axis(draft_probs, drafts[..., None],
                                 axis=-1)[..., 0]
        ok = (u * jnp.maximum(qd, 1e-30) < pd) \
            & (jj[None, :] < dl[:, None])
        acc = jnp.cumprod(ok.astype(jnp.int32), axis=-1).sum(axis=-1)
        rejected = (dl > 0) & (acc < dl)
        pick = jnp.where(dl > 0, acc, S_v - 1)
        # the bonus/plain draw: categorical on the raw scaled logits with
        # the classic (req_id, step) key — at S_v == 1 this IS the
        # non-speculative sampling path, bit for bit
        kp = jax.vmap(jax.random.fold_in)(
            kb, sample_steps + jnp.where(dl > 0, pick, 0))
        lp = jnp.take_along_axis(
            lw, pick[:, None, None], axis=1)[:, 0]         # [B, V]
        bonus = jax.vmap(jax.random.categorical)(kp, lp)
        # the rejection resample: norm(max(p - q, 0)) at the first
        # rejected position (falls back to p when the residual vanishes —
        # q >= p everywhere means the accept test already passed a.s.)
        ridx = jnp.minimum(pick, S_v - 2)
        q_r = jnp.take_along_axis(
            draft_probs, ridx[:, None, None], axis=1)[:, 0]
        p_r = jnp.take_along_axis(p_w, ridx[:, None, None], axis=1)[:, 0]
        res = jnp.maximum(p_r - q_r, 0.0)
        res = jnp.where(res.sum(-1, keepdims=True) > 0, res, p_r)
        rkeys = jax.vmap(lambda k: jax.random.fold_in(k, 2))(kp)
        rtok = jax.vmap(jax.random.categorical)(
            rkeys, jnp.log(jnp.maximum(res, 1e-30)))
        sampled = jnp.where(rejected, rtok, bonus)
        return sampled, acc

    def unified_step(params, cache, tokens, starts, chunk_lens, block_tables,
                     req_ids, sample_steps, submodel_ids, seg_ids,
                     vote_flags, draft_lens, draft_probs, root_key, *,
                     ensembles=False):
        cparams = cast_tree(params, run.compute_dtype)
        serve_masks = None
        if bank_masks is not None:
            serve_masks = jax.tree.map(lambda m: m[submodel_ids], bank_masks)
        B = tokens.shape[0]
        S_v = draft_probs.shape[1] + 1
        j = jnp.arange(S_v)[None, :]
        cl = chunk_lens[:, None]
        # speculating slots verify their whole chunk (window == chunk,
        # left-aligned — a slot clamped below the tick's draft length just
        # ignores the tail); everyone else right-aligns on the last valid
        # position so j == S_v - 1 is the classic sampling position
        widx = jnp.where(draft_lens[:, None] > 0,
                         jnp.minimum(j, jnp.maximum(cl - 1, 0)),
                         jnp.clip(cl - S_v + j, 0, tokens.shape[1] - 1))
        logits_w, new_cache = api.paged_step(
            cparams, cache, tokens, starts, chunk_lens, block_tables,
            cfg, ctx, serve_masks=serve_masks, logit_index=widx)
        sampled, accepted = verify(logits_w, tokens, draft_lens,
                                   draft_probs, req_ids, sample_steps,
                                   root_key)
        if bank_masks is not None and ensembles:
            # ensemble members never speculate (draft_lens == 0): combine
            # their last-position logits exactly as before and let the
            # verify result stand for speculating / solo slots
            lf = jnp.take_along_axis(
                logits_w, jnp.where(draft_lens > 0, 0, S_v - 1)
                [:, None, None], axis=1)[:, 0].astype(f32)
            ones = jnp.ones((B,), f32)
            counts = jax.ops.segment_sum(ones, seg_ids, num_segments=B)
            mean = jax.ops.segment_sum(lf, seg_ids, num_segments=B) \
                / jnp.maximum(counts, 1.0)[:, None]
            # mean-logit: ensemble members carry the leader's req_id, so
            # identical keys sample the identical token from identical
            # combined logits; a segment of one divides by 1.0 (exact), so
            # a solo slot sharing the tick samples the same token either way
            mean_tok = sample(mean[seg_ids], req_ids, sample_steps, root_key)
            own_tok = sample(lf, req_ids, sample_steps, root_key)
            votes = jax.ops.segment_sum(
                jax.nn.one_hot(own_tok, lf.shape[-1], dtype=f32),
                seg_ids, num_segments=B)
            vote_tok = jnp.argmax(votes, axis=-1)[seg_ids]
            combined = jnp.where(vote_flags, vote_tok, mean_tok)
            sampled = jnp.where(draft_lens > 0, sampled, combined)
            accepted = jnp.where(draft_lens > 0, accepted, 0)
        return sampled.astype(jnp.int32), accepted.astype(jnp.int32), \
            new_cache

    paxes = api.model_axes(cfg)
    p_shard = tree_shardings(paxes, ctx)
    cache_struct = jax.eval_shape(
        lambda: T.init_paged_cache(cfg, num_pages, page_size,
                                   dtype=kv_dtype,
                                   num_slots=run.shape.global_batch,
                                   conv_dtype=run.compute_dtype))
    variants = {
        flag: jax.jit(partial(unified_step, ensembles=flag),
                      in_shardings=(p_shard,) + (None,) * 13,
                      out_shardings=None, donate_argnums=(1,))
        for flag in (False, True)}

    def step(*args, ensembles: bool = False):
        return variants[ensembles](*args)

    # the observability profiler watches each variant's compile cache
    # and AOT-lowers them for cost_analysis attribution
    step.variants = variants

    return step, {"params": p_shard, "cache_struct": cache_struct}


def make_draft_spec_step(run: RunConfig, mesh, *, num_pages: int,
                         page_size: int, k: int, temperature: float = 0.0,
                         draft_salt: int = 0x5bec):
    """One jitted *draft tick* for speculative decoding: catch the draft
    circuit up on each slot's committed stream and autoregressively propose
    ``k`` tokens, all inside a single device call.

    step(params, cache, tokens [B, C], starts [B], chunk_lens [B],
         block_tables [B, maxp], req_ids [B], sample_steps [B], root_key)
      -> (drafts [B, k] int32, draft_probs [B, k, Vq] f32, cache)

    ``tokens`` is the catch-up chunk: the committed tokens the draft has
    not yet written K/V for, ending with the pending token (the one the
    parent will decode next), so the chunk's last-position logits propose
    d_1.  The remaining k - 1 proposals run as a ``lax.scan`` of C == 1
    paged steps feeding each draft back in — K sequential *model* steps
    but ONE host dispatch, which is what makes drafting cheaper than the
    K parent ticks it replaces.  K/V for d_1 .. d_{k-1} is appended to the
    draft's own page pool as it goes (d_k's K/V is written by the next
    tick's catch-up, exactly like the engine's pending token).

    Greedy drafts are argmax and ``draft_probs`` is a [B, k, 1] dummy;
    with temperature > 0 each proposal is a categorical draw under a
    *draft-private* key chain (root folded with ``draft_salt``, then
    (req_id, sample_step + i)) — independent of every verify-side draw by
    construction — and ``draft_probs`` carries the full proposal
    distribution q_i the verifier's rejection sampler needs.  ``k`` is
    static: the engine builds one step per draft length it actually runs
    (jit then caches per catch-up-width bucket)."""
    cfg = run.model
    ctx = make_ctx(cfg, mesh, run.shape)

    def sample(logits, req_ids, steps, droot):
        lf = logits.astype(f32)
        if temperature > 0:
            keys = jax.vmap(lambda r, s: jax.random.fold_in(
                jax.random.fold_in(droot, r), s))(req_ids, steps)
            tok = jax.vmap(jax.random.categorical)(keys, lf / temperature)
            q = jax.nn.softmax(lf / temperature, axis=-1)
        else:
            tok = jnp.argmax(lf, axis=-1)
            q = jnp.zeros(lf.shape[:-1] + (1,), f32)
        return tok.astype(jnp.int32), q

    def draft_step(params, cache, tokens, starts, chunk_lens, block_tables,
                   req_ids, sample_steps, root_key):
        cparams = cast_tree(params, run.compute_dtype)
        droot = jax.random.fold_in(root_key, draft_salt)
        logits, cache = api.paged_step(
            cparams, cache, tokens, starts, chunk_lens, block_tables,
            cfg, ctx)
        d0, q0 = sample(logits, req_ids, sample_steps, droot)
        if k == 1:
            return d0[:, None], q0[:, None], cache

        def body(carry, i):
            cache, tok, pos = carry
            lg, cache = api.paged_step(
                cparams, cache, tok[:, None], pos,
                jnp.ones_like(pos), block_tables, cfg, ctx)
            nt, q = sample(lg, req_ids, sample_steps + i, droot)
            return (cache, nt, pos + 1), (nt, q)

        (cache, _, _), (ds, qs) = jax.lax.scan(
            body, (cache, d0, starts + chunk_lens), jnp.arange(1, k))
        drafts = jnp.concatenate([d0[:, None], jnp.moveaxis(ds, 0, 1)], 1)
        probs = jnp.concatenate([q0[:, None], jnp.moveaxis(qs, 0, 1)], 1)
        return drafts, probs, cache

    paxes = api.model_axes(cfg)
    p_shard = tree_shardings(paxes, make_ctx(cfg, mesh, run.shape))
    return jax.jit(draft_step, in_shardings=(p_shard,) + (None,) * 8,
                   out_shardings=None, donate_argnums=(1,))


def make_page_copy_step():
    """Device-side KV page copy for copy-on-write: ``copy(cache, src, dst)``
    duplicates page ``src[i]`` into page ``dst[i]`` across every layer's
    K and V pool in one donated (in-place) call.

    ``src``/``dst`` are equal-length int32 arrays; callers pad them to a
    power-of-two width with (0, 0) pairs — copying the null page onto
    itself is a no-op by construction — so jit compiles one executable per
    width bucket, not per COW event.  Paged-cache leaves are
    [num_pages, KH, psize, D] pools or [num_pages, KH] int8-mode scale
    sidecars (remainder layers), each optionally prefixed by the scanned-
    superblock [R, ...] axis — the page axis is 0 for even rank, 1 for odd,
    and scale rows travel with their pages (COW / prefix-cache publishes
    never split a page from its scale)."""

    @partial(jax.jit, donate_argnums=(0,))
    def copy(cache, src, dst):
        def cp(x):
            if x.ndim % 2 == 0:              # [P, ...] pool or scale leaf
                return x.at[dst].set(x[src])
            return x.at[:, dst].set(x[:, src])   # [R, P, ...] scanned stack
        return jax.tree.map(cp, cache)

    return copy


def decode_input_specs(run: RunConfig):
    """(tokens, pos, [encoder_out]) ShapeDtypeStructs for decode cells."""
    cfg, shape = run.model, run.shape
    B = shape.global_batch
    sd = jax.ShapeDtypeStruct
    out = {"tokens": sd((B, 1), jnp.int32), "pos": sd((), jnp.int32)}
    if cfg.is_encoder_decoder:
        out["encoder_out"] = sd((B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    return out
