"""Continuous-batching inference engine over the paged KV cache.

One engine tick = one jitted device call, whatever the tick holds.  The
scheduler fills a fixed *token budget* with a mix of decode tokens (one per
running slot) and prompt chunks from admitting requests; the unified paged
step appends every token's K/V to the page pool in place, runs chunked paged
attention, and returns on-device-sampled next tokens for every slot.  A
32k-token admission therefore costs each in-flight request at most
``token_budget`` tokens of latency per tick — never a monolithic prefill
stall.

Positions are per-slot: slot b's chunk starts at the number of KV tokens it
already has in pages, so a fresh 7-token request and a 900-token-deep one
advance in the same device step.  Sampling keys are derived per (request,
step) via vectorized fold_in inside the step — no key is ever reused across
requests or steps, and no per-slot host loop touches the logits.

Pool pressure under the ``on_demand`` policy no longer kills the server:
the engine preempts the youngest running sequence back to the head of the
waiting queue (page references released, KV recomputed on re-admission
through the same chunked-prefill path) and degrades to lower throughput.
``EngineOOM`` is reserved for genuinely unservable states — a single
sequence that can never fit the pool even alone.

Chunk widths are bucketed to powers of two so the unified step compiles
once per width, not once per chunk length; a decode-only tick runs the
C == 1 cell, bit-compatible with the classic paged-decode step.

Multi-submodel serving (Horn §2 at inference): pass a ``ModelBank`` and the
engine serves its G parallel circuits behind the same scheduler and page
pool — a ``Router`` tags each request with a ``submodel_id``, the unified
step gathers that slot's fixed circuit masks on device, and tokens from
different sub-models co-batch in one tick.  ``submit(..., ensemble=...)``
fans one prompt across all G circuits in lockstep and combines their
per-step logits on device (mean-logit or majority vote) before sampling —
the paper's collective ensemble served as one request.

Prefix caching + copy-on-write (``EngineConfig.prefix_cache``, default on):
full prompt pages are content-addressed by a rolling hash chained over
their token blocks, retired pages are held (LRU) by the pool's
``PrefixCache``, and admission adopts the longest cached page-prefix so
chunked prefill starts mid-prompt — a shared system prompt is prefilled
once across millions of requests.  An ensemble's shared prompt context
(positions [0, prompt_len - 1), dense-parent encoded — circuit masks
engage at the last prompt token) is the degenerate case: the leader
prefills it once, every member forks the pages (refcount G), and only
per-member decode tails copy-on-write on divergence — ensemble prefill
costs ~1/G of the re-prefill path, byte-identically.

The host->device block-table mirror is synced incrementally: only rows
whose page tables changed since the last device call are re-uploaded
(steady decode inside a page uploads nothing).

Speculative decoding (``EngineConfig.speculate_k`` + a
``ModelBank.draft_model``): each speculating decode slot first runs the
materialized draft circuit for up to K tokens — one jitted draft call per
tick, batched across slots, against the draft's private page pool
(``serving/speculative.py``) — then the parent verifies all K+1 positions
inside the SAME single token-budget call the tick would have made anyway
(a verify chunk is a K+1-token chunk through the existing chunk-append
path, scored over a window of logits).  Greedy acceptance is the longest
draft prefix matching the parent argmax — byte-identical to sequential
greedy decode — and temperature > 0 runs on-device rejection sampling
against the draft distribution, byte-reproducible per (req_id,
sample_step) fold_in.  A rejected tail rolls back by releasing page
references (``PagePool.truncate_seq``), never by copying.  The budget
meters parent compute: a speculating slot consumes 1 + K verified tokens,
drafted tokens are free."""
from __future__ import annotations

import dataclasses
import gc
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ATTN, LOCAL, MAMBA, HornConfig,
                                ModelConfig, RunConfig, ShapeConfig)
from repro.core import steps as S
from repro.models import transformer as T
from repro.serving.block_table import (BlockTableMirror, marshal_i32,
                                       pow2_bucket)
from repro.serving.kv_cache import PagePool, PagePoolOOM
from repro.serving.model_bank import DraftModel, ModelBank
from repro.serving.observability import EngineStats, Telemetry
from repro.serving.router import Router
from repro.serving.scheduler import (EnsembleGroup, FCFSScheduler, Request,
                                     speculative_draft_len)
from repro.serving.speculative import DraftRunner

COMBINES = ("mean_logit", "majority_vote")

_annotate = jax.profiler.TraceAnnotation


def _unified_step_key(args, kw):
    """Compile-cell label for the profiler: the unified step
    specialises on the chunk-width bucket (tokens arg), the verify
    window extent (draft_probs arg), and the static ensembles flag —
    everything else is shape-fixed per engine.  Cheap: three attribute
    reads per tick, no pytree walk."""
    c = args[2].shape[1]                  # tokens [B, C]
    sv = args[12].shape[1] + 1            # draft_probs [B, S_v - 1, V]
    ens = bool(kw.get("ensembles", False))
    return (c, sv, ens), f"C={c},Sv={sv},ens={ens}"


class EngineOOM(RuntimeError):
    """The page pool cannot serve a sequence even after preempting every
    other running sequence (e.g. one request's context alone exceeds the
    pool).  The engine state is left consistent; callers should surface
    this and exit cleanly."""


@dataclass(frozen=True)
class EngineConfig:
    num_slots: int = 8               # decode batch width
    num_pages: int = 256             # pool size (page 0 is the null page)
    page_size: int = 16              # tokens per KV page
    max_prompt_len: int = 256
    max_new_tokens: int = 64         # default + hard cap per request
    token_budget: int = 256          # tokens per unified tick (decode+chunks)
    temperature: float = 0.0
    seed: int = 0
    policy: str = "reserve"          # "reserve" | "on_demand" (see scheduler)
    eos_id: Optional[int] = None
    kv_dtype: str = "bfloat16"       # page-pool dtype: float32 (parity
                                     # tests) | bfloat16 | int8 (quantized
                                     # pools + per-(page, head) f32 scale
                                     # sidecars — ~2x pages at equal HBM,
                                     # bounded-error decode)
    compute_dtype: str = "bfloat16"  # model compute dtype
    pages_per_step: Optional[int] = None  # KV pages per paged-kernel grid
                                     # step (None: derived from the call's
                                     # shapes, kernel.chunk_tiling; output
                                     # is bit-identical across values)
    prefix_cache: bool = True        # content-addressed page reuse + COW
                                     # (off: PR-3-style per-request prefill)
    speculate_k: int = 0             # draft tokens verified per decode tick
                                     # (0: no speculation; > 0 needs a
                                     # DraftModel passed to the Engine)

    @property
    def max_model_len(self) -> int:
        return self.max_prompt_len + self.max_new_tokens


# tick-entry record: what one slot contributes to this tick's device call
@dataclass
class _Entry:
    req: Request
    start: int                       # KV tokens already in pages
    tokens: np.ndarray               # [chunk_len] int32
    chunk_len: int
    sample_step: int                 # fold_in step for the sampling key
    record: bool                     # keep the sampled token?
    mask_id: int                     # circuit-mask row the step gathers for
                                     # this chunk (the dense sentinel for an
                                     # ensemble's shared prompt context)
    draft_len: int = 0               # drafted tokens this chunk verifies
                                     # (tokens[1:1+draft_len] are proposals)


class Engine:
    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 mesh=None, *, bank: Optional[ModelBank] = None,
                 router: Optional[Router] = None,
                 draft: Optional[DraftModel] = None,
                 telemetry: Optional[Telemetry] = None):
        bad = [k for k in cfg.layer_pattern
               if k not in (ATTN, LOCAL, MAMBA)]
        if bad or cfg.is_encoder_decoder or cfg.num_patches or cfg.learned_pos:
            raise ValueError(
                f"paged serving supports decoder-only attention and Mamba "
                f"LMs; {cfg.name} has "
                f"{bad or 'an unsupported input frontend'}")
        self.mamba_layers = sum(k == MAMBA for k in cfg.layer_kinds())
        if self.mamba_layers:
            # state bytes one live slot's scans read and write in a tick:
            # the f32 SSM state and the conv tail of every Mamba layer,
            # each way (transformer.init_paged_cache's "ssm")
            from repro.models.ssm import ssm_dims
            d_in, H, P, N = ssm_dims(cfg)
            tail = (cfg.ssm_conv_width - 1) * (d_in + 2 * N) \
                * jnp.dtype(ecfg.compute_dtype).itemsize
            self._ssm_slot_tick_bytes = \
                2 * self.mamba_layers * (H * P * N * 4 + tail)
            # a Mamba layer's state is one running sum per slot: nothing
            # can adopt it (prefix pages), roll it back (a rejected draft
            # tail) or share it between circuits
            held = [what for what, on in (
                ("prefix_cache", ecfg.prefix_cache),
                ("speculation", ecfg.speculate_k > 0 or draft is not None),
                ("a ModelBank", bank is not None)) if on]
            if held:
                raise ValueError(
                    f"{cfg.name} has Mamba layers, whose per-slot state "
                    f"cannot carry {', '.join(held)}")
        if ecfg.max_prompt_len % ecfg.page_size:
            raise ValueError("max_prompt_len must be page-aligned")
        if ecfg.token_budget < ecfg.num_slots:
            raise ValueError(
                f"token_budget ({ecfg.token_budget}) must cover one decode "
                f"token per slot ({ecfg.num_slots})")
        self.cfg, self.ecfg = cfg, ecfg
        self.params = params
        self.bank = bank
        if bank is not None:
            if bank.cfg != cfg:
                raise ValueError(
                    f"bank was built for {bank.cfg.name}, engine serves "
                    f"{cfg.name}")
            self.router = router if router is not None \
                else Router(bank.num_submodels)
            if self.router.num_submodels != bank.num_submodels:
                raise ValueError(
                    f"router spans {self.router.num_submodels} submodels, "
                    f"bank holds {bank.num_submodels}")
        elif router is not None:
            raise ValueError("a Router needs a ModelBank to route over")
        else:
            self.router = None
        self.pool = PagePool(ecfg.num_pages, ecfg.page_size,
                             prefix_cache=ecfg.prefix_cache)
        self.sched = FCFSScheduler(ecfg.num_slots, self.pool,
                                   policy=ecfg.policy)
        self.max_pages_per_seq = self.pool.pages_for(ecfg.max_model_len)
        # mask row the unified step gathers for dense-parent chunks (an
        # ensemble's shared prompt context): device_masks pads an all-ones
        # row at index G
        self._dense_mask_id = bank.num_submodels if bank is not None else 0
        if ecfg.speculate_k > 0:
            if draft is None:
                raise ValueError(
                    "speculate_k > 0 needs a DraftModel "
                    "(ModelBank.draft_model) to propose tokens")
            if draft.cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft.cfg.vocab_size} != parent vocab "
                    f"{cfg.vocab_size} — drafted ids would be meaningless")
            self.spec: Optional[DraftRunner] = DraftRunner(draft, ecfg, mesh)
        elif draft is not None:
            raise ValueError("a DraftModel needs speculate_k > 0 to be used")
        else:
            self.spec = None

        run = RunConfig(model=cfg,
                        shape=ShapeConfig("serve", "decode",
                                          ecfg.max_model_len, ecfg.num_slots),
                        horn=HornConfig(enabled=False),
                        compute_dtype=ecfg.compute_dtype)
        # telemetry before the jitted step: the profiler wraps it and
        # must see its very first (warmup) compile
        self.obs = telemetry if telemetry is not None else Telemetry()
        # static kernel tuning knob, read at trace time — set before the
        # first jitted step is traced (see kernels/paged_attention/ops.py)
        from repro.kernels.paged_attention import ops as _pops
        _pops.set_pages_per_step(ecfg.pages_per_step)
        self._step, _ = S.make_unified_paged_step(
            run, mesh, num_pages=ecfg.num_pages, page_size=ecfg.page_size,
            temperature=ecfg.temperature,
            bank_masks=bank.device_masks() if bank is not None else None,
            kv_dtype=jnp.dtype(ecfg.kv_dtype))
        if self.obs.profiler is not None:
            self._step = self.obs.profiler.wrap(
                "unified_step", self._step, key_fn=_unified_step_key)
        self._page_copy = S.make_page_copy_step()
        self.cache = T.init_paged_cache(cfg, ecfg.num_pages, ecfg.page_size,
                                        dtype=jnp.dtype(ecfg.kv_dtype),
                                        num_slots=ecfg.num_slots,
                                        conv_dtype=ecfg.compute_dtype)

        B = ecfg.num_slots
        # chunk widths are clamped so every compile cell is a power of two
        # <= bucket(max_chunk): a preempted request's re-prefill (up to
        # max_model_len - 1 kv tokens) just takes one extra tick instead of
        # minting a wider compile cell no warmup sweep would have seen
        self.max_chunk = min(ecfg.token_budget, ecfg.max_prompt_len)
        # incremental block-table sync (shared with the draft runner —
        # serving/block_table.py): per-slot (req_id, admit_seq,
        # table_version) keys decide which ROWS re-upload; the pool bumps
        # a sequence's version on every table mutation (page appended,
        # adopted, COW- or rollback-swapped), and admit_seq keys a
        # preempt/re-admit cycle that lands the same request back in its
        # old slot.
        self._bt = BlockTableMirror(B, self.max_pages_per_seq)
        # the S_v == 1 verify window of a tick with no speculating slot
        self._noprobs = jnp.zeros((B, 0, 1), jnp.float32)
        self._root_key = jax.random.key(ecfg.seed)
        self._next_id = 0
        self._next_group_id = 0
        # serving counters live on an EngineStats dataclass (observability/
        # stats.py); module-level properties below keep every counter
        # readable/writable as a plain engine attribute
        self.stats = EngineStats()
        self._evictions_base = 0         # pool evictions at last reset
        # stamp the tuning knobs into exported traces + metrics snapshots
        # — two traces from differently-configured engines must be
        # distinguishable without filenames
        self.obs.set_engine_config(
            kv_dtype=ecfg.kv_dtype, compute_dtype=ecfg.compute_dtype,
            pages_per_step=ecfg.pages_per_step,
            attn_tiling={str(1 << i): list(self._attn_tiling(1 << i))
                         for i in range(pow2_bucket(self.max_chunk)
                                        .bit_length())},
            speculate_k=ecfg.speculate_k,
            bank_size=bank.num_submodels if bank is not None else 0,
            num_slots=ecfg.num_slots, num_pages=ecfg.num_pages,
            page_size=ecfg.page_size, token_budget=ecfg.token_budget,
            max_prompt_len=ecfg.max_prompt_len,
            max_new_tokens=ecfg.max_new_tokens, policy=ecfg.policy,
            prefix_cache=ecfg.prefix_cache,
            temperature=ecfg.temperature, seed=ecfg.seed)

    @property
    def preemptions(self) -> int:
        return self.sched.preemptions

    @property
    def accept_rate(self) -> float:
        return self.stats.accept_rate

    @property
    def accepted_tok_per_tick(self) -> float:
        return self.stats.accepted_tok_per_tick

    @property
    def cobatch_ratio(self) -> float:
        return self.stats.cobatch_ratio

    @property
    def prefix_hit_rate(self) -> Optional[float]:
        return self.stats.prefix_hit_rate

    @property
    def cache_evictions(self) -> int:
        """Prefix-cache pressure evictions since the last ``reset_stats``
        (the pool counter is lifetime; benchmarks measure post-warmup)."""
        if self.pool.cache is None:
            return 0
        return self.pool.cache.evictions - self._evictions_base

    def metrics(self) -> dict:
        """Full telemetry snapshot — counters, derived rates, pool/router/
        cache/spec state, latency + tick distributions, SLO attainment.
        The stats line and the benchmark phases read this instead of
        engine internals; it also refreshes ``self.obs.registry``."""
        self.obs.collect(self)
        return self.obs.snapshot(self)

    def reset_stats(self) -> None:
        """Zero the serving counters without touching compile caches or the
        pool — benchmarks warm up on the engine they measure (a fresh Engine
        would also mean a fresh jit cache) and then discard the warmup's
        contribution here.  Telemetry (histograms, traces, timeline, SLO
        scores) resets with the counters.

        This is the warm-up boundary, so it also moves every object set-up
        left alive (traced programs, compile caches, weights' wrappers)
        out of the cyclic collector's reach (``gc.freeze``): a full
        collection inside the serving loop then walks only what serving
        allocated, where it walked the whole set-up heap in the middle of
        a tick (about 0.2 s for granite-4.0-h-micro on a v5e host)."""
        gc.collect()
        gc.freeze()
        self.stats.reset()
        if self.spec is not None:
            self.spec.draft_calls = 0
        if self.pool.cache is not None:
            self._evictions_base = self.pool.cache.evictions
        self.sched.preemptions = 0
        self.sched.finished.clear()
        self.obs.reset()

    # -- request intake ------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               arrival_time: float = 0.0, *,
               submodel_id: Optional[int] = None, session=None,
               ensemble: Optional[str] = None, slo_class: str = "default"
               ) -> Union[Request, EnsembleGroup]:
        """Queue one request.  With a ModelBank attached, the Router picks
        (or validates) the circuit; ``ensemble`` ("mean_logit" |
        "majority_vote") instead fans the prompt across ALL G circuits as
        one lockstep group and returns the EnsembleGroup.  ``slo_class``
        names the priority class the finished request is scored under
        (observability/slo.py)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not 0 < len(prompt) <= self.ecfg.max_prompt_len:
            raise ValueError(
                f"prompt length {len(prompt)} not in [1, "
                f"{self.ecfg.max_prompt_len}] — an empty prompt has no "
                f"token to decode from (it would allocate zero pages and "
                f"decode off the null page)")
        mnt = min(max_new_tokens or self.ecfg.max_new_tokens,
                  self.ecfg.max_new_tokens)

        if ensemble is not None:
            if self.bank is None:
                raise ValueError("ensemble mode requires a ModelBank")
            if submodel_id is not None or session is not None:
                raise ValueError(
                    "ensemble fans across ALL circuits — submodel_id/"
                    "session routing hints conflict with it")
            if ensemble not in COMBINES:
                raise ValueError(
                    f"unknown combine {ensemble!r}; known: {COMBINES}")
            G = self.bank.num_submodels
            if G > self.ecfg.num_slots:
                raise ValueError(
                    f"ensemble needs {G} slots (one per circuit) but the "
                    f"engine has {self.ecfg.num_slots}")
            group = EnsembleGroup(id=self._next_group_id, combine=ensemble,
                                  share=self.ecfg.prefix_cache)
            self._next_group_id += 1
            # the shared prompt context [0, len - 1) is dense-parent
            # encoded (namespace b"dense"); each member's circuit engages
            # at the last prompt token — so the context bytes are
            # member-invariant and the leader can prefill them for all
            group.members = [
                Request(id=self._next_id + g, prompt=prompt,
                        max_new_tokens=mnt, arrival_time=arrival_time,
                        eos_id=self.ecfg.eos_id, submodel_id=g, group=group,
                        kv_namespace=b"dense", mask_from=len(prompt) - 1,
                        slo_class=slo_class)
                for g in range(G)]
            self._check_feasible(group.members[0])
            self._next_id += G
            if self.router is not None:
                for g in range(G):
                    self.router.acquire(g)
            for req in group.members:
                self.sched.submit(req)
                self.obs.on_submit(req, arrival_time)
            return group

        req = Request(id=self._next_id, prompt=prompt, max_new_tokens=mnt,
                      arrival_time=arrival_time, eos_id=self.ecfg.eos_id,
                      slo_class=slo_class)
        self._check_feasible(req)
        if self.bank is not None:
            req.submodel_id = self.router.route(
                submodel_id=submodel_id, session=session, prompt=prompt)
            req.kv_namespace = b"sub:%d" % req.submodel_id
        elif submodel_id not in (None, 0):
            raise ValueError("submodel routing requires a ModelBank")
        self._next_id += 1
        self.sched.submit(req)
        self.obs.on_submit(req, arrival_time)
        return req

    def _check_feasible(self, req: Request) -> None:
        """Reject requests that could never be admitted even into an empty
        pool — otherwise they'd pin the FCFS head and the drive loop would
        spin forever waiting for pages that cannot exist."""
        need = self._admission_need(req)
        if need > self.pool.capacity:
            raise ValueError(
                f"request needs {need} page(s) at admission "
                f"(policy={self.ecfg.policy}) but the pool has only "
                f"{self.pool.capacity}; raise num_pages or shrink "
                f"prompt/max_new_tokens")

    def _admission_need(self, req: Request) -> int:
        """Worst-case (no cache hit) pages the whole scheduling unit
        (solo, or every ensemble member) needs available to admit."""
        unit = req.group.members if req.group is not None else [req]
        return self.sched.unit_admission_pages(unit)

    # -- internals -----------------------------------------------------------
    def _chunk_bucket(self, n: int) -> int:
        """Power-of-two chunk-width bucket (bounds unified-step retraces)."""
        return pow2_bucket(n)

    def _sync_block_tables(self) -> None:
        """Incremental row sync of the device block table (see
        ``serving/block_table.py``)."""
        self.bt_rows_synced += self._bt.sync(
            self.pool, self.sched.running,
            lambda r: (r.id, r.admit_seq, self.pool.table_version(r.id)))

    def _sample_peak(self) -> None:
        self.peak_utilization = max(self.peak_utilization,
                                    self.pool.utilization())
        if self.bank is not None:
            for owner, util in self.pool.utilization_by_owner().items():
                if util > self.peak_util_by_submodel.get(owner, 0.0):
                    self.peak_util_by_submodel[owner] = util

    def _release(self, done: List[Request]) -> None:
        for req in done:
            # every finished request passes through here exactly once, on
            # every tick path (early returns and OOM raises included)
            self.obs.on_finish(req, req.t_done)
        if self.router is not None:
            for req in done:
                self.router.release(req.submodel_id)
        if self.spec is not None:
            for req in done:
                self.spec.drop(req.id)

    def _clock(self, now: Optional[float]) -> float:
        return time.monotonic() if now is None else now

    def _flush_copies(self, pairs: List[Tuple[int, int]]) -> None:
        """Issue the device-side page copies a COW swap requires, padded to
        a power-of-two width ((0, 0) pads copy the null page onto itself)
        so jit compiles one executable per bucket."""
        if not pairs:
            return
        n = self._chunk_bucket(len(pairs))
        src = np.zeros((n,), np.int32)
        dst = np.zeros((n,), np.int32)
        for i, (s, d) in enumerate(pairs):
            src[i], dst[i] = s, d
        self.cache = self._page_copy(self.cache, *marshal_i32(src, dst))
        self.cow_page_copies += len(pairs)

    def _prepare_entry_write(self, req: Request, start: int,
                             end: int) -> None:
        """Grow the request's table through ``end`` tokens and COW any page
        in the written range [start, end) that other tables (or the prefix
        cache) still hold.  May raise PagePoolOOM — the preempt-youngest
        loop in ``_plan_tick`` answers."""
        self.pool.ensure(req.id, end)
        self._flush_copies(self.pool.prepare_write(req.id, start, end))

    # -- tick planning -------------------------------------------------------
    def _plan_tick(self, now: float) -> Dict[int, _Entry]:
        """Fill the token budget: one decode token per decode-phase slot,
        then prompt chunks for prefill-phase slots in admission order.
        Preempts the youngest running sequence (and replans) whenever decode
        growth hits pool pressure; raises EngineOOM only when no preemption
        can help."""
        while True:
            try:
                return self._try_plan()
            except PagePoolOOM as e:
                victim = self.sched.preempt_youngest()
                if victim is None:
                    raise EngineOOM(
                        f"tick {self.steps}: {e}; no other sequence left to "
                        f"preempt — this request can never fit; raise "
                        f"--pages, lower --gen, or use --policy reserve"
                        ) from e
                unit = victim.group.members if victim.group is not None \
                    else [victim]
                for m in unit:
                    m.t_preempted = now
                    self.obs.on_preempt(m, now)
                    if self.spec is not None:
                        # the draft pool stays bounded by the running slots:
                        # a preempted request's draft KV is recomputed by
                        # one catch-up chunk on re-admission
                        self.spec.drop(m.id)

    def _try_plan(self) -> Dict[int, _Entry]:
        entries: Dict[int, _Entry] = {}
        budget = self.ecfg.token_budget
        decode, prefill = [], []
        for slot, req in sorted(self.sched.running.items()):
            (prefill if req.in_prefill else decode).append((slot, req))

        # speculative draft length for this tick: uniform across the
        # speculating slots (one verify-window width per call), sized so
        # the parent budget covers every decode slot's pending token plus
        # 1 + k verified tokens per speculating slot
        spec_k = self.ecfg.speculate_k if self.spec is not None else 0

        def allowance(r: Request) -> int:
            # a tick commits at most 1 + dl tokens, so drafting past the
            # request's remaining allowance minus one can never land (it
            # would only burn draft/verify budget and depress accept_rate
            # at every request tail).  The same bound keeps K/V writes
            # inside both max_model_len and the reserve-policy admission
            # reservation: the verify chunk ends at
            # context + dl <= prompt + max_new - 1
            return r.prompt_len + r.max_new_tokens - r.context_len - 1

        # only slots that can actually land a draft share the speculative
        # budget — a slot one token from its cap drafts nothing and must
        # not dilute the others' split
        n_spec = sum(1 for _, r in decode
                     if r.spec_eligible and allowance(r) > 0) \
            if spec_k else 0
        k_tick = min(speculative_draft_len(spec_k, budget, len(decode),
                                           n_spec), self.max_chunk - 1)
        for slot, req in decode:
            dl = 0
            if k_tick > 0 and req.spec_eligible:
                dl = max(0, min(k_tick, allowance(req)))
            # grows the table through context_len (+ the draft tail) and
            # COWs any shared page the writes would touch; may raise
            # PagePoolOOM
            self._prepare_entry_write(req, req.context_len - 1,
                                      req.context_len + dl)
            toks = np.zeros((1 + dl,), np.int32)
            toks[0] = req.out_tokens[-1]     # drafts land in toks[1:] later
            entries[slot] = _Entry(
                req=req, start=req.context_len - 1, tokens=toks,
                chunk_len=1 + dl, sample_step=len(req.out_tokens),
                record=True, mask_id=req.submodel_id, draft_len=dl)
            budget -= 1 + dl
        # prompt chunks soak up whatever budget the decode tokens left,
        # oldest admission first (it holds pages; finish it soonest).
        # Ensemble groups advance in LOCKSTEP: every member gets the same
        # chunk width this tick (identical streams + identical prefill_pos),
        # so all members finish prefill in the same tick and their combined
        # logits produce the group's first token together.  Chunks break at
        # ``mask_from``: an ensemble stream is dense-parent encoded before
        # it (shared context) and member-masked from it on — in share mode
        # only the leader computes the dense region, then the group forks.
        prefill.sort(key=lambda sr: sr[1].admit_seq)
        planned_groups = set()
        for slot, req in prefill:
            group = req.group
            if group is not None:
                if group.id in planned_groups:
                    continue
                planned_groups.add(group.id)
                if group.share and not group.forked:
                    leader = group.leader
                    if leader.prefill_pos < leader.mask_from:
                        unit = [(leader.slot, leader)]   # dense solo advance
                    else:
                        self.prefill_tok_saved += self.sched.fork_group(group)
                        unit = [(m.slot, m) for m in group.members]
                else:
                    unit = [(m.slot, m) for m in group.members]
            else:
                unit = [(slot, req)]
            n = len(unit)
            r0 = unit[0][1]
            want = len(r0.kv_tokens) - r0.prefill_pos
            dense = r0.prefill_pos < r0.mask_from
            if dense:                       # stop at the mask boundary
                want = min(want, r0.mask_from - r0.prefill_pos)
            cl = min(want, max(budget, 0) // n, self.max_chunk)
            if cl <= 0:
                continue                          # budget exhausted this tick
            # write-prep members BEFORE the leader: each member's COW of the
            # shared boundary page redeems its own deferred-reserve credit,
            # and the leader — whose admission reserve covers the original
            # page — is the last holder left and writes it in place.
            # Leader-first would draw an unreserved free page for the
            # leader's copy while a member credit idles, OOMing a pool
            # sized exactly to the reserve-policy worst case.
            for s, r in unit[1:] + unit[:1]:
                kv = r.kv_tokens
                finishes = r.prefill_pos + cl == len(kv)
                self._prepare_entry_write(r, r.prefill_pos, r.prefill_pos + cl)
                entries[s] = _Entry(
                    req=r, start=r.prefill_pos,
                    tokens=kv[r.prefill_pos:r.prefill_pos + cl],
                    chunk_len=cl, sample_step=0,
                    # the chunk that completes a *fresh* prompt yields the
                    # first token; a preempted request's next token is
                    # already known
                    record=finishes and not r.out_tokens,
                    mask_id=self._dense_mask_id if dense else r.submodel_id)
            budget -= cl * n
        return entries

    # -- one engine tick -----------------------------------------------------
    def step(self, now: Optional[float] = None,
             tick_clock=None) -> List[Request]:
        """Admit + advance every running slot by one unified device call.
        Returns the requests that finished this tick.  Pass ``tick_clock``
        (a zero-arg callable on the same epoch as ``now``) for an honest
        post-tick timestamp; without it every event in the tick shares
        ``now``.

        The tick and each of its phases are ``jax.profiler`` trace
        annotations (``engine:tick`` > ``engine:plan`` /
        ``engine:host_prep`` (> ``engine:draft``) / ``engine:device_step``
        (> ``engine:h2d`` / ``engine:dispatch`` / ``engine:sync``) /
        ``engine:commit``), opened and closed where the ``TickTimeline``
        marks are taken, so a profiler trace shows them on the device
        ops' clock; outside a profiler session they cost about a
        microsecond each.  A tick that reaches the device stamps its
        ``engine:tick`` span with its work counts (``_tick_counts``); one
        that returns before the device carries none."""
        now = self._clock(now)
        tick_now = tick_clock if tick_clock else (lambda: now)
        pc = time.perf_counter                    # timeline clock (µs spans)
        with _annotate("engine:tick") as tick_span:
            m_start = pc()
            with _annotate("engine:plan"):
                for req in self.sched.admit(now):
                    self.cache_hit_tokens += req.num_cached_tokens
                    self.cache_eligible_tokens += req.cache_eligible_tokens
                    self.prefill_tok_saved += req.num_cached_tokens
                    self.obs.on_admit(req, now)
                self._sample_peak()               # admissions allocate pages
                # e.g. max_new_tokens == 1
                done = self.sched.evict_finished(tick_now())
                if not self.sched.running:
                    if self.sched.waiting:
                        # a preempted request's context can outgrow the
                        # whole pool; with nothing running and the FCFS
                        # head unadmittable even into an empty pool, the
                        # drive loop would spin forever
                        head = self.sched.waiting[0]
                        need = self._admission_need(head)
                        if need > self.pool.capacity:
                            # don't leak router loads on raise
                            self._release(done)
                            raise EngineOOM(
                                f"request {head.id} needs {need} page(s) "
                                f"to re-admit but the pool has only "
                                f"{self.pool.capacity}; its context can "
                                f"never fit — raise --pages or lower --gen")
                    self._release(done)
                    return done

                try:
                    entries = self._plan_tick(now)
                except EngineOOM:
                    self._release(done)   # don't leak router loads on raise
                    raise
                self._sample_peak()           # decode growth allocates too
                if not entries:               # nothing runnable this tick
                    self._release(done)
                    return done
                m_plan = pc()

            with _annotate("engine:host_prep"):
                # draft proposals first: one jitted draft-circuit call
                # covering every speculating slot (catch-up chunk +
                # on-device scan), then the drafted tokens ride the verify
                # chunks of the parent call
                spec_units = [(slot, e) for slot, e in entries.items()
                              if e.draft_len > 0]
                draft_span = ()
                if spec_units:
                    with _annotate("engine:draft"):
                        t_draft = pc()
                        k_tick = max(e.draft_len for _, e in spec_units)
                        drafts, draft_probs = self.spec.propose(
                            [(s, e.req) for s, e in spec_units], k_tick,
                            self._root_key)
                        for slot, e in spec_units:
                            e.tokens[1:1 + e.draft_len] = \
                                drafts[slot, :e.draft_len]
                        draft_span = (("draft", t_draft, pc()),)
                else:
                    draft_probs = self._noprobs

                B = self.ecfg.num_slots
                C = self._chunk_bucket(
                    max(e.chunk_len for e in entries.values()))
                tokens = np.zeros((B, C), np.int32)
                starts = np.zeros((B,), np.int32)
                chunk_lens = np.zeros((B,), np.int32)
                req_ids = np.zeros((B,), np.int32)
                sample_steps = np.zeros((B,), np.int32)
                submodel_ids = np.zeros((B,), np.int32)
                seg_ids = np.arange(B, dtype=np.int32)  # solo: own segment
                vote_flags = np.zeros((B,), bool)
                draft_lens = np.zeros((B,), np.int32)
                for slot, e in entries.items():
                    tokens[slot, :e.chunk_len] = e.tokens
                    starts[slot] = e.start
                    chunk_lens[slot] = e.chunk_len
                    req_ids[slot] = e.req.id
                    sample_steps[slot] = e.sample_step
                    submodel_ids[slot] = e.mask_id
                    draft_lens[slot] = e.draft_len
                    group = e.req.group
                    if group is not None:
                        seg_ids[slot] = group.leader.slot
                        if group.combine == "majority_vote":
                            vote_flags[slot] = True   # members sample, vote
                        else:
                            # mean-logit: one sampling key per group -> one
                            # draw
                            req_ids[slot] = group.leader.id

                self.ticks_nonempty += 1
                if len({e.req.submodel_id for e in entries.values()}) > 1:
                    self.ticks_cobatched += 1
                self._sync_block_tables()
                counts = self._tick_counts(entries, C)
                self.real_tokens += counts["real_tokens"]
                self.padded_tokens += counts["padded_tokens"]
                if self.mamba_layers:
                    self.ssm_state_bytes += counts["ssm_state_bytes"]
                    self.ssm_tokens += counts["ssm_tokens"]
                tick_span.set_metadata(**counts)

                # ticks without an ensemble group skip the on-device
                # combine entirely (static jit arg: one extra compile per
                # bucket at most)
                ensembles = any(e.req.group is not None
                                for e in entries.values())
                m_host = pc()
            with _annotate("engine:device_step"):
                with _annotate("engine:h2d"):
                    (d_tokens, d_starts, d_chunk_lens, d_req_ids,
                     d_sample_steps, d_submodel_ids, d_seg_ids, d_vote_flags,
                     d_draft_lens) = \
                        marshal_i32(tokens, starts, chunk_lens, req_ids,
                                    sample_steps, submodel_ids, seg_ids,
                                    vote_flags, draft_lens)
                with _annotate("engine:dispatch"):
                    sampled, accepted, self.cache = self._step(
                        self.params, self.cache, d_tokens, d_starts,
                        d_chunk_lens, self._bt.dev, d_req_ids,
                        d_sample_steps, d_submodel_ids, d_seg_ids,
                        d_vote_flags, d_draft_lens, draft_probs,
                        self._root_key, ensembles=ensembles)
                with _annotate("engine:sync"):
                    # one deliberate host pull commits the tick; the
                    # accepted counts matter only where a slot drafted,
                    # so a tick without drafts pulls the tokens alone
                    if spec_units:
                        sampled, accepted = jax.device_get(  # hornlint: sync-ok
                            (sampled, accepted))
                    else:
                        sampled = jax.device_get(sampled)  # hornlint: sync-ok
                m_dev = pc()
            with _annotate("engine:commit"):
                self.steps += 1
                post = tick_now()

                for slot, e in entries.items():
                    req = e.req
                    was_prefill = req.in_prefill
                    if was_prefill:
                        self.prefill_tokens += e.chunk_len
                        self.obs.on_prefill_chunk(req, post, e.start,
                                                  e.chunk_len)
                    if e.draft_len:
                        self._commit_spec(slot, e, int(sampled[slot]),
                                          int(accepted[slot]), post)
                        continue
                    # decode writes K/V too (position context_len - 1), so
                    # advance prefill_pos past every write this tick —
                    # otherwise the next generated token flips the request
                    # back into "prefill" and re-feeds one already-written
                    # token as a redundant chunk
                    req.prefill_pos = max(req.prefill_pos,
                                          e.start + e.chunk_len)
                    if was_prefill and req.page_hashes:
                        # content-index every freshly materialized full
                        # page of the publishable (namespace-uniform)
                        # region — the next request with this prefix maps
                        # the pages instead of recomputing
                        full = min(req.prefill_pos, req.publishable_end) \
                            // self.ecfg.page_size
                        if full:
                            self.pool.publish_prefix(req.id,
                                                     req.page_hashes, full)
                    if e.record:
                        self.sched.record_token(slot, int(sampled[slot]),
                                                post)
                        self.generated_tokens += 1
                        sid = req.submodel_id
                        self.tokens_by_submodel[sid] = \
                            self.tokens_by_submodel.get(sid, 0) + 1
                        self.obs.on_token(req, post)

                finished = self.sched.evict_finished(post)
                self._release(done + finished)
                # the tick's phase spans + per-slot device-window
                # annotations — per-slot tuples are only built when a
                # timeline is recording
                if self.obs.timeline is not None:
                    slot_events = [
                        (slot, f"verify+{e.draft_len}" if e.draft_len
                         else ("decode" if e.sample_step else "prefill"),
                         m_host, m_dev,
                         {"req": e.req.id, "tokens": e.chunk_len,
                          "start": e.start})
                        for slot, e in entries.items()]
                    counters = {"used_pages": self.pool.used_pages,
                                "cached_pages": self.pool.cached_pages,
                                "running": len(self.sched.running),
                                "waiting": len(self.sched.waiting)}
                else:
                    slot_events, counters = (), None
                m_end = pc()
        self.obs.on_tick(self.steps - 1, (m_start, m_plan, m_host, m_dev,
                                          m_end),
                         slot_events=slot_events, extra_spans=draft_span,
                         counters=counters, counts=counts, t=post,
                         used_pages=self.pool.used_pages,
                         live_pages=self.pool.live_table_pages)
        return done + finished

    def _attn_tiling(self, C: int):
        """(pages_per_step, heads_per_step) of the paged-attention call at
        chunk width ``C``: ``EngineConfig.pages_per_step`` where set, else
        what ``chunk_tiling`` derives, as the kernel does."""
        from repro.kernels.paged_attention.kernel import chunk_tiling
        KH, maxp = self.cfg.num_kv_heads, self.max_pages_per_seq
        pps, hps = chunk_tiling(C, self.cfg.num_heads // KH, KH, maxp)
        if self.ecfg.pages_per_step is not None:
            pps = max(1, min(self.ecfg.pages_per_step, maxp))
        return pps, hps

    def _tick_counts(self, entries: Dict[int, _Entry], C: int) -> dict:
        """The work of one device tick: ``real_tokens`` the slots' chunk
        lengths, ``prefill_tokens`` those of slots still in prefill,
        ``padded_tokens`` the positions the step computes (every slot
        padded to the chunk width ``chunk``), ``live_slots`` the slots
        that carry a chunk; ``attn_grid_steps`` the grid steps of one
        layer's paged-attention call, ``attn_live_steps`` those that hold
        at least one live page (their ratio is the dead-step share).  A
        model with Mamba layers adds ``ssm_state_bytes``, the per-slot
        state the live slots' scans read and write (SSM state and conv
        tail, each way, over every Mamba layer), and ``ssm_tokens``, the
        real tokens through the scan."""
        pps, hps = self._attn_tiling(C)
        groups = self.cfg.num_kv_heads // hps
        psize = self.ecfg.page_size
        live_pages = [-(-(e.start + e.chunk_len) // psize)
                      for e in entries.values()]
        live = sum(-(-n // pps) for n in live_pages)
        real = sum(e.chunk_len for e in entries.values())
        counts = {"tick": self.steps,
                  "real_tokens": real,
                  "prefill_tokens": sum(e.chunk_len for e in entries.values()
                                        if e.req.in_prefill),
                  "padded_tokens": self.ecfg.num_slots * C,
                  "chunk": C,
                  "live_slots": len(entries),
                  "attn_grid_steps": self.ecfg.num_slots * groups
                  * -(-self.max_pages_per_seq // pps),
                  "attn_live_steps": live * groups}
        if self.mamba_layers:
            counts["ssm_state_bytes"] = \
                len(entries) * self._ssm_slot_tick_bytes
            counts["ssm_tokens"] = real
        return counts

    def _commit_spec(self, slot: int, e: _Entry, sampled: int, acc: int,
                     now: float) -> None:
        """Land a verify verdict: commit the accepted draft prefix plus
        the one verified (bonus or correction) token the parent sampled
        after it — stopping at EOS / max_new exactly where sequential
        decode would — then roll the page tail back to the committed K/V
        (a ref-release via ``truncate_seq``, never a copy) and tell the
        draft runner which of its proposals survived."""
        req = e.req
        acc = min(acc, e.draft_len)
        n0 = req.context_len                  # before any commit
        commit = [int(t) for t in e.tokens[1:1 + acc]] + [sampled]
        c = 0
        for tok in commit:
            self.sched.record_token(slot, tok, now)
            c += 1
            self.generated_tokens += 1
            sid = req.submodel_id
            self.tokens_by_submodel[sid] = \
                self.tokens_by_submodel.get(sid, 0) + 1
            if req.finished:                  # EOS or max_new mid-window
                break
        self.spec_slot_ticks += 1
        self.spec_drafted += e.draft_len
        self.spec_accepted += min(acc, c)
        self.spec_committed += c
        self.obs.on_speculate(req, now, e.draft_len, min(acc, c), c)
        self.obs.on_token(req, now, n=c)
        if req.finished:
            # pages are freed wholesale by evict_finished and the draft
            # state by _release; prefill_pos only needs to stay consistent
            req.prefill_pos = n0 + min(acc, c)
            return
        # valid K/V = committed stream minus its pending last token: the
        # context plus exactly the accepted drafts (the verify chunk wrote
        # K/V for every draft; the rejected tail is stale and its pages go
        # back — recredited under reserve so the admission-time
        # reservation survives the rollback)
        req.prefill_pos = n0 + acc
        self.pool.truncate_seq(req.id, req.prefill_pos,
                               recredit=self.ecfg.policy == "reserve")
        self.spec.commit(req, acc)

    def finished_streams(self) -> List[Request]:
        """Finished requests deduplicated to one per delivered token
        stream: solo requests plus one leader per ensemble group (every
        member carries the identical combined stream).  User-facing
        latency/throughput accounting should use this; device-side token
        counts still sum over all of ``sched.finished``."""
        return [r for r in self.sched.finished
                if r.group is None or r is r.group.leader]

    def run(self, *, clock=None) -> List[Request]:
        """Drive until every submitted request has finished."""
        clock = clock or time.monotonic
        while self.sched.has_work():
            self.step(clock())
        return self.sched.finished


def _stats_attr(name: str) -> property:
    def get(self):
        return getattr(self.stats, name)

    def set_(self, v):
        setattr(self.stats, name, v)

    return property(get, set_)


# every EngineStats counter stays a plain engine attribute
# (``engine.generated_tokens``, ``self.steps += 1``) — derived from the
# dataclass fields, so a counter added to EngineStats is automatically an
# engine attribute too
for _f in dataclasses.fields(EngineStats):
    setattr(Engine, _f.name, _stats_attr(_f.name))
del _f
