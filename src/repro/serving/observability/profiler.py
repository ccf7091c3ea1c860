"""Device-step cost attribution: compile-event capture and per-variant
``cost_analysis()`` for the engine's jitted step functions.

The engine's whole device story is a handful of jitted callables (the
unified step's two ensemble variants, each specialised per chunk-width
bucket and verify-window extent).  Two things about them are prime
silent regressions:

  * **Recompiles.**  A chunk width the warmup sweep never minted, a
    static flag flipping mid-run, or an upstream cache flush turns one
    cheap tick into a multi-second trace+compile stall.  The profiler
    watches each jitted callable's compile-cache size across calls — a
    growth is a compile, stamped with the call's wall duration and
    whether it happened after the warmup boundary (``mark_warm``,
    driven by ``Engine.reset_stats``).  Post-warm compiles surface as
    first-class ``TickTimeline`` spans, ``Engine.metrics()`` counters,
    and a ``recompile`` anomaly alert.
  * **Cost drift.**  ``cost_analysis()`` FLOPs / HBM-bytes per compiled
    variant put a number on what each tick *asks* the device to do, so
    a PR that doubles the bytes-accessed of the decode step is visible
    in the replay report even when wall clock on a noisy CI box is not.
    Argument shape/dtype structs are captured on each variant's first
    call and the (potentially multi-second) ``lower().compile()`` for
    cost extraction is deferred to ``cost_report()`` — exit-report /
    regression-harness time, never the tick path."""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional


@dataclass
class CompileEvent:
    """One observed jit compile (cache-size growth across a call)."""

    name: str                    # wrapped step's name ("unified_step")
    variant: str                 # shape-bucket label, e.g. "C=32,ens=False"
    t0: float                    # perf_counter at call start
    dur_s: float                 # wall duration of the compiling call
    post_warm: bool              # after the warmup boundary => regression

    def as_dict(self) -> dict:
        return {"name": self.name, "variant": self.variant,
                "dur_s": round(self.dur_s, 4), "post_warm": self.post_warm}


@dataclass
class _Variant:
    """Book-keeping for one (step, shape-signature) compile cell."""

    label: str
    jitted: object
    structs: Optional[tuple] = None      # ShapeDtypeStruct tree for lower()
    calls: int = 0
    compiles: int = 0
    cost: Optional[dict] = field(default=None)


class StepProfiler:
    """Wraps jitted step callables; collects compile events + variant
    cost/call stats.  One per Telemetry; the engine wraps its steps at
    construction time."""

    def __init__(self, clock=time.perf_counter, max_events: int = 256):
        self.clock = clock
        self.max_events = max_events
        self.compile_events: List[CompileEvent] = []
        self.compiles_total = 0
        self.compiles_post_warm = 0
        self._warm = False
        self._variants: Dict[tuple, _Variant] = {}
        # set by the owning Telemetry: routes each event to the timeline
        # span + anomaly monitor the moment the compile is observed
        self.on_compile: Optional[Callable[[CompileEvent], None]] = None

    # -- wrapping ------------------------------------------------------------
    def wrap(self, name: str, step_fn, key_fn=None):
        """Return a drop-in replacement for ``step_fn``.

        ``step_fn`` may be a plain jitted callable or the unified-step
        closure carrying a ``.variants`` dict of static-flag -> jitted
        (cache sizes are then watched per flag).  ``key_fn(args, kw)``
        labels the shape bucket; the default uses every top-level
        array argument's shape, which is cheap (no pytree walk) and
        distinguishes exactly what jit's shape specialisation does for
        the engine's steps."""
        variants = getattr(step_fn, "variants", None)

        def default_key(args, kw):
            shapes = tuple(tuple(a.shape) for a in args
                           if hasattr(a, "shape"))
            return shapes, ",".join("x".join(map(str, s)) for s in shapes)

        keyer = key_fn if key_fn is not None else default_key

        def wrapped(*args, **kw):
            jitted = variants[kw.get("ensembles", False)] \
                if variants is not None else step_fn
            sig, label = keyer(args, kw)
            key = (name, sig, tuple(sorted(kw.items())))
            rec = self._variants.get(key)
            before = jitted._cache_size()
            t0 = self.clock()
            out = step_fn(*args, **kw)
            dur = self.clock() - t0
            if rec is None:
                rec = self._variants[key] = _Variant(
                    label=f"{name}[{label}]", jitted=jitted)
                try:
                    import jax
                    rec.structs = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        args)
                except Exception:
                    rec.structs = None
            rec.calls += 1
            if jitted._cache_size() > before:
                self._record_compile(name, rec.label, t0, dur)
                rec.compiles += 1
            return out

        return wrapped

    def _record_compile(self, name: str, variant: str, t0: float,
                        dur_s: float) -> None:
        ev = CompileEvent(name, variant, t0, dur_s, self._warm)
        self.compiles_total += 1
        if self._warm:
            self.compiles_post_warm += 1
        if len(self.compile_events) < self.max_events:
            self.compile_events.append(ev)
        if self.on_compile is not None:
            self.on_compile(ev)

    def mark_warm(self) -> None:
        """Warmup boundary (``Engine.reset_stats``): compiles so far were
        expected; any compile from here on is a late compile — the
        regression signal.  A no-op until the wrapped step has actually
        run at least once: resetting a cold engine (e.g. a one-shot
        ``--replay`` on a fresh process) must not turn its very first
        compiles into alerts."""
        if any(rec.calls for rec in self._variants.values()):
            self._warm = True

    # -- cost attribution ----------------------------------------------------
    @staticmethod
    def _compile(rec: _Variant):
        """AOT-lower + compile the variant's captured arg structs.  The
        unified-step closure dispatches on a kwarg the underlying jitted
        partial has already baked in, so lower() takes the positional
        structs only."""
        return rec.jitted.lower(*rec.structs).compile()

    @classmethod
    def _extract_cost(cls, rec: _Variant) -> dict:
        """The variant's FLOPs / bytes-accessed from ``cost_analysis``."""
        cost = cls._compile(rec).cost_analysis() or {}
        return {"flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0))}

    def cost_report(self, compute: bool = True) -> dict:
        """Per-variant calls/compiles plus (lazily computed, memoised)
        ``cost_analysis`` numbers.  ``compute=False`` returns whatever is
        already memoised without paying any AOT compile — the shape
        ``Engine.metrics()`` uses on the hot stats-line path."""
        out: Dict[str, dict] = {}
        for rec in self._variants.values():
            entry = {"calls": rec.calls, "compiles": rec.compiles}
            if rec.cost is None and compute and rec.structs is not None:
                try:
                    rec.cost = self._extract_cost(rec)
                except Exception as e:          # pragma: no cover
                    rec.cost = {"error": f"{type(e).__name__}: {e}"[:200]}
            if rec.cost:
                entry.update(rec.cost)
            out[rec.label] = entry
        return out

    def compiled_hlo(self) -> Dict[str, str]:
        """Optimized HLO text of every observed variant, AOT-compiled from
        its captured arg structs — what shows whether a Pallas kernel
        (``tpu_custom_call``) is in the program the device runs."""
        return {rec.label: self._compile(rec).as_text()
                for rec in self._variants.values()
                if rec.structs is not None}

    # -- lifecycle -----------------------------------------------------------
    def summary(self) -> dict:
        return {"compiles_total": self.compiles_total,
                "compiles_post_warm": self.compiles_post_warm,
                "variants": len(self._variants),
                "events": [e.as_dict() for e in self.compile_events]}

    def reset(self) -> None:
        """Drop events/counters but keep variant + cost memos (compile
        caches survive a stats reset, so should their attribution)."""
        self.compile_events.clear()
        self.compiles_total = 0
        self.compiles_post_warm = 0
