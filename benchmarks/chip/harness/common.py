"""What both runners share: the program's import path, the window's
compile counter, the trace capture and the checks' limits."""
from __future__ import annotations

import shutil
import statistics
import sys
import tempfile
from contextlib import contextmanager
from pathlib import Path
from typing import Dict, List, Optional

from harness.spec import ROOT
from harness import tracing


def import_program():
    """Put the checkout's ``src`` on the path; exits without it."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"the program is not in this checkout "
                         f"({src / 'repro'} is missing)")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


class CompileCounter:
    """Counts XLA compilations (and persistent-cache loads) while armed:
    there should be none inside a measured window."""

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed \
                and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def program_config(arch: str, m: dict):
    """The program's registered configuration for ``arch`` with the sizes
    the benchmark's configuration file states; refuses an architecture
    whose other settings differ from the file's."""
    import dataclasses
    from repro.configs.base import get_model_config
    cfg = dataclasses.replace(
        get_model_config(arch), num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"])
    pairs = {"rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
             "tie_word_embeddings": cfg.tie_embeddings,
             "hidden_act": cfg.act, "attention_bias": cfg.qkv_bias}
    bad = {k: (m[k], v) for k, v in pairs.items() if m[k] != v}
    if bad or not cfg.qk_norm or not cfg.mlp_gated:
        raise SystemExit(f"the program's {arch} differs from the "
                         f"benchmark's configuration (file, program): {bad}")
    return cfg


def limit_checks(readings: Dict[str, float], limits: Dict[str, float]):
    """{name: {"value", "limit"}} in the limits' order, and whether every
    reading is within its limit (a missing or NaN reading is not)."""
    out = {}
    ok = True
    for name, lim in limits.items():
        v = readings.get(name)
        out[name] = {"value": v, "limit": lim}
        if v is None or lim is None or not v <= lim:
            ok = False
    return out, ok


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   skip: Optional[List[str]] = None) -> float:
    """max over leaves of |prog - ref| / max(ref, median leaf of ref)."""
    skip = set(skip or ())
    names = [n for n in ref if n not in skip]
    med = statistics.median(ref[n] for n in names)
    return max(abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
               for n in names)


@contextmanager
def traced(enabled: bool):
    """Yield a capture dict; with ``enabled`` a profiler trace (written
    under TMPDIR, removed once read) runs while ``cap["start"]()`` and
    ``cap["stop"]()`` bracket the steady part."""
    import jax
    cap = {"dir": None, "running": False}
    tmp = Path(tempfile.mkdtemp(prefix="trace-")) if enabled else None

    def start():
        if enabled and not cap["running"] and cap["dir"] is None:
            jax.profiler.start_trace(str(tmp))
            cap["running"] = True

    def stop():
        if cap["running"]:
            jax.profiler.stop_trace()
            cap["running"] = False
            cap["dir"] = tmp

    cap["start"], cap["stop"] = start, stop
    try:
        yield cap
    finally:
        stop()


def load_trace(cap: dict):
    if cap.get("dir") is None:
        return None
    try:
        return tracing.load(tracing.latest_xplane(cap["dir"]))
    finally:
        shutil.rmtree(cap["dir"], ignore_errors=True)
