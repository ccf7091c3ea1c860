"""Percentiles and the window's request and tick records, for readers."""
from __future__ import annotations

import math
from typing import List, Sequence


def pct(values: Sequence[float], p: float) -> float:
    """The nearest-rank ``p``-th percentile (p in (0, 100])."""
    xs = sorted(values)
    return xs[max(0, math.ceil(p / 100 * len(xs)) - 1)]


def token_gaps(obs: dict) -> List[float]:
    """Gaps between consecutive tokens of a stream whose later token came
    in the window."""
    w0, w1 = obs["window"]
    out = []
    for r in obs["requests"]:
        ts = r["times"]
        out += [b - a for a, b in zip(ts, ts[1:]) if w0 <= b <= w1]
    return out


def window_ticks(obs: dict):
    m0, m1 = obs["window_mono"]
    return [t for t in obs["ticks"] if m0 <= t[0] < m1]


def idle_share(obs: dict):
    red = obs.get("reduced")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def kernel_time(obs: dict, kernel: str):
    """(device seconds, calls) of the trace's Pallas calls of ``kernel``
    (its exact name), or None without a trace or a call."""
    from harness.tracing import is_kernel, op_seconds
    tr = obs.get("trace")
    if tr is None:
        return None
    sec, n = op_seconds(tr, lambda o: is_kernel(o, kernel))
    return (sec, n) if n else None
