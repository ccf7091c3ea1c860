"""Capturing a profiler trace and reducing it to numbers.

A traced run wraps a short steady part of its window in
``jax.profiler.start_trace`` / ``stop_trace`` and marks what the host is
doing with ``jax.profiler.TraceAnnotation`` spans whose names start with
``bench:``; the program's own spans (the engine's tick phases) are placed
on the same clock by ``align``.  ``reduce`` turns the trace into:

  busy_s / window_s   the union of device-op intervals, and the span from
                      the first device op to the last, averaged over chips
  op_s                device seconds per op group (``group_of``); a while
                      loop's own span is left out, its body's ops count
  gaps                idle seconds between device ops, by the host span
                      that was open at each gap's midpoint
"""
from __future__ import annotations

import bisect
import glob
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Tuple

Interval = Tuple[int, int]                      # [start_ns, end_ns)


@dataclass
class Op:
    name: str
    start: int
    end: int
    info: str                   # the event's string stats, "key=value"


@dataclass
class Trace:
    devices: Dict[str, List[Op]] = field(default_factory=dict)
    host: List[Tuple[str, int, int]] = field(default_factory=list)


def latest_xplane(log_dir) -> Path:
    files = sorted(glob.glob(str(Path(log_dir) / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(files[-1])


def load(path) -> Trace:
    """Device ops (the ``XLA Ops`` line of each ``/device:TPU:n`` plane)
    and the host's ``bench:`` spans from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") \
                and "SparseCore" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    info = " ".join(f"{k}={v}" for k, v in e.stats
                                    if isinstance(v, str))
                    s = int(e.start_ns)
                    ops.append(Op(e.name, s, s + int(e.duration_ns), info))
            if ops:
                tr.devices[plane.name] = sorted(ops, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench:"):
                        s = int(e.start_ns)
                        tr.host.append((e.name, s, s + int(e.duration_ns)))
    return tr


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def hlo_name(op: Op) -> str:
    """The op's HLO instruction name.  On the chip an event is named by
    its whole HLO text (``%fusion.218 = bf16[...] fusion(...)``); the
    name is the part before `` = ``."""
    return op.name.split(" = ", 1)[0].lstrip("%")


def base_name(op: Op) -> str:
    """The HLO name without the suffixes XLA appends to copies of one
    instruction (``.7``, ``.remat``, ``.clone``)."""
    return re.sub(r"(\.(\d+|remat\d*|clone\d*))+$", "", hlo_name(op))


def group_of(op: Op) -> str:
    """The op's group in the breakdown: its ``tf_op`` where the trace
    gives one; else, for an event named by its HLO text, the instruction
    with its result type and opcode (``fusion.218 = bf16[151936,2048]
    fusion``: one instruction of a layer scan's body sums over its
    layers); else the name without its numeric suffix."""
    m = re.search(r"\btf_op[=:]?\s*(\S+)", op.info)
    if m:
        return m.group(1)
    m = _typed(op)
    if m:
        return f"{hlo_name(op)} = {m.group(1)} {m.group(2)}"
    return re.sub(r"\.\d+$", "", op.name)


def _typed(op: Op):
    """(result type, opcode) of an event named by its HLO text."""
    if " = " not in op.name:
        return None
    rest = re.sub(r"\{[^{}]*\}", "", op.name.split(" = ", 1)[1])
    return re.match(r"(\([^()]*\)|\S+)\s+([\w-]+)", rest)


def encloses(op: Op) -> bool:
    """A while, conditional or call op: its span holds its body's ops,
    which the trace lists too."""
    m = _typed(op)
    return bool(m) and m.group(2) in ("while", "conditional", "call")


def is_kernel(op: Op, kernel: str) -> bool:
    """Whether ``op`` is a call of the Pallas kernel ``kernel``: a custom
    call whose HLO name is the kernel's name with XLA's suffixes (the ops
    of its reference backward or its wrapper are other ops)."""
    return base_name(op) == kernel and any(
        k in op.name or k in op.info for k in ("custom-call",
                                               "custom_call"))


def op_seconds(tr: Trace, match: Callable[[Op], bool]) -> Tuple[float, int]:
    """(device seconds, events) of the ops ``match`` picks, summed over
    chips and divided by their number."""
    tot, n = 0, 0
    for ops in tr.devices.values():
        for o in ops:
            if match(o):
                tot += o.end - o.start
                n += 1
    k = max(1, len(tr.devices))
    return tot / 1e9 / k, n


def host_at(host: List[Tuple[str, int, int]], starts: List[int],
            t: int) -> str:
    """The innermost (latest-starting) host span open at ``t``; ``host``
    is sorted by start and ``starts`` lists those starts."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(-1, i - 64), -1):
        name, s, e = host[j]
        if s <= t < e:
            return name[len("bench:"):]
    return "outside_spans"


def reduce(tr: Trace, top: int = 10):
    """The trace's numbers, or None where it holds no device op."""
    if not tr.devices:
        return None
    busy, window = [], []
    op_s: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    host = sorted(tr.host, key=lambda h: h[1])
    starts = [h[1] for h in host]
    for i, ops in enumerate(tr.devices.values()):
        u = union([(o.start, o.end) for o in ops])
        busy.append(sum(e - s for s, e in u))
        window.append(u[-1][1] - u[0][0])
        for o in ops:
            if encloses(o):
                continue
            g = group_of(o)
            op_s[g] = op_s.get(g, 0.0) + (o.end - o.start) / 1e9
        if i == 0:
            for (_, e0), (s1, _) in zip(u, u[1:]):
                where = host_at(host, starts, (e0 + s1) // 2)
                gaps[where] = gaps.get(where, 0.0) + (s1 - e0) / 1e9
    k = len(tr.devices)
    ranked = lambda d: [[n, v / (k if d is op_s else 1)] for n, v in
                        sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": sum(busy) / k / 1e9, "window_s": sum(window) / k / 1e9,
            "chips": k, "device_ops": ranked(op_s), "idle_gaps": ranked(gaps)}


def align(marks: List[Tuple[str, float, float]],
          anchors: List[Tuple[int, float]]) -> List[Tuple[str, int, int]]:
    """Place spans timed by the host's ``perf_counter`` (seconds) on the
    trace's clock.  ``anchors`` pairs a ``bench:`` span's start in the
    trace (ns) with the ``perf_counter`` read just before it opened; the
    median offset maps the rest."""
    if not anchors:
        return []
    offs = sorted(t_ns - pc * 1e9 for t_ns, pc in anchors)
    off = offs[len(offs) // 2]
    return [(f"bench:{n}", int(a * 1e9 + off), int(b * 1e9 + off))
            for n, a, b in marks]
