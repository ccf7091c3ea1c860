"""decode_tick_ms.hybrid: the mean ``engine:tick`` duration over the
window's ticks whose work counts show no prefill (``prefill_tokens`` 0),
from the program's tick records (the traced run records them)."""
import time


def read(obs):
    recs = obs.get("tick_records")
    if not recs:
        return None
    m0, m1 = obs["window_mono"]
    off = time.monotonic() - time.perf_counter()
    ms = [1e3 * (t1 - t0) for t0, t1, counts in recs
          if counts.get("real_tokens") and counts.get("prefill_tokens") == 0
          and m0 <= t0 + off < m1]
    return sum(ms) / len(ms) if ms else None
