"""itl_p99_ms: 99th percentile of the gap between consecutive output
tokens of a stream, over every gap that closed in the window.  In the
reasoning loop about 4% of the gaps fall in ticks that also prefill a
refilled slot's prompt (about three times a decode tick), so the 95th
percentile sits on the edge between the two kinds of tick and swings from
run to run; the 99th lies among the prefill ticks, with some 30 gaps of
a 30 s window beyond it."""
from harness.stats import pct, token_gaps


def read(obs):
    if "requests" not in obs:
        return None
    gaps = token_gaps(obs)
    return 1e3 * pct(gaps, 99) if gaps else None
