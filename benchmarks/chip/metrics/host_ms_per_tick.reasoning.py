"""host_ms_per_tick.reasoning: mean host time per engine tick outside the
device call -- the TickTimeline phases plan + host_prep + commit -- over
the ticks of the window (the traced run records the timeline)."""


def read(obs):
    ph = obs.get("tick_phases")
    if not ph:
        return None
    m0, m1 = obs["window_mono"]
    import time
    off = time.monotonic() - time.perf_counter()
    host = [b - a for n, a, b in ph
            if n in ("plan", "host_prep", "commit") and m0 <= a + off < m1]
    ticks = sum(1 for n, a, _ in ph if n == "plan" and m0 <= a + off < m1)
    return 1e3 * sum(host) / ticks if ticks else None
