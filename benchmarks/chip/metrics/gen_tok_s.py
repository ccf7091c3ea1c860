"""gen_tok_s: generated tokens committed in the window, over the
window."""


def read(obs):
    if "requests" not in obs:
        return None
    w0, w1 = obs["window"]
    n = sum(1 for r in obs["requests"] for t in r["times"] if w0 <= t <= w1)
    return n / (w1 - w0)
