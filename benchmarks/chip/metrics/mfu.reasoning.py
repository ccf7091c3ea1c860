"""mfu.reasoning: required operations of the real prefill and decode
tokens of the traced ticks (harness/counts.py), over the traced window and
the chip's bf16 peak."""
from harness.serve import tick_flops


def read(obs):
    red = obs.get("reduced")
    if not red or not obs.get("traced_ticks"):
        return None
    return 100.0 * tick_flops(obs["config"]["model"], obs) / (
        red["window_s"] * red["chips"] * obs["peaks"]["bf16_flops"])
