"""mfu.train: the Horn sub-model's required operations per trained token
(forward and backward, at the keep rate, recomputation not counted;
harness/counts.py) times the traced steps' tokens, over the traced
window and the chip's bf16 peak."""


def read(obs):
    red = obs.get("reduced")
    if not red or "traced_steps" not in obs:
        return None
    flops = obs["flops_per_token"] * obs["tokens_per_step"] \
        * obs["traced_steps"]
    return 100.0 * flops / (red["window_s"] * red["chips"]
                            * obs["peaks"]["bf16_flops"])
