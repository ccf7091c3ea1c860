"""dropout_matmul_roofline.train: the least time the traced
dropout_matmul calls could take (each call's kept blocks from the step's
Horn masks; the larger of operations over peak and bytes over bandwidth),
over the device time of the kernel's events in the trace."""
from harness import counts
from harness.stats import kernel_time

KERNEL = "dropout_matmul"


def read(obs):
    kt = kernel_time(obs, KERNEL)
    if kt is None or "kept_blocks" not in obs:
        return None
    sec, events = kt
    c, mix = obs["config"], obs["traffic"]
    m, horn = c["model"], c["horn"]
    kept = obs["kept_blocks"]                       # [step][layer][group]
    calls = events / (len(kept) * m["num_hidden_layers"])
    rows = mix["batch"] // horn["groups"] * mix["seq_len"]
    ideal = sum(counts.roofline_s(*counts.dropout_matmul_call(
        rows, m["hidden_size"], m["intermediate_size"], horn["block"], kb),
        obs["peaks"]) for step in kept for kb in step)
    return 100.0 * ideal * calls / sec
