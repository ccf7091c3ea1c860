"""paged_attn_roofline.reasoning: the least time the traced ticks' paged
chunk-attention calls could take (per layer: causal scores over each live
slot's pages, and its live K/V pages read once), over the device time of
the kernel's events in the trace."""
from harness import counts
from harness.serve import traced_chunks
from harness.stats import kernel_time

KERNEL = "paged_chunk_attention"


def read(obs):
    kt = kernel_time(obs, KERNEL)
    if kt is None:
        return None
    m = obs["config"]["model"]
    ideal = sum(counts.roofline_s(*counts.paged_attn_call(
        m, ch, obs["page_size"]), obs["peaks"])
        for ch in traced_chunks(obs)) * m["num_hidden_layers"]
    return 100.0 * ideal / kt[0]
