"""paged_attn_roofline.hybrid: the least time the traced ticks' paged
chunk-attention calls could take in the hybrid's attention layers (per
layer, as for the reasoning cell: causal scores over each live slot's
pages, and its live K/V pages read once, at head_dim hidden / heads),
over the device time of the kernel's events in the trace."""
from harness import counts, counts_hybrid
from harness.serve import traced_chunks
from harness.stats import kernel_time

KERNEL = "paged_chunk_attention"


def read(obs):
    kt = kernel_time(obs, KERNEL)
    if kt is None:
        return None
    m = obs["config"]["model"]
    shape = counts_hybrid.attn_shape(m)
    ideal = sum(counts.roofline_s(*counts.paged_attn_call(
        shape, ch, obs["page_size"]), obs["peaks"])
        for ch in traced_chunks(obs)) * m["layer_types"].count("attention")
    return 100.0 * ideal / kt[0]
