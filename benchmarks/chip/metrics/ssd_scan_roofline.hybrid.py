"""ssd_scan_roofline.hybrid: the least time the traced ticks' SSD scan
calls could take (per Mamba layer, harness/counts_hybrid.ssd_call: each
live slot's f32 state read and written once, its x, B, C, dt read and y
written, the chunked dual form's operations; the larger of operations
over peak and bytes over bandwidth), over the device time of the
kernel's events in the trace."""
from harness import counts, counts_hybrid
from harness.serve import traced_chunks
from harness.stats import kernel_time

KERNEL = "ssd_chunk_scan"


def read(obs):
    kt = kernel_time(obs, KERNEL)
    if kt is None:
        return None
    m = obs["config"]["model"]
    layers = m["layer_types"].count("mamba")
    ideal = sum(counts.roofline_s(*counts_hybrid.ssd_call(m, ch),
                                  obs["peaks"])
                for ch in traced_chunks(obs)) * layers
    return 100.0 * ideal / kt[0]
