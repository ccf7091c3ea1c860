"""The chip: the peaks table, the check that a run has the chips it asks
for, and the device block of the result line."""
from __future__ import annotations

# Published peaks per chip, keyed by ``device_kind`` as JAX reports it.
# TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}


class NoChip(SystemExit):
    """Raised before any result is printed: no TPU, or too few chips."""


def peaks_for(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"device kind {kind!r} is not in the peaks table; "
                       f"add its published peaks to harness/device.py")
    return PEAKS[kind]


def require_chips(n: int):
    """The first ``n`` TPU devices; exits non-zero without a TPU."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    peaks_for(devs[0].device_kind)
    return devs[:n]


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip (0 where not reported)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def device_block(devices, peak_bytes: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
