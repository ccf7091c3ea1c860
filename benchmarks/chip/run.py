"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is looked up in ``BENCHMARK.json``;
its configuration names the runner (``harness/<kind>.py``).  The run
checks that JAX finds the chips the cell asks for (and exits non-zero
with no result otherwise), sets up, measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints as
its last stdout line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared, with its limit.
The same numbers end standard error.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

from harness import spec  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(cell, args, out: dict, devices, peaks: dict) -> dict:
    from harness import device
    obs = dict(out["obs"], peaks=peaks, setup_s=out["setup_s"],
               reduced=out.get("reduced"), cell=cell.name,
               config=cell.config, traffic=cell.traffic)
    metrics = spec.read_metrics(
        cell.per_layer if args.trace else cell.end_to_end, obs)
    res = {"correct": bool(out["correct"]), "attempted": int(out["attempted"]),
           "failed": int(out["failed"]), "metrics": metrics,
           "device": device.device_block(devices, out["peak_bytes"])}
    red = out.get("reduced")
    if args.trace and red is not None:
        res["device"]["busy_s"] = red["busy_s"]
        res["device"]["window_s"] = red["window_s"]
        res["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    res["window_compiles"] = out["obs"].get("window_compiles", 0)
    res["checks"] = out["checks"]
    return res


def main(argv=None, *, cell=None, fault: str = "",
         check_device: bool = True) -> dict:
    """One run; returns the result line (also printed).  Tests pass a
    ``cell`` of their own, skip the device check and plant a ``fault``."""
    args = parse(argv)
    cell = cell if cell is not None else spec.find_cell(args.workload)
    from harness import common, device
    common.import_program()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if check_device:
        # the checkout's own compile cache, at a fixed path (the path is
        # part of the cache's key): only a cell's first run compiles
        jax.config.update("jax_compilation_cache_dir",
                          str(spec.ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        devices = device.require_chips(cell.chips)
        peaks = device.peaks_for(devices[0].device_kind)
    else:
        devices = jax.devices()[:cell.chips]
        peaks = device.PEAKS["TPU v5 lite"]
    runner = spec.load_runner(cell.config["kind"])
    out = runner.run(cell, args, devices, T_START, fault=fault)
    res = result_line(cell, args, out, devices, peaks)
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return res


if __name__ == "__main__":
    main()
