"""Readings that set a hybrid serving cell's limit: the program's and the
fp8 control's, on several seeds in one process.

    python3 benchmarks/chip/calibrate_hybrid.py --workload <name> \
        --seeds 1,2,3 [--seconds S] [--control]

The benchmark's own runs never run this.  For each seed it runs the cell
at its own load for --seconds (``harness/serve_hybrid.py``) and prints one
JSON line: the program's widest served-token gap below the reference's
best logit, and with --control, at the same positions of the same
requests, the gap of the token the reference computed in fp8 puts first;
``correct`` gives each side's verdict under the cell's limit
(``common.limit_checks``).  The limit is then set between the program's
largest reading and the control's smallest.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from harness import common, spec  # noqa: E402


def serve_seed(cell, seed: int, seconds: float, control: bool,
               devices) -> dict:
    from harness import serve_hybrid as SH, traffic as TR
    args = argparse.Namespace(workload=cell.name, seed=seed,
                              seconds=seconds, trace=0)
    out = SH.run(cell, args, devices, time.monotonic())
    res = {"seed": seed, "program": {
        "served_logit_gap": out["checks"]["served_logit_gap"]["value"]},
        "sample": out["obs"]["served_sample"]}
    if control:
        g = SH.served_gaps(cell, out["wkey"], out["sample"],
                           TR.longest_context(cell.traffic), control=True)
        res["control"] = {"served_logit_gap": g["control"]}
    # each side through the cell's own comparison and limit
    limits = cell.config["checks"]["limits"]
    res["correct"] = {side: common.limit_checks(res[side], limits)[1]
                      for side in ("program", "control") if side in res}
    gc.collect()
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--control", action="store_true")
    a = ap.parse_args()
    cell = spec.find_cell(a.workload)
    common.import_program()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from harness import device
    jax.config.update("jax_compilation_cache_dir",
                      str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = device.require_chips(cell.chips)
    for s in (int(x) for x in a.seeds.split(",")):
        print(json.dumps(serve_seed(cell, s, a.seconds, a.control,
                                    devices)), flush=True)


if __name__ == "__main__":
    main()
