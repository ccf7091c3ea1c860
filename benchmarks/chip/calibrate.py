"""Readings that set a cell's limits: the program's, the control's and the
planted faults', on many seeds in one process.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 1,2,3 [--seconds S] [--control] [--faults]

The benchmark's own runs never run this.  For each seed it prints one JSON
line with the numbers the cell compares:

  train   the program's readings (set-up's three steps, no window); with
          --control the reference computed in fp8 put in the program's
          place; with --faults the reference with half of each batch left
          out (the mean over the rest).  A state left unchanged reads 1 by
          construction and needs no run.
  serve   a run of the cell at its own load for --seconds, the program's
          widest served-token gap; with --control, at the same positions
          of the same requests, the gap of the token the fp8 control puts
          first.

Limits are then set between the program's largest reading and the
smallest reading that the control (or a fault) gives.
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


def train_seed(cell, seed: int, control: bool, faults: bool) -> dict:
    import jax
    from harness import train as T
    mix, c = cell.traffic, cell.config
    m = c["model"]
    _, step_fn, sh, state, jseed, wkey = T.build(cell, seed)
    batches = T.stage_batches(mix, m["vocab_size"], jseed, sh["batch"])[:3]
    compiled = step_fn.lower(state, batches[0]).compile()
    state, prog = T.program_readings(compiled, state, batches, m, wkey,
                                     c["optimizer"]["b1"])
    for x in jax.tree.leaves(state):
        x.delete()
    del state, compiled
    gc.collect()
    ref = T.reference_readings(cell, jseed, wkey, batches)
    skip = c["checks"]["skip_still_leaves"]
    out = {"seed": seed, "program": T.compare(prog, ref, skip),
           "raw": {"program": prog, "reference": ref}}
    if control:
        ctl = T.reference_readings(cell, jseed, wkey, batches, "fp8")
        out["control"] = T.compare(ctl, ref, skip)
    if faults:
        half = T.reference_readings(cell, jseed, wkey, batches,
                                    rows=mix["batch"] // 2)
        out["half_batch"] = T.compare(half, ref, skip)
    return out


def serve_seed(cell, seed: int, seconds: float, control: bool,
               devices) -> dict:
    from harness import serve as S, traffic as TR
    args = argparse.Namespace(workload=cell.name, seed=seed,
                              seconds=seconds, trace=0)
    out = S.run(cell, args, devices, time.monotonic())
    res = {"seed": seed, "program": {
        "served_logit_gap": out["checks"]["served_logit_gap"]["value"]},
        "sample": out["obs"]["served_sample"]}
    if control:
        g = S.served_gaps(cell, out["wkey"], out["sample"],
                          TR.longest_context(cell.traffic), control=True)
        res["control"] = {"served_logit_gap": g["control"]}
    gc.collect()
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
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
        if cell.config["kind"] == "train":
            r = train_seed(cell, s, a.control, a.faults)
        else:
            r = serve_seed(cell, s, a.seconds, a.control, devices)
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
