"""run.py: no result without a TPU or without the program; the last
line's schema."""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import tiny
from harness import spec

RUN = tiny.load_run()


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "train-horn-8l", "--seed", str(2 ** 31 + 7), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=240)


def _no_result(p):
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        assert not line.startswith("{"), line


def test_no_tpu_exits_nonzero_without_result():
    p = _run(spec.ROOT)
    _no_result(p)
    assert "no TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    _no_result(p)
    assert "not in this checkout" in p.stderr


def test_result_line_schema():
    cell = spec.find_cell("serve-reasoning")
    out = {"correct": True, "attempted": 30, "failed": 0,
           "peak_bytes": 123, "setup_s": 40.0,
           "checks": {"served_logit_gap": {"value": 0.1, "limit": 0.5}},
           "obs": {"window": (0.0, 10.0), "window_mono": (0.0, 10.0),
                   "requests": [{"due": 0.0, "times": [1.0, 1.5, 2.5]}],
                   "cutoff": 10.0},
           "reduced": {"busy_s": 0.9, "window_s": 1.0, "chips": 1,
                       "device_ops": [["fusion", 0.5]],
                       "idle_gaps": [["harness", 0.1]]}}
    dev = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                           memory_stats=lambda: {})]
    peaks = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    e2e = RUN.result_line(cell, SimpleNamespace(trace=0), out, dev, peaks)
    assert list(e2e)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(e2e)[-1] == "checks"
    assert set(e2e["metrics"]) == {"gen_tok_s", "itl_p99_ms", "setup_s"}
    assert e2e["metrics"]["itl_p99_ms"] == {"value": 1000.0, "unit": "ms"}
    assert e2e["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1, "memory_peak_bytes": 123}
    traced = RUN.result_line(cell, SimpleNamespace(trace=1), out, dev,
                             peaks)
    assert traced["device"]["busy_s"] == 0.9
    assert traced["device"]["window_s"] == 1.0
    assert traced["breakdown"]["device_ops"] == [["fusion", 0.5]]
    assert "device_idle.reasoning" in traced["metrics"]
    assert "gen_tok_s" not in traced["metrics"]
    assert list(traced)[-1] == "checks"
    json.dumps(traced)
