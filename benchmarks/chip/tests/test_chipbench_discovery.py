"""A configuration, a traffic mix, a metric and a runner added only as
files and entries are found by name; nothing already there is edited."""
import json
import shutil

from harness import spec


def test_added_files_are_found(tmp_path):
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(spec.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "data",
                                                  "__pycache__"))
    bench = spec.load_benchmark()
    raw = json.loads((spec.ROOT / bench["configs"][0]["file"]).read_text())
    raw["num_hidden_layers"] = 2
    raw["bench"]["kind"] = "probe"
    (bench_dir / "configs" / "tiny-model.json").write_text(json.dumps(raw))
    (bench_dir / "workloads" / "tiny-mix.json").write_text(
        json.dumps({"loop": "train", "batch": 2, "seq_len": 8}))
    (bench_dir / "metrics" / "probe_ms.tiny.py").write_text(
        "def read(obs):\n    return obs.get('probe')\n")
    (bench_dir / "harness" / "probe.py").write_text(
        "def run(cell, args, devices, t_start, fault=''):\n"
        "    return {'kind': 'probe'}\n")
    bench["configs"].append({
        "name": "tiny-model", "source": "https://example.org/tiny",
        "file": "benchmarks/chip/configs/tiny-model.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({"name": "tiny-cell", "config": "tiny-model",
                               "traffic": "tiny-mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0].setdefault("workloads", []).append("tiny-cell")
    bench["per_layer"].append({
        "name": "probe_ms.tiny", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "device",
        "moves": bench["end_to_end"][0]["name"], "workloads": ["tiny-cell"]})

    cell = spec.find_cell("tiny-cell", bench, bench_dir=bench_dir)
    assert cell.config["model"]["num_hidden_layers"] == 2
    assert cell.traffic == {"loop": "train", "batch": 2, "seq_len": 8}
    assert [m.name for m in cell.per_layer] == ["probe_ms.tiny"]
    assert {m.name for m in cell.end_to_end} == {
        bench["end_to_end"][0]["name"], "setup_s"}
    assert spec.read_metrics(cell.per_layer, {"probe": 2.5}) == {
        "probe_ms.tiny": {"value": 2.5, "unit": "ms"}}
    # a reader that finds nothing leaves its metric out
    assert spec.read_metrics(cell.per_layer, {}) == {}
    runner = spec.load_runner("probe", bench_dir / "harness")
    assert runner.run(cell, None, None, 0.0) == {"kind": "probe"}


def test_every_named_metric_and_runner_exists():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.find_cell(w["name"], bench)
        assert cell.per_layer and len(cell.end_to_end) >= 2
        assert (spec.BENCH_DIR / "harness"
                / f"{cell.config['kind']}.py").is_file()
        reported = {m.name for m in cell.end_to_end}
        for m in bench["per_layer"]:
            if w["name"] in m["workloads"]:
                assert m["moves"] in reported
