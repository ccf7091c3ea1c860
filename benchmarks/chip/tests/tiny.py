"""A cell of the benchmark cut to a size the CPU runs in seconds: the same
harness, runner and checks, with tiny widths, short traffic and the
limits read at the test sizes."""
import copy
import importlib.util

from harness import spec

TINY = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
            head_dim=32, intermediate_size=512, vocab_size=8192,
            num_hidden_layers=2)

# published widths at two layers and a cut vocabulary: the smallest size
# at which the controls read as they do at the cells' own
WIDE = dict(hidden_size=2048, num_attention_heads=16, num_key_value_heads=8,
            head_dim=128, intermediate_size=6144, vocab_size=16384,
            num_hidden_layers=2)

# Limits at the test sizes, set as the cells' are (between the program's
# largest reading and the control's or a fault's smallest), from CPU
# readings at WIDE: program loss 6.1e-5..1.5e-4, gradient 1.5e-4..3.9e-4,
# change 8.3e-5..1.8e-4, served gap 0..0.076; control 2.6e-4..1.3e-3,
# 2.3e-3..3.6e-3, 6.3e-4..2.0e-3, 0.17..0.63.  The cells' own limits come
# from chip readings at their own size, where both sides read higher.
LIMITS = {
    "train-horn-8l": {"loss_rel_gap": 6e-4, "grad_norm_gap": 1.2e-3,
                      "change_norm_gap": 3.8e-4},
    "serve-reasoning": {"served_logit_gap": 0.12},
}

TRAFFIC = {
    "train-horn-8l": dict(batch=8, seq_len=128, staged_batches=8),
    "serve-reasoning": dict(
        slots=4, queue=400,
        prompt={"dist": "loguniform", "min": 8, "max": 32},
        output={"dist": "uniform", "min": 16, "max": 32}),
}


def cell(name: str, size: dict = TINY) -> spec.Cell:
    c = spec.find_cell(name)
    cfg = copy.deepcopy(c.config)
    cfg["model"].update(size)
    cfg["checks"]["limits"] = dict(LIMITS[name])
    if cfg["kind"] == "serve":
        cfg["engine"].update(slots=4, pages=64, token_budget=32)
        cfg["trace"] = {"after_s": 0.5, "seconds": 0.5}
        cfg["checks"]["served_tokens"] = 200
    traffic = dict(c.traffic, **TRAFFIC[name])
    return spec.Cell(name, c.chips, c.config_name, c.traffic_name, cfg,
                     traffic, c.end_to_end, c.per_layer)


def load_run():
    """benchmarks/chip/run.py, loaded by path (``benchmarks/run.py`` is
    another module named ``run``)."""
    spec_ = importlib.util.spec_from_file_location(
        "chipbench_run", spec.BENCH_DIR / "run.py")
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod


def run(name: str, fault: str = "", seconds: float = 2.0, seed: int = 5,
        size: dict = TINY):
    return load_run().main(["--workload", name, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0"],
                           cell=cell(name, size), fault=fault,
                           check_device=False)
