"""The hybrid serving cell cut to a size the CPU runs in seconds: the same
runner (``harness/serve_hybrid.py``), found by name through ``run.main``,
with one period of granite's 10-layer pattern at tiny widths, short
traffic and the limit read at the test size."""
import copy

import jax
import numpy as np
import pytest

import calibrate_hybrid
import tiny
from harness import common, counts_hybrid, spec
from harness import serve_hybrid as SH

CELL = "serve-hybrid-reasoning"
TINY = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
            shared_intermediate_size=256, intermediate_size=256,
            vocab_size=2048, mamba_n_heads=8, mamba_d_head=32,
            mamba_d_state=32, num_hidden_layers=10)
TRAFFIC = dict(slots=4, queue=400,
               prompt={"dist": "loguniform", "min": 8, "max": 32},
               output={"dist": "uniform", "min": 16, "max": 32})
# The limit at this size, set as the cell's is (between the program's
# largest reading and the fault's smallest): CPU readings here give the
# program 0.0 on seeds 3, 5 and 2**31 + 9 (every served token is the
# reference's first choice), the planted token fault 0.30..0.34 on seeds
# 5 and 7.
LIMIT = 0.05

# granite's published widths at two layers (a Mamba mixer, then attention)
# and a cut vocabulary, as tiny.WIDE cuts Qwen.  At 0.02 two layers leave
# the x12 embedding far ahead in the residual stream, so every position's
# best logit is its own token's by a margin no fp8 rounding closes (the
# control reads 0 there); forty layers of 0.22-scaled sublayers dilute it.
# Matrices drawn at 0.1 dilute it in two (a sublayer's output grows
# faster than the scale, the embedding as the scale), and the readings
# land where the cell's own do on the chip: CPU readings here give the
# program 0..0.022 and the control 0.22..0.38 on seeds 5, 7, 13 and
# 2**31 + 9.
WIDE = dict(vocab_size=16384, num_hidden_layers=2, initializer_range=0.1)
WIDE_TYPES = ["mamba", "attention"]


def cell(limit=LIMIT, size=TINY, types=None) -> spec.Cell:
    c = spec.find_cell(CELL)
    cfg = copy.deepcopy(c.config)
    cfg["model"].update(size)
    cfg["model"]["layer_types"] = types or cfg["model"]["layer_types"][:10]
    cfg["checks"]["limits"] = {"served_logit_gap": limit}
    cfg["engine"].update(slots=4, pages=64, token_budget=32)
    cfg["trace"] = {"after_s": 0.5, "seconds": 0.5}
    cfg["checks"]["served_tokens"] = 200
    return spec.Cell(CELL, c.chips, c.config_name, c.traffic_name, cfg,
                     dict(c.traffic, **TRAFFIC), c.end_to_end, c.per_layer)


def run(seed=5, fault="", trace=0, seconds=2.0):
    return tiny.load_run().main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)], cell=cell(), fault=fault,
        check_device=False)


def test_cell_is_found_by_name():
    c = spec.find_cell(CELL)
    assert c.config["kind"] == "serve_hybrid"
    assert callable(spec.load_runner(c.config["kind"]).run)
    assert {m.name for m in c.end_to_end} == {"gen_tok_s", "itl_p99_ms",
                                              "setup_s"}
    assert {m.name for m in c.per_layer} == {
        "ssd_scan_roofline.hybrid", "mfu.hybrid", "decode_tick_ms.hybrid",
        "device_idle.hybrid", "paged_attn_roofline.hybrid",
        "host_ms_per_tick.reasoning"}


def test_tiny_cell_runs_and_is_correct():
    res = run(2 ** 31 + 9)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"gen_tok_s", "itl_p99_ms", "setup_s"}
    assert res["window_compiles"] == 0
    assert res["checks"]["served_logit_gap"]["value"] <= LIMIT


def test_planted_token_fault_is_not_correct():
    res = run(fault="token")
    assert res["correct"] is False
    assert res["checks"]["served_logit_gap"]["value"] > LIMIT


def test_traced_run_reads_the_program_counts():
    """On the CPU the trace holds no TPU plane, so the device readers find
    nothing; the program's tick records and phases are there and the
    decode-tick and host-time readers read them."""
    res = run(trace=1)
    assert res["correct"] is True
    assert res["metrics"]["decode_tick_ms.hybrid"]["value"] > 0
    assert res["metrics"]["host_ms_per_tick.reasoning"]["value"] > 0


def test_paged_attn_roofline_counts_the_attention_layers():
    """The reader counts the 4 attention layers at head_dim hidden / heads
    = 64 (the file has no head_dim key) against the kernel's device time:
    one traced tick of 2 decode slots at 16 and 40 tokens of context."""
    from harness import tracing
    m = spec.find_cell(CELL).config["model"]
    tr = tracing.Trace()
    tr.devices["/device:TPU:0"] = [tracing.Op(
        "paged_chunk_attention.6 = bf16[24,8,4,64] custom-call", 0, 1000,
        "")]
    peaks = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    obs = {"trace": tr, "config": {"model": m}, "page_size": 16,
           "peaks": peaks, "traced_ticks": [0],
           "ticks": [(0.0, 1, np.array([15, 39, 0]), np.array([1, 1, 0]))]}
    read = spec.load_reader("paged_attn_roofline.hybrid")
    # per layer: K and V of 1 + 3 pages of 8 kv heads x 16 x 64 bf16, and
    # q and out of 2 tokens x 32 heads x 64 bf16
    nbytes = 2 * 4 * 8 * 16 * 64 * 2 + 2 * 2 * 32 * 64 * 2
    assert read(obs) == pytest.approx(
        100 * 4 * nbytes / peaks["hbm_bytes_s"] / 1e-6)
    assert read({}) is None


def test_serve_hybrid_control_is_not_correct():
    """The control -- the reference computed in fp8, one precision below
    the bf16 the configuration states -- fails the cell's own limit where
    the program passes it (at WIDE; on the chip at the cell's own size:
    calibrate_hybrid.py)."""
    limit = spec.find_cell(CELL).config["checks"]["limits"][
        "served_logit_gap"]
    c = cell(limit, WIDE, WIDE_TYPES)
    r = calibrate_hybrid.serve_seed(c, 13, 3.0, True, jax.devices()[:1])
    limits = c.config["checks"]["limits"]
    assert common.limit_checks(r["program"], limits)[1] is True
    assert common.limit_checks(r["control"], limits)[1] is False
    assert r["correct"] == {"program": True, "control": False}


def test_state_bytes_match_the_engine():
    c = cell()
    m = c.config["model"]
    engine, _, _ = SH.build(c, 3, timeline=False)
    rng = np.random.default_rng(0)
    for n in (5, 9, 12):
        engine.submit(rng.integers(0, m["vocab_size"], n).astype(np.int32),
                      4)
    engine.step()
    assert engine.stats.ssm_state_bytes == counts_hybrid.tick_state_bytes(
        m, live_slots=3) > 0
    engine.step()
    assert engine.stats.ssm_state_bytes == 2 * counts_hybrid.tick_state_bytes(
        m, live_slots=3)


def test_per_slot_chunks_are_kept_for_traced_ticks_only():
    """The runner keeps a tick's per-slot device arrays only while the
    caller marks the tick traced (the FLOP and roofline readers read those
    ticks alone); other ticks record their time and chunk width."""
    c = cell()
    m = c.config["model"]
    keep = [False]
    engine, ticks, _ = SH.build(c, 3, timeline=False, keep=keep)
    rng = np.random.default_rng(0)
    for n in (5, 9):
        engine.submit(rng.integers(0, m["vocab_size"], n).astype(np.int32),
                      4)
    engine.step()
    keep[0] = True
    engine.step()
    (_, C0, s0, n0), (_, C1, s1, n1) = ticks
    assert C0 > 1 and s0 is None and n0 is None
    assert C1 == 1 and list(np.asarray(n1)) == [1, 1, 0, 0]
    assert list(np.asarray(s1)) == [5, 9, 0, 0]
