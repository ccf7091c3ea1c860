"""Trace reduction: the busy union, idle gaps named by host span, and
kernel time by name, on hand-made traces."""
import pytest

from harness import tracing
from harness.stats import kernel_time


def test_union_merges_overlaps():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == [
        (0, 4), (5, 7), (9, 10)]


def _hand_trace():
    op = tracing.Op
    tr = tracing.Trace()
    # busy [0,10) [10,20) [30,40) and an overlapping [35,50): union 40 of 50
    tr.devices["/device:TPU:0"] = [
        op("fusion.1", 0, 10, ""),
        op("paged_chunk_attention.2", 10, 20,
           'long_name=%paged_chunk_attention.2 = bf16[4,8,32,128] '
           'custom-call(%a, %b), custom_call_target="tpu_custom_call"'),
        op("fusion.3", 30, 40, "tf_op=jit(step)/jit(paged_chunk_attention)"),
        op("fusion.4", 35, 50, "")]
    tr.host = [("bench:tick", 0, 45), ("bench:commit", 20, 31)]
    return tr


def test_reduce_busy_window_and_named_gaps():
    red = tracing.reduce(_hand_trace())
    assert red["busy_s"] == pytest.approx(40e-9)
    assert red["window_s"] == pytest.approx(50e-9)
    assert red["idle_gaps"] == [["commit", pytest.approx(10e-9)]]
    names = dict((n, v) for n, v in red["device_ops"])
    assert names["fusion"] == pytest.approx(25e-9)
    assert names["jit(step)/jit(paged_chunk_attention)"] == \
        pytest.approx(10e-9)


def test_kernel_time_by_name():
    obs = {"trace": _hand_trace()}
    # the Pallas call alone: not a fusion under the kernel's wrapper, not
    # a kernel whose name it only begins with
    assert kernel_time(obs, "paged_chunk_attention") == \
        (pytest.approx(10e-9), 1)
    assert kernel_time(obs, "paged") is None
    assert kernel_time({}, "paged_chunk_attention") is None
    assert tracing.reduce(tracing.Trace()) is None


def test_align_places_host_spans_on_the_trace_clock():
    marks = [("plan", 1.0, 1.5), ("commit", 2.0, 2.25)]
    spans = tracing.align(marks, [(5_000_000_000, 0.5), (6_000_000_000, 1.5)])
    assert spans == [("bench:plan", 5_500_000_000, 6_000_000_000),
                     ("bench:commit", 6_500_000_000, 6_750_000_000)]


def test_kernel_and_groups_by_hlo_text():
    """On the chip an op event is named by its whole HLO text; the kernel
    is the custom call of that name, a copy made by remat included, and
    the breakdown groups by instruction, result type and opcode, and
    leaves out a while loop's span, which holds its body's ops."""
    op = tracing.Op
    tr = tracing.Trace()
    tr.devices["/device:TPU:0"] = [
        op('%dropout_matmul.1 = f32[4,1024,6144]{2,1,0:T(8,128)S(1)} '
           'custom-call(%m.1, %x.1, %w.1), '
           'custom_call_target="tpu_custom_call"', 0, 10, ""),
        op('%dropout_matmul.1.remat = f32[4,1024,6144]{2,1,0:T(8,128)} '
           'custom-call(%m.1, %x.1, %w.1)', 10, 30, ""),
        op('%fusion.361 = (bf16[8,512]{1,0:T(8,128)(2,1)S(1)}, '
           'f32[8,512,151936]{2,1,0:T(8,128)}) fusion(bf16[8,512,2048]'
           '{2,1,0:T(8,128)(2,1)S(1)} %dropout_matmul.1), kind=kOutput',
           30, 60, ""),
        op('%fusion.361 = (bf16[8,512]{1,0:T(8,128)(2,1)S(1)}, '
           'f32[8,512,151936]{2,1,0:T(8,128)}) fusion(bf16[8,512,2048]'
           '{2,1,0:T(8,128)(2,1)S(1)} %dropout_matmul.1), kind=kOutput',
           60, 90, ""),
        op('%while.18 = (s32[], bf16[8,512,2048]{2,1,0}) while(%tuple.3), '
           'condition=%cond, body=%body', 0, 95, "")]
    assert kernel_time({"trace": tr}, "dropout_matmul") == \
        (pytest.approx(30e-9), 2)
    red = tracing.reduce(tr)
    assert red["device_ops"][0] == [
        "fusion.361 = (bf16[8,512], f32[8,512,151936]) fusion",
        pytest.approx(60e-9)]
    assert not any("while" in n for n, _ in red["device_ops"])
    assert red["busy_s"] == pytest.approx(95e-9)


def test_kernels_found_in_ops_recorded_on_the_chip():
    """Op events as the profiler recorded them on a v5e (``data/``): the
    Pallas calls are found by name, the compiler's own custom calls
    (buffer allocation, bitcasts) and fusions are not."""
    import json
    from pathlib import Path
    rec = json.loads((Path(__file__).parent / "data" / "chip_ops.json")
                     .read_text())
    tr = tracing.Trace()
    for cell in ("train", "serve"):
        tr.devices[f"/device:TPU:{cell}"] = [
            tracing.Op(e["name"], e["start_ns"],
                       e["start_ns"] + e["duration_ns"], "")
            for e in rec["events"] if e["cell"] == cell]
    obs = {"trace": tr}
    # custom-call.5, a bitcast of the kernel's output shape, is not a call
    dm, n = kernel_time(obs, "dropout_matmul")
    assert n == 2 and dm == pytest.approx((7408595 + 7987547) / 2 * 1e-9)
    assert kernel_time(obs, "flash_attention")[1] == 1
    assert kernel_time(obs, "paged_chunk_attention")[1] == 1
    groups = [n for n, _ in tracing.reduce(tr)["device_ops"]]
    assert "dropout_matmul.49 = f32[4,1024,6144] custom-call" in groups
