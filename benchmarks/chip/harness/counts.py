"""Operations and bytes the work needs, as functions of its shapes.

These are the algorithm's counts, not the compiler's: work a program
recomputes (rematerialised blocks) or wastes (padded positions, dropped
blocks computed densely) is not counted, so a share of peak built on them
falls when the program wastes work.  ``m`` is a model-shape dict with the
published config's keys (``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``intermediate_size``,
``vocab_size``, ``num_hidden_layers``).
"""
from __future__ import annotations

from typing import Iterable, Tuple


def _dims(m: dict):
    return (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"],
            m["intermediate_size"], m["vocab_size"])


def layer_matmul_flops(m: dict, keep_ffn: float = 1.0) -> float:
    """One token through one layer's projections and gated FFN (forward):
    q, k, v, o projections plus up, gate and down over the kept share of
    the hidden units."""
    d, h, kh, hd, ff, _ = _dims(m)
    proj = 2 * d * (h + 2 * kh) * hd + 2 * h * hd * d
    return proj + 3 * 2 * d * ff * keep_ffn


def attn_flops(m: dict, n_keys: float) -> float:
    """One query token attending over ``n_keys`` keys (QK^T and PV), one
    layer."""
    _, h, _, hd, _, _ = _dims(m)
    return 4 * h * hd * n_keys


def head_flops(m: dict) -> float:
    d, *_, v = _dims(m)
    return 2 * d * v


def train_flops_per_token(m: dict, seq_len: int, keep_ffn: float) -> float:
    """Required forward + backward operations per trained token: three
    times the forward of a causal sequence of ``seq_len`` (mean keys per
    query (S + 1) / 2), the FFN at the Horn sub-model's keep rate, the
    tied head on every position."""
    L = m["num_hidden_layers"]
    fwd = L * (layer_matmul_flops(m, keep_ffn)
               + attn_flops(m, (seq_len + 1) / 2)) + head_flops(m)
    return 3.0 * fwd


def serve_tick_flops(m: dict, chunks: Iterable[Tuple[int, int]]) -> float:
    """Required forward operations of one serving tick: ``chunks`` holds
    (start, length) of each live slot's chunk; every real token runs the
    layers, attends over its causal prefix, and each slot's last token
    runs the head."""
    L = m["num_hidden_layers"]
    total = 0.0
    for start, n in chunks:
        if n <= 0:
            continue
        keys = n * start + n * (n + 1) / 2          # sum of (pos + 1)
        total += L * (n * layer_matmul_flops(m) + attn_flops(m, keys))
        total += head_flops(m)
    return total


def paged_attn_call(m: dict, chunks: Iterable[Tuple[int, int]],
                    page_size: int, kv_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one layer's paged chunk-attention call: the
    live pages of each slot's K and V are read once, queries read and
    outputs written once (bf16)."""
    _, h, kh, hd, _, _ = _dims(m)
    flops = 0.0
    nbytes = 0.0
    for start, n in chunks:
        if n <= 0:
            continue
        keys = n * start + n * (n + 1) / 2
        flops += attn_flops(m, keys)
        pages = -(-(start + n) // page_size)
        nbytes += 2 * pages * kh * page_size * hd * kv_bytes
        nbytes += 2 * n * h * hd * 2
    return flops, nbytes


def dropout_matmul_call(rows: int, k: int, n: int, block_n: int,
                        kept_blocks: Iterable[int]) -> Tuple[float, float]:
    """(operations, bytes) of one ``dropout_matmul`` call [G, rows, k] x
    [k, n]: each group multiplies only its kept column blocks; x (bf16)
    and w (bf16) are read once and the f32 output written once."""
    kept = list(kept_blocks)
    g = len(kept)
    flops = sum(2.0 * rows * k * kb * block_n for kb in kept)
    nbytes = 2.0 * g * rows * k + 2.0 * k * n + 4.0 * g * rows * n
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_s"])
