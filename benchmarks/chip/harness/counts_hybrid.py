"""Operations and bytes the Granite hybrid's serving work needs, as
functions of its shapes (the published config's keys in ``m``).

As in ``counts.py`` these are the algorithm's counts: padded positions
and idle slots are not counted, so a share of peak built on them falls
when the program wastes work.  ``chunks`` holds (start, length) of each
live slot's chunk in a tick.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from harness import weights_hybrid as WH

ACT_BYTES = 2                     # bf16 activations (the compute dtype)


def _layers(m: dict) -> Tuple[int, int]:
    """(Mamba layers, attention layers)."""
    ks = WH.kinds(m)
    return ks.count("mamba"), len(ks) - ks.count("mamba")


def attn_shape(m: dict) -> dict:
    """The attention layers' shape under the keys ``counts.py`` reads
    (the published config has no ``head_dim``: it is hidden / heads)."""
    k = WH.dims(m)
    return {"hidden_size": k["d"], "num_attention_heads": k["h"],
            "num_key_value_heads": k["kh"], "head_dim": k["hd"],
            "intermediate_size": k["ff"], "vocab_size": k["V"]}


def ssd_call(m: dict, chunks: Iterable[Tuple[int, int]]
             ) -> Tuple[float, float]:
    """(operations, bytes) of one Mamba layer's SSD scan over a tick: per
    live slot of n tokens, the chunked dual form's causal [n, n] C B^T
    (shared by the heads) and per head its weighted [n, n] x [n, P]
    product, the carried state's C state^T and the state update (each
    2 n P N); the slot's f32 state read and written once, its x, B, C, dt
    read and its y written once."""
    k = WH.dims(m)
    H, P, N = k["H"], k["P"], k["N"]
    flops = nbytes = 0.0
    for _, n in chunks:
        if n <= 0:
            continue
        pairs = n * (n + 1) / 2
        flops += 2 * N * pairs + H * (2 * P * pairs + 4 * n * P * N)
        nbytes += 2 * H * P * N * 4
        nbytes += n * (2 * H * P * ACT_BYTES + 2 * N * ACT_BYTES + H * 4)
    return flops, nbytes


def slot_state_bytes(m: dict, conv_bytes: int = ACT_BYTES) -> int:
    """One slot's state in one Mamba layer: the f32 SSM state [H, P, N]
    and the conv tail [W - 1, d_in + 2N]."""
    k = WH.dims(m)
    return k["H"] * k["P"] * k["N"] * 4 \
        + (k["W"] - 1) * (k["d_in"] + 2 * k["N"]) * conv_bytes


def tick_state_bytes(m: dict, live_slots: int,
                     conv_bytes: int = ACT_BYTES) -> int:
    """The per-slot state a tick's live slots read and write, over every
    Mamba layer (both ways)."""
    return live_slots * _layers(m)[0] * slot_state_bytes(m, conv_bytes) * 2


def token_flops(m: dict) -> float:
    """One token's projections through every layer (forward): the Mamba
    in/out projections and conv, the attention projections, the SwiGLU
    MLP of every layer."""
    k = WH.dims(m)
    d, d_in, N, H = k["d"], k["d_in"], k["N"], k["H"]
    n_m, n_a = _layers(m)
    mamba = 2 * d * (2 * d_in + 2 * N + H) + 2 * k["W"] * (d_in + 2 * N) \
        + 2 * d_in * d
    attn = 2 * d * (k["h"] + 2 * k["kh"]) * k["hd"] + 2 * k["h"] * k["hd"] * d
    mlp = 3 * 2 * d * k["ff"]
    return n_m * mamba + n_a * attn + (n_m + n_a) * mlp


def serve_tick_flops(m: dict, chunks: Iterable[Tuple[int, int]]) -> float:
    """Required forward operations of one serving tick: every real token
    runs the projections, the SSD scans (``ssd_call``) and attends over
    its causal prefix in the attention layers; each slot's last token
    runs the head."""
    k = WH.dims(m)
    chunks = [(s, n) for s, n in chunks if n > 0]
    n_m, n_a = _layers(m)
    total = n_m * ssd_call(m, chunks)[0]
    for start, n in chunks:
        keys = n * start + n * (n + 1) / 2          # sum of (pos + 1)
        total += n * token_flops(m) + n_a * 4 * k["h"] * k["hd"] * keys
        total += 2 * k["d"] * k["V"]
    return total
