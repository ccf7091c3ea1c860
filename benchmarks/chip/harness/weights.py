"""Weights from the seed, made by the benchmark, in one jitted call.

The benchmark owns its weights: ``make(key, m)`` draws a plain Qwen3
parameter set (stacked over layers, float32, the published config's
``initializer_range`` for matrices, norm weights near 1) on the device.
The reference reads it as it is; ``to_program`` lays the same arrays out
as the program's parameter tree, so the program is handed the weights and
makes none of them.  ``check_layout`` refuses a program tree that differs
from that layout in any leaf or shape.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

f32 = jnp.float32

# where each benchmark leaf sits in the program's parameter tree, and
# whether the program stores a norm weight w as (w - 1); the order fixes
# each leaf's key
PROGRAM_PATH = {
    "embed": (("embed", "embedding"), False),
    "final_norm": (("final_norm", "scale"), True),
    "attn_norm": (("blocks", "l0", "pre_norm", "scale"), True),
    "wq": (("blocks", "l0", "attn", "wq"), False),
    "wk": (("blocks", "l0", "attn", "wk"), False),
    "wv": (("blocks", "l0", "attn", "wv"), False),
    "wo": (("blocks", "l0", "attn", "wo"), False),
    "q_norm": (("blocks", "l0", "attn", "q_norm", "scale"), True),
    "k_norm": (("blocks", "l0", "attn", "k_norm", "scale"), True),
    "ffn_norm": (("blocks", "l0", "ffn_norm", "scale"), True),
    "w_gate": (("blocks", "l0", "mlp", "wg"), False),
    "w_up": (("blocks", "l0", "mlp", "wi"), False),
    "w_down": (("blocks", "l0", "mlp", "wo"), False),
}
LEAVES = tuple(PROGRAM_PATH)
SALT = 0x57454947                 # the weights' key, apart from the run's


def key(jseed: int):
    """The weights' key for a run's 31-bit seed."""
    return jax.random.fold_in(jax.random.key(jseed), SALT)


def shapes(m: dict) -> Dict[str, Tuple[int, ...]]:
    L, d = m["num_hidden_layers"], m["hidden_size"]
    h, kh, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    ff, v = m["intermediate_size"], m["vocab_size"]
    return {"embed": (v, d), "final_norm": (d,), "attn_norm": (L, d),
            "wq": (L, d, h, hd), "wk": (L, d, kh, hd), "wv": (L, d, kh, hd),
            "wo": (L, h, hd, d), "q_norm": (L, hd), "k_norm": (L, hd),
            "ffn_norm": (L, d), "w_gate": (L, d, ff), "w_up": (L, d, ff),
            "w_down": (L, ff, d)}


def make(key, m: dict) -> Dict[str, jax.Array]:
    """The weights for ``key``; call under ``jax.jit``."""
    out = {}
    for i, (name, shape) in enumerate(
            (n, shapes(m)[n]) for n in LEAVES):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, f32)
        out[name] = 1.0 + 0.1 * z if name.endswith("norm") \
            else m["initializer_range"] * z
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, value in flat.items():
        path = PROGRAM_PATH[name][0]
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = value
    return tree


def to_program(w: Dict[str, jax.Array]) -> dict:
    """The same arrays as the program's nested parameter tree."""
    return _nest({n: w[n] - 1.0 if PROGRAM_PATH[n][1] else w[n]
                  for n in PROGRAM_PATH})


def program_leaf(tree: dict, name: str):
    node = tree
    for p in PROGRAM_PATH[name][0]:
        node = node[p]
    return node


def check_layout(program_shapes: dict, m: dict) -> None:
    """Raise unless the program's parameter tree (a tree of
    ShapeDtypeStructs) is exactly ``to_program``'s layout."""
    want = _nest({n: list(s) for n, s in shapes(m).items()})
    got = jax.tree.map(lambda s: list(s.shape), program_shapes)
    if want != got:
        raise ValueError(f"the program's parameter layout changed:\n"
                         f"expected {want}\ngot {got}")
