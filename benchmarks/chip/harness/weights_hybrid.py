"""The Granite hybrid's weights from the seed, made by the benchmark.

``make(key, m)`` draws a plain parameter set in one jitted call on the
device -- float32 draws rounded to bfloat16, the dtype the configuration
serves in, so the reference (which computes in float32) and the program
hold the very same values.  Leaves are stacked by kind in layer order:

  embed [V, d], final_norm [d]
  every layer (L):  input_norm, post_norm [L, d]; mlp_gate, mlp_up
                    [L, d, ff]; mlp_down [L, ff, d]
  Mamba layers (M): in_z, in_x [M, d, d_in]; in_B, in_C [M, d, N]; in_dt
                    [M, d, H]; conv_w [M, W, d_in + 2N]; conv_b
                    [M, d_in + 2N]; dt_bias, A_log, D [M, H]; gnorm
                    [M, d_in]; out [M, d_in, d]
  attention (A):    wq [A, d, h, hd]; wk, wv [A, d, kh, hd]; wo [A, h, hd, d]

with the published config's keys in ``m`` (``layer_types`` orders the
kinds).  Norm weights multiply (1 + 0.1 z, clipped to 4 sigma, so w - 1 is
exact in bf16); matrices are ``initializer_range`` z; the Mamba-2 scalars
follow its init ranges (A = -U(1, 16), dt in [0.001, 0.1] log-uniform
through softplus^-1 of dt_bias).  ``to_program`` lays the same arrays out
as the program's parameter tree (a scan over periods of the layer
pattern, norms stored as w - 1); ``check_layout`` refuses a program tree
that differs from that layout in any leaf or shape.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

f32, bf16 = jnp.float32, jnp.bfloat16
SALT = 0x47524E54                 # the weights' key, apart from the run's

MAMBA_KEYS = ("in_z", "in_x", "in_B", "in_C", "in_dt", "conv_w", "conv_b",
              "dt_bias", "A_log", "D", "gnorm", "out")
ATTN_KEYS = ("wq", "wk", "wv", "wo")
LAYER_KEYS = ("input_norm", "post_norm", "mlp_gate", "mlp_up", "mlp_down")
NORMS = ("final_norm", "input_norm", "post_norm", "gnorm")


def key(jseed: int):
    """The weights' key for a run's 31-bit seed."""
    return jax.random.fold_in(jax.random.key(jseed), SALT)


def dims(m: dict) -> dict:
    d, h = m["hidden_size"], m["num_attention_heads"]
    return {"d": d, "h": h, "kh": m["num_key_value_heads"], "hd": d // h,
            "ff": m["shared_intermediate_size"], "V": m["vocab_size"],
            "H": m["mamba_n_heads"], "P": m["mamba_d_head"],
            "N": m["mamba_d_state"], "W": m["mamba_d_conv"],
            "d_in": m["mamba_n_heads"] * m["mamba_d_head"]}


def kinds(m: dict) -> List[str]:
    return list(m["layer_types"])


def shapes(m: dict) -> Dict[str, Tuple[int, ...]]:
    k = dims(m)
    d, d_in, N, H = k["d"], k["d_in"], k["N"], k["H"]
    L = len(kinds(m))
    M = kinds(m).count("mamba")
    A = L - M
    return {"embed": (k["V"], d), "final_norm": (d,),
            "input_norm": (L, d), "post_norm": (L, d),
            "mlp_gate": (L, d, k["ff"]), "mlp_up": (L, d, k["ff"]),
            "mlp_down": (L, k["ff"], d),
            "in_z": (M, d, d_in), "in_x": (M, d, d_in), "in_B": (M, d, N),
            "in_C": (M, d, N), "in_dt": (M, d, H),
            "conv_w": (M, k["W"], d_in + 2 * N), "conv_b": (M, d_in + 2 * N),
            "dt_bias": (M, H), "A_log": (M, H), "D": (M, H),
            "gnorm": (M, d_in), "out": (M, d_in, d),
            "wq": (A, d, k["h"], k["hd"]), "wk": (A, d, k["kh"], k["hd"]),
            "wv": (A, d, k["kh"], k["hd"]), "wo": (A, k["h"], k["hd"], d)}


def make(key, m: dict) -> Dict[str, jax.Array]:
    """The weights for ``key`` (bf16); call under ``jax.jit``."""
    out = {}
    for i, (name, shape) in enumerate(shapes(m).items()):
        kk = jax.random.fold_in(key, i)
        z = jax.random.normal(kk, shape, f32)
        u = jax.random.uniform(kk, shape, f32)
        if name in NORMS:
            w = 1.0 + 0.1 * jnp.clip(z, -4.0, 4.0)
        elif name == "A_log":
            w = jnp.log(1.0 + 15.0 * u)
        elif name == "dt_bias":
            dt = jnp.exp(math.log(1e-3) + u * (math.log(0.1) - math.log(1e-3)))
            w = dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1
        elif name == "D":
            w = 1.0 + 0.1 * z
        elif name == "conv_w":
            w = z / math.sqrt(shape[1])
        elif name == "conv_b":
            w = 0.1 * z
        else:
            w = m["initializer_range"] * z
        out[name] = w.astype(bf16)
    return out


def _period(m: dict) -> List[str]:
    """The repeating period of ``layer_types``."""
    ks = kinds(m)
    for p in range(1, len(ks) + 1):
        if len(ks) % p == 0 and ks == ks[:p] * (len(ks) // p):
            return ks[:p]
    return ks


def _program_block(w: dict, m: dict, i: int, kind: str, R: int) -> dict:
    """Pattern position ``i``'s leaves, stacked over the R periods."""
    per = _period(m)
    p = len(per)
    j = per[:i].count(kind)               # index among the period's kind
    n = per.count(kind)

    def common(name):
        return w[name].reshape((R, p) + w[name].shape[1:])[:, i]

    def own(name):
        return w[name].reshape((R, n) + w[name].shape[1:])[:, j]

    blk = {"pre_norm": {"scale": common("input_norm") - 1},
           "ffn_norm": {"scale": common("post_norm") - 1},
           "mlp": {"wi": common("mlp_up"), "wo": common("mlp_down"),
                   "wg": common("mlp_gate")}}
    if kind == "attention":
        blk["attn"] = {k: own(k) for k in ATTN_KEYS}
        return blk
    d_in, N = dims(m)["d_in"], dims(m)["N"]
    conv_w, conv_b = own("conv_w"), own("conv_b")
    blk["mamba"] = {
        "wz": own("in_z"), "wx": own("in_x"), "wB": own("in_B"),
        "wC": own("in_C"), "wdt": own("in_dt"), "dt_bias": own("dt_bias"),
        "A_log": own("A_log"), "D": own("D"), "gnorm": own("gnorm") - 1,
        "wo": own("out"),
        "conv_x": conv_w[..., :d_in], "conv_B": conv_w[..., d_in:d_in + N],
        "conv_C": conv_w[..., d_in + N:],
        "conv_x_bias": conv_b[..., :d_in],
        "conv_B_bias": conv_b[..., d_in:d_in + N],
        "conv_C_bias": conv_b[..., d_in + N:]}
    return blk


def to_program(w: Dict[str, jax.Array], m: dict) -> dict:
    """The same arrays as the program's nested parameter tree."""
    per = _period(m)
    R = len(kinds(m)) // len(per)
    return {"embed": {"embedding": w["embed"]},
            "final_norm": {"scale": w["final_norm"] - 1},
            "blocks": {f"l{i}": _program_block(w, m, i, k, R)
                       for i, k in enumerate(per)}}


def check_layout(program_shapes: dict, m: dict) -> None:
    """Raise unless the program's parameter tree (a tree of
    ShapeDtypeStructs) is exactly ``to_program``'s layout."""
    fake = {n: jax.ShapeDtypeStruct(s, bf16) for n, s in shapes(m).items()}
    want = jax.tree.map(lambda s: list(s.shape),
                        jax.eval_shape(lambda w: to_program(w, m), fake))
    got = jax.tree.map(lambda s: list(s.shape), program_shapes)
    if want != got:
        raise ValueError(f"the program's parameter layout changed:\n"
                         f"expected {want}\ngot {got}")
