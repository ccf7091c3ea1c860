"""Mesh construction and logical-axis sharding rules.

The production mesh is ``(data=16, model=16)`` per pod (256 chips, TPU v5e) and
``(pod=2, data=16, model=16)`` for the 2-pod dry-run.  Model code never touches
mesh axes directly: it annotates tensors with *logical* axes ("batch", "heads",
"ffn", ...) through a :class:`ShardingCtx`, and the rules below map logical →
physical with divisibility-aware fallbacks (e.g. 8 kv-heads cannot shard over a
16-way model axis → replicate; 56 q-heads cannot → shard head_dim instead).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh (function, so importing never inits jax)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1) -> Mesh:
    """A (data, model) mesh over the first ``data * model`` devices; raises
    when fewer devices exist than the mesh asks for."""
    devices = jax.devices()
    if data * model > len(devices):
        raise ValueError(f"mesh (data={data}, model={model}) needs "
                         f"{data * model} devices, found {len(devices)}")
    devs = np.array(devices[: data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"))


def axis_sizes(mesh: Optional[Mesh]) -> dict:
    if mesh is None:
        return {}
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def sharding_rules(cfg: ModelConfig, mesh: Optional[Mesh],
                   shape=None) -> dict:
    """Logical-axis -> mesh-axis mapping for one architecture on one mesh.

    ``shape`` (a ShapeConfig) enables shape-aware fallbacks: a decode cell
    with global_batch=1 cannot shard batch over `data`, so the KV-cache
    sequence axis takes the data axis instead (sequence-parallel decode).
    """
    sizes = axis_sizes(mesh)
    model_n = sizes.get("model", 1)
    data_n = sizes.get("data", 1)
    has_pod = "pod" in sizes

    def on_model(dim: int):
        return "model" if model_n > 1 and dim > 0 and dim % model_n == 0 \
            else None

    def on_data(dim: int):
        return "data" if data_n > 1 and dim > 0 and dim % data_n == 0 \
            else None

    heads = on_model(cfg.num_heads)
    kv_heads = on_model(cfg.num_kv_heads)
    # GQA fallback: if q-heads don't shard, shard head_dim (llama4: 40H, llava: 56H)
    head_dim = on_model(cfg.head_dim) if heads is None else None
    if heads is None and head_dim is not None:
        kv_heads = None  # k/v share the head_dim sharding instead

    d_in = cfg.ssm_expand * cfg.d_model
    rules = {
        # activations
        "batch": ("pod", "data") if has_pod else ("data",),
        "seq": None,
        "seq_model": on_model(1 << 30),   # opt-in KV-sequence sharding (decode)
        "act_embed": None,
        "act_ffn": on_model(cfg.d_ff),
        "heads": heads,
        "kv_heads": kv_heads,
        "head_dim": head_dim,
        "kv_head_dim": head_dim if kv_heads is None else None,
        # weights (FSDP on the d_model dim over `data`; TP on the wide dim)
        "embed": on_data(cfg.d_model),
        "ffn": on_model(cfg.d_ff),
        "moe_ffn": on_model(cfg.moe_ff) if cfg.num_experts else None,
        "vocab": on_model(cfg.vocab_size),
        "experts": on_model(cfg.num_experts) if cfg.num_experts else None,
        "ssm_inner": on_model(d_in) if cfg.ssm_state else None,
        "ssm_heads": (on_model(d_in // cfg.ssm_head_dim)
                      if cfg.ssm_state else None),
        "ssm_state": None,
        "layers": None,
        "conv": None,
        "noshard": None,
    }
    # KV-cache sequence axis: prefer kv-head sharding; when kv heads do not
    # divide the model axis (qwen1.5: 20, llava/jamba: 8 on 16), shard the
    # cache's *sequence* dim over model instead (flash-decode style) — this
    # is what stops GSPMD from all-gathering the whole cache (EXPERIMENTS
    # §Perf, hillclimb 2).
    rules["kv_seq"] = None
    if shape is not None:
        if (shape.kind == "decode" and rules["kv_heads"] is None
                and shape.seq_len % max(model_n, 1) == 0):
            rules["kv_seq"] = on_model(shape.seq_len)
        # Serving shapes: weights are read-only — FSDP's per-layer
        # all-gathers buy nothing, so replicate over `data` (hillclimb 1/2).
        if shape.kind != "train":
            rules["embed"] = None
        # Prefill/train with unshardable heads: sequence-parallel attention
        # (shard q-sequence over model; no score psum needed) instead of
        # head_dim sharding, which made GSPMD gather/psum huge score tensors.
        rules["sp_seq"] = None
        if (shape.kind in ("prefill", "train") and rules["heads"] is None
                and shape.seq_len % max(model_n, 1) == 0):
            rules["sp_seq"] = on_model(shape.seq_len)
            rules["head_dim"] = None
            rules["kv_head_dim"] = None
            # Megatron-style sequence parallelism: keep the residual stream
            # seq-sharded everywhere (norms/elementwise local; K/V gathered
            # in bf16 — 40x smaller than the full-activation f32 gathers the
            # SP<->TP boundary otherwise produces each sublayer).  Measured
            # win on dense archs; REGRESSION on MoE (expert dispatch wants
            # token-replicated rows) — so gated to num_experts == 0.
            if cfg.num_experts == 0:
                rules["seq"] = rules["sp_seq"]
        dp = data_n * sizes.get("pod", 1)
        if dp > 1 and shape.global_batch % dp != 0:
            rules["batch"] = None
            # sequence-parallel fallback (long-context decode, batch 1)
            if shape.kind == "decode" and shape.seq_len % data_n == 0:
                rules["seq"] = "data"
    return rules


@dataclass
class ShardingCtx:
    """Applies logical-axis sharding constraints inside model code."""

    mesh: Optional[Mesh] = None
    rules: dict = field(default_factory=dict)

    def spec(self, *axes) -> P:
        entries, used = [], set()
        for a in axes:
            m = self.rules.get(a) if a is not None else None
            if m is None:
                entries.append(None)
                continue
            ms = m if isinstance(m, tuple) else (m,)
            ms = tuple(x for x in ms if x not in used)
            used.update(ms)
            entries.append(ms if len(ms) != 1 else ms[0])
            if not ms:
                entries[-1] = None
        return P(*entries)

    def sharding(self, *axes) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(*axes))

    def constrain(self, x, *axes):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.sharding(*axes))

    def shard_kernel(self, fn, in_axes, out_axes):
        """``fn`` (a Pallas kernel call, which GSPMD cannot partition) run
        on each device's shard under ``shard_map``; every operand and the
        result are laid out by their logical axes.  Identity off-mesh."""
        if self.mesh is None:
            return fn
        return jax.shard_map(fn, mesh=self.mesh,
                             in_specs=tuple(self.spec(*a) for a in in_axes),
                             out_specs=self.spec(*out_axes), check_vma=False)

    @property
    def dp_size(self) -> int:
        s = axis_sizes(self.mesh)
        return s.get("data", 1) * s.get("pod", 1)

    @property
    def model_size(self) -> int:
        return axis_sizes(self.mesh).get("model", 1)


def null_ctx() -> ShardingCtx:
    return ShardingCtx(mesh=None, rules={})


def is_axes_leaf(x) -> bool:
    """An axes annotation: a (possibly empty) tuple of axis names / None.
    (A (k, v) cache pair is a tuple of tuples — NOT a leaf.)"""
    return isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x)


def tree_shardings(axes_tree, ctx: ShardingCtx):
    """Map a pytree of logical-axes tuples to NamedShardings (or None off-mesh)."""
    if ctx.mesh is None:
        return jax.tree.map(lambda _: None, axes_tree, is_leaf=is_axes_leaf)
    return jax.tree.map(lambda ax: ctx.sharding(*ax), axes_tree,
                        is_leaf=is_axes_leaf)
