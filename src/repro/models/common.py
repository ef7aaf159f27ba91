"""Parameter-spec system + shared layer primitives.

Every model in ``repro.models`` is *functional*: it exposes

  ``param_specs(cfg) -> PyTree[ParamSpec]``
  ``apply(cfg, params, inputs, ...) -> outputs``

A ``ParamSpec`` carries shape, dtype, init distribution and *logical axis
names*.  Logical axes are mapped to mesh axes by sharding rules
(:func:`logical_to_mesh`), which is how one model definition serves:

  * smoke tests  (materialize small params on CPU),
  * the dry-run  (ShapeDtypeStructs, no allocation),
  * production   (NamedShardings for pjit / shard_map partial-auto).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

P = jax.sharding.PartitionSpec

# ---------------------------------------------------------------------------
# ParamSpec
# ---------------------------------------------------------------------------

INITS = ("normal", "scaled", "zeros", "ones", "embed", "small")


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]          # logical axis names, len == len(shape)
    init: str = "scaled"                     # fan-in scaled normal
    dtype: str = "float32"
    fan_in_axis: int = -2                    # which axis is fan-in for "scaled"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)
        assert self.init in INITS, self.init


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def spec_leaves(tree):
    return jax.tree.leaves(tree, is_leaf=is_spec)


def tree_map_specs(fn, tree, *rest):
    return jax.tree.map(fn, tree, *rest, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# Materialization / shape-struct / sharding derivation
# ---------------------------------------------------------------------------

def _init_one(spec: ParamSpec, key) -> jax.Array:
    dtype = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "embed":
        return (jax.random.normal(key, spec.shape) * 0.02).astype(dtype)
    if spec.init == "small":
        return (jax.random.normal(key, spec.shape) * 1e-2).astype(dtype)
    if spec.init == "normal":
        return jax.random.normal(key, spec.shape).astype(dtype)
    # fan-in scaled
    fan_axis = spec.fan_in_axis if len(spec.shape) > 1 else 0
    fan_in = spec.shape[fan_axis]
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, spec.shape) * scale).astype(dtype)


def init_params(spec_tree, key):
    """Materialize a ParamSpec tree into arrays (deterministic per-leaf keys)."""
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_one(s, k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def param_shape_structs(spec_tree, shardings=None):
    """ShapeDtypeStruct tree for the dry-run (no allocation)."""
    if shardings is None:
        return tree_map_specs(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)), spec_tree)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype), sharding=sh),
        spec_tree, shardings, is_leaf=is_spec)


# Default logical-axis -> mesh-axis rules (Megatron-style TP on "model").
# "embed" maps to the FSDP axis in fsdp mode (see rules_for).
DEFAULT_RULES: Tuple[Tuple[str, Optional[str]], ...] = (
    ("vocab", "model"),
    ("heads", "model"),        # fused head*head_dim projections
    ("kv_heads", "model"),
    ("mlp", "model"),
    ("expert", "model"),       # expert-parallel when divisible
    ("expert_mlp", None),      # per-arch override may set "model"
    ("lru", "model"),
    ("lru_in", None),          # input dim of square LRU gate matrices
    ("embed", None),
    ("embed2", None),          # second d_model-sized axis (e.g. wo output)
    ("layers", None),
    ("window", None),
    ("rank", None),            # low-rank/LoRA dims (MLA kv_lora, rwkv decay)
)


def rules_for(mesh_cfg, model_cfg=None) -> dict:
    """Resolve sharding rules for a (mesh, model) pair.

    fsdp mode shards the "embed" (d_model) weight dimension over the data axis
    — streaming ZeRO-3 via all_gather inside the layer scan.
    """
    rules = dict(DEFAULT_RULES)
    if mesh_cfg.dp_mode == "fsdp":
        # shard the d_model weight dim over EVERY dp axis (pod included)
        dp = tuple(a for a in mesh_cfg.axis_names if a in ("pod", "data"))
        rules["embed"] = dp if len(dp) > 1 else "data"
    for k, v in mesh_cfg.rules_override:
        rules[k] = v
    if model_cfg is not None and model_cfg.moe is not None:
        # expert-parallel only when the expert count divides the model axis
        msize = 1
        for s, a in zip(mesh_cfg.shape, mesh_cfg.axis_names):
            if a == "model":
                msize = s
        if model_cfg.moe.num_experts % max(msize, 1) != 0:
            rules["expert"] = None
            rules["expert_mlp"] = "model"
    return rules


def spec_to_pspec(spec: ParamSpec, rules: dict) -> P:
    """Logical axes -> PartitionSpec under the given rules."""
    return P(*(rules.get(a) if a is not None else None for a in spec.axes))


def logical_to_mesh(spec_tree, mesh, rules: dict):
    """ParamSpec tree -> NamedSharding tree."""
    def one(s: ParamSpec):
        return jax.sharding.NamedSharding(mesh, spec_to_pspec(s, rules))
    return tree_map_specs(one, spec_tree)


def manual_axis_specs(spec_tree, rules: dict, manual_axes: Tuple[str, ...]):
    """PartitionSpecs *restricted to manual axes* — what shard_map's in_specs
    needs for the params under partial-auto shard_map.  Auto-axis shardings
    flow through the jit-level NamedShardings instead."""
    def one(s: ParamSpec):
        out = []
        for a in s.axes:
            m = rules.get(a) if a is not None else None
            if isinstance(m, tuple):
                kept = tuple(x for x in m if x in manual_axes)
                out.append(kept if kept else None)
            else:
                out.append(m if m in manual_axes else None)
        return P(*out)
    return tree_map_specs(one, spec_tree)


def stack_specs(tree, n: int):
    """Prepend a scanned-layer dimension (logical axis "layers") to every leaf."""
    def one(s: ParamSpec):
        fan = s.fan_in_axis
        # keep fan-in pointing at the same physical axis after stacking
        if fan >= 0:
            fan += 1
        return ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.dtype, fan)
    return tree_map_specs(one, tree)


# ---------------------------------------------------------------------------
# Shared numerics
# ---------------------------------------------------------------------------

import contextlib

# When the whole mesh is auto (fsdp / serve paths), activation constraints
# must also pin the batch dim to the DP axes — otherwise P(None, ...) forces
# batch REPLICATION over data.  The trainer / launcher set this around
# tracing; inside manual shard_map regions it stays None (batch is manual).
_ACT_BATCH_AXES = None


@contextlib.contextmanager
def activation_batch_axes(axes):
    global _ACT_BATCH_AXES
    old = _ACT_BATCH_AXES
    _ACT_BATCH_AXES = tuple(axes) if axes else None
    try:
        yield
    finally:
        _ACT_BATCH_AXES = old


def maybe_wsc(x, spec: P):
    """with_sharding_constraint that no-ops outside a mesh context or when the
    referenced axes are absent/manual (smoke tests run on 1 CPU device)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    parts = list(spec)
    if (_ACT_BATCH_AXES and parts and parts[0] is None
            and x.ndim == len(parts) and x.shape[0] > 1):
        parts[0] = _ACT_BATCH_AXES
        spec = P(*parts)
    axis_types = dict(zip(mesh.axis_names, mesh.axis_types))
    for part in spec:
        for a in (part if isinstance(part, tuple) else (part,)):
            if a is not None and axis_types.get(a) != jax.sharding.AxisType.Auto:
                return x
    return jax.lax.with_sharding_constraint(x, spec)


def cast_tree(tree, dtype):
    d = jnp.dtype(dtype)
    return jax.tree.map(
        lambda x: x.astype(d) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_specs(cfg, shape_prefix=(), axes_prefix=()):
    """Norm params for one layer position (stacked under the layer scan)."""
    d = cfg.d_model
    if cfg.norm == "layernorm":
        return {
            "scale": ParamSpec(shape_prefix + (d,), axes_prefix + ("embed2",), init="ones"),
            "bias": ParamSpec(shape_prefix + (d,), axes_prefix + ("embed2",), init="zeros"),
        }
    return {"scale": ParamSpec(shape_prefix + (d,), axes_prefix + ("embed2",), init="zeros")}


@jax.named_scope("norm")
def apply_norm(cfg, p, x):
    if cfg.norm == "layernorm":
        return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rms_norm(x, p["scale"], cfg.norm_eps)


# --- RoPE ------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10_000.0,
               rotary_dim: Optional[int] = None):
    """x: [..., seq, heads, head_dim]; positions: [..., seq].  Rotates the
    first ``rotary_dim`` dims of each head (all of them by default) at the
    frequencies of a ``rotary_dim``-wide head; the rest pass through."""
    hd = x.shape[-1]
    if rotary_dim is not None and rotary_dim < hd:
        rot = apply_rope(x[..., :rotary_dim], positions, theta)
        return jnp.concatenate([rot, x[..., rotary_dim:]], axis=-1)
    freqs = jnp.asarray(rope_frequencies(hd, theta))           # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]                     # [..., seq, 1, hd/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def activation(name: str):
    return {
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "silu": jax.nn.silu,
        "swiglu": jax.nn.silu,   # gate activation used inside SwiGLU
        "geglu": jax.nn.gelu,
    }[name]


def dot(x, w, compute_dtype=None):
    """Linear apply with dtype management (bf16 compute, fp32 master)."""
    cd = compute_dtype or x.dtype
    return jax.lax.dot_general(
        x.astype(cd), w.astype(cd),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32 if cd == jnp.bfloat16 else None,
    ).astype(cd)
