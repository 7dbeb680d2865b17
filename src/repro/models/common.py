"""Common model machinery: parameter descriptors, init, norms, rope, FFN.

Parameters are plain pytrees (nested dicts) of jnp arrays. Each module
defines its parameters once as a tree of :class:`ParamSpec` descriptors —
a single source of truth for shape, sharding (PartitionSpec) and
initializer — from which we derive (a) materialized params, (b) the
NamedSharding tree for pjit, and (c) ShapeDtypeStructs for dry-runs.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig

# ---------------------------------------------------------------------------
# Parameter descriptors
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    spec: Any = None                     # PartitionSpec or None (replicated)
    init: str = "normal"                 # normal | zeros | ones | small | decay
    scale: float = 1.0
    dtype: Optional[str] = None          # override param dtype


def _init_array(ps: ParamSpec, key: jax.Array, default_dtype: str) -> jax.Array:
    dtype = ps.dtype or default_dtype
    if ps.init == "zeros":
        return jnp.zeros(ps.shape, dtype)
    if ps.init == "ones":
        return jnp.ones(ps.shape, dtype)
    if ps.init == "decay":
        # rwkv-style decay init: spread in [-6, -1] pre-softplus
        n = math.prod(ps.shape)
        vals = jnp.linspace(-6.0, -1.0, n).reshape(ps.shape)
        return vals.astype(dtype)
    fan_in = ps.shape[-2] if len(ps.shape) >= 2 else ps.shape[-1]
    std = ps.scale / math.sqrt(max(fan_in, 1))
    if ps.init == "small":
        std = 0.02 * ps.scale
    return (jax.random.normal(key, ps.shape, jnp.float32) * std).astype(dtype)


def is_param_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_paths(tree) -> Dict[str, ParamSpec]:
    flat = {}

    def walk(prefix, node):
        if is_param_spec(node):
            flat[prefix] = node
        elif isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}/{k}" if prefix else k, v)
        else:
            raise TypeError(f"bad node at {prefix}: {type(node)}")

    walk("", tree)
    return flat


def materialize(tree, key: jax.Array, param_dtype: str):
    """Materialize a ParamSpec tree into arrays, deterministic per path."""
    flat = tree_paths(tree)
    names = sorted(flat)
    keys = jax.random.split(key, len(names))
    arrays = {
        name: _init_array(flat[name], k, param_dtype)
        for name, k in zip(names, keys)
    }

    def rebuild(prefix, node):
        if is_param_spec(node):
            return arrays[prefix]
        return {
            k: rebuild(f"{prefix}/{k}" if prefix else k, v) for k, v in node.items()
        }

    return rebuild("", tree)


def specs_tree(tree):
    """ParamSpec tree -> PartitionSpec tree (replicated leaves become P())."""
    return jax.tree.map(
        lambda ps: ps.spec if ps.spec is not None else P(),
        tree,
        is_leaf=is_param_spec,
    )


def abstract_tree(tree, param_dtype: str):
    """ParamSpec tree -> ShapeDtypeStruct tree (for dry-run lowering)."""
    return jax.tree.map(
        lambda ps: jax.ShapeDtypeStruct(ps.shape, jnp.dtype(ps.dtype or param_dtype)),
        tree,
        is_leaf=is_param_spec,
    )


def stack_specs(tree, n: int):
    """Prepend a stacking dim of size n (for scan-over-layers params)."""

    def bump(ps: ParamSpec) -> ParamSpec:
        spec = ps.spec
        if spec is None:
            spec = P()
        new_spec = P(None, *tuple(spec))
        return dataclasses.replace(ps, shape=(n, *ps.shape), spec=new_spec)

    return jax.tree.map(bump, tree, is_leaf=is_param_spec)


def shard_if_divisible(n: int, axis: str, mesh_axis_size: int) -> Optional[str]:
    """Return the mesh axis name if ``n`` divides evenly over it."""
    return axis if n % mesh_axis_size == 0 and n >= mesh_axis_size else None


# Mesh axis size used for *spec construction*. Specs name logical axes;
# whether a dim is actually shardable is resolved when we know the mesh.
MODEL_AXIS = "model"


def maybe_model(n: int, model_axis_size: int) -> Optional[str]:
    return MODEL_AXIS if model_axis_size > 0 and n % model_axis_size == 0 else None


def constrain(x: jax.Array, spec) -> jax.Array:
    """Best-effort ``with_sharding_constraint``: a no-op when no mesh is
    active (CPU smoke tests) so model code can annotate layouts freely."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:            # no mesh / axis not present
        return x


# ---------------------------------------------------------------------------
# Numerics / layers (pure functions over param dicts)
# ---------------------------------------------------------------------------


def cast(x, dtype_str: str):
    return x.astype(jnp.dtype(dtype_str))


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
    return out.astype(dt)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return out.astype(dt)


def norm_params(cfg: ModelConfig, d: int) -> Dict[str, ParamSpec]:
    if cfg.norm == "layernorm":
        return {
            "gamma": ParamSpec((d,), P(), "ones", dtype="float32"),
            "beta": ParamSpec((d,), P(), "zeros", dtype="float32"),
        }
    return {"gamma": ParamSpec((d,), P(), "ones", dtype="float32")}


def apply_norm(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layer_norm(x, p["gamma"], p["beta"])
    return rms_norm(x, p["gamma"])


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)                     # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def ffn_params(cfg: ModelConfig, d_model: int, d_ff: int, model_axis: int):
    mf = maybe_model(d_ff, model_axis)
    p = {
        "w_up": ParamSpec((d_model, d_ff), P(None, mf)),
        "w_down": ParamSpec((d_ff, d_model), P(mf, None)),
    }
    if cfg.activation == "swiglu":
        p["w_gate"] = ParamSpec((d_model, d_ff), P(None, mf))
    return p


def apply_ffn(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    # "up" and "gate": outputs a checkpoint policy may keep for the
    # backward pass (``Model.saving``); no-ops outside one
    up = checkpoint_name(x @ p["w_up"], "up")
    if cfg.activation == "swiglu":
        act = jax.nn.silu(checkpoint_name(x @ p["w_gate"], "gate")) * up
    else:
        act = jax.nn.gelu(up)
    return act @ p["w_down"]


def embed_params(cfg: ModelConfig, model_axis: int):
    mv = maybe_model(cfg.vocab_size, model_axis)
    p = {"embedding": ParamSpec((cfg.vocab_size, cfg.d_model), P(mv, None), "small")}
    if not cfg.tie_embeddings:
        p["unembed"] = ParamSpec((cfg.d_model, cfg.vocab_size), P(None, mv), "small")
    return p


def embed_tokens(p, tokens: jax.Array, dtype: str) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0).astype(jnp.dtype(dtype))


def unembed(cfg: ModelConfig, p, h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", h, p["embedding"],
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.matmul(h, p["unembed"],
                            preferred_element_type=jnp.float32)
    if cfg.logit_soft_cap > 0:
        c = cfg.logit_soft_cap
        logits = c * jnp.tanh(logits / c)
    return logits
