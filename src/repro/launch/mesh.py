"""Production meshes and sharding helpers.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model); the batch
shards over (pod, data) jointly and parameters/caches over model.

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (the dry-run driver sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; tests and benches see the single real CPU device).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with GSPMD-propagated (``Auto``) axes. Its default
    is ``Explicit`` axes, under which gathers and reshapes of sharded
    operands demand an output sharding that this code leaves to the
    partitioner."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Tiny mesh over however many real devices exist (CPU testing)."""
    n = len(jax.devices())
    assert data * model <= n, f"need {data * model} devices, have {n}"
    return _auto_mesh((data, model), ("data", "model"))


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes the global batch shards over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh: Mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    size = 1
    for n in names:
        size *= mesh.shape[n] if n in mesh.axis_names else 1
    return size


def batch_spec(mesh: Mesh, global_batch: int, ndim: int, *,
               seq_dim: Optional[int] = None, seq_len: int = 0) -> P:
    """Shard dim 0 (batch) over the data axes when divisible; otherwise
    fall back to sharding the sequence dim (long-context, batch==1)."""
    da = data_axes(mesh)
    ds = axis_size(mesh, da)
    spec = [None] * ndim
    if global_batch % ds == 0 and global_batch >= ds:
        spec[0] = da if len(da) > 1 else da[0]
    elif seq_dim is not None and seq_len % ds == 0 and seq_len >= ds:
        spec[seq_dim] = da if len(da) > 1 else da[0]
    return P(*spec)


def model_axis_size(mesh: Mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def cache_partition_specs(cache_abstract, mesh: Mesh) -> object:
    """Heuristic KV-cache/state sharding.

    Leaf layouts (leading ``count`` = layers-in-segment stack dim):
      kv:      (count, B, L, Hkv, hd)        mla: (count, B, L, r)
      rwkv s:  (count, B, H, hd, hd)         mamba h: (count, B, H, ds, hd)
    Policy: shard batch over data axes when divisible, else the length
    dim (dim 2); shard the first remaining head-ish dim that divides the
    model axis over ``model``.
    """
    da = data_axes(mesh)
    ds = axis_size(mesh, da)
    ms = model_axis_size(mesh)
    da_entry = da if len(da) > 1 else (da[0] if da else None)

    def leaf(a) -> P:
        shape = a.shape
        nd = len(shape)
        spec = [None] * nd
        used = set()
        if nd >= 2 and shape[1] % ds == 0 and shape[1] >= ds and ds > 1:
            spec[1] = da_entry
            used.add(1)
        elif nd >= 3 and shape[2] % ds == 0 and shape[2] >= ds and ds > 1:
            spec[2] = da_entry
            used.add(2)
        if ms > 1:
            # prefer head-ish dims (3+) over the length dim (2): sharding
            # cache length over `model` would force per-step resharding
            for i in list(range(3, nd)) + [2]:
                if i in used or i >= nd:
                    continue
                if shape[i] % ms == 0 and shape[i] >= ms:
                    spec[i] = "model"
                    break
        return P(*spec)

    return jax.tree.map(leaf, cache_abstract)
