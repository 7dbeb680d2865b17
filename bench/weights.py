"""Seeded random weights, drawn on the device.

Each tensor is a function of (seed, its name, its layer) alone, so the
program's whole parameter tree can be made in one jitted call, and the
reference can draw any single layer again, bit for bit, without holding
the rest. Values are drawn in float32 and rounded to the dtype the
configuration serves them in; the reference reads them back as float32.

Scales: projections 1/sqrt(fan-in), embeddings, biases and norm shifts
0.02, norm gains 1 + 0.02 N(0, 1). Biases and norm parameters are
random, not zero and one, so that the reference checks how they are
applied.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

SMALL = 0.02
# Leaves the program keeps in float32 whatever the served dtype.
FLOAT32_LEAVES = ("gamma", "beta")


def _std(name: str, shape) -> float:
    leaf = name.rsplit("/", 1)[-1]
    if leaf in ("wq", "wk", "wv", "w_up", "w_gate", "w_down"):
        return 1.0 / math.sqrt(shape[0])
    if leaf == "wo":
        return 1.0 / math.sqrt(shape[0] * shape[1])
    if leaf in ("embedding", "unembed", "bq", "bk", "bv", "beta", "gamma"):
        return SMALL
    raise KeyError(f"no initialiser for weight {name!r}")


def tensor(words, name: str, layer, shape, dtype):
    """One weight tensor (traceable; ``layer`` may be a tracer)."""
    key = jax.random.wrap_key_data(words)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, layer)
    v = jax.random.normal(key, shape, jnp.float32) * _std(name, shape)
    if name.endswith("gamma"):
        v = v + 1.0
    return v.astype(dtype)


def served_dtype(name: str, param_dtype: str):
    if name.rsplit("/", 1)[-1] in FLOAT32_LEAVES:
        return jnp.float32
    return jnp.dtype(param_dtype)


def program_params(words, abstract, stacked):
    """The program's parameter tree in one jitted call.

    ``abstract``: the program's tree of ``ShapeDtypeStruct``;
    ``stacked``: names of its top-level groups whose leaves stack layers
    on the first axis (layer ``i`` of leaf ``g/a/b`` is tensor ``a/b``).
    """
    def build(words):
        def walk(path, node):
            if isinstance(node, dict):
                return {k: walk(path + (k,), v) for k, v in node.items()}
            if path[0] in stacked:
                name = "/".join(path[1:])
                return jax.vmap(lambda i: tensor(
                    words, name, i, node.shape[1:], node.dtype))(
                    jnp.arange(node.shape[0]))
            return tensor(words, "/".join(path), 0, node.shape, node.dtype)
        return walk((), abstract)

    return jax.jit(build)(jnp.asarray(words))


def group(words, shapes, param_dtype: str, layer: int = 0):
    """float32 copies of the tensors ``shapes`` ({name: shape}) as
    served, for the reference."""
    return {name: tensor(words, name, layer, shape,
                         served_dtype(name, param_dtype)).astype(jnp.float32)
            for name, shape in shapes.items()}
