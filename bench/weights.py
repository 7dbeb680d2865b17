"""Seeded random weights, drawn on the device.

Each tensor is a function of (seed, its name, its layer) alone -- and,
in a stack of routed experts, of the expert -- so the program's whole
parameter tree can be made in one jitted call, and the reference can draw
any single layer again, bit for bit, without holding the rest. Values are
drawn in float32 and rounded to the dtype the configuration serves them
in; the reference reads them back as float32.

Keys:
- a layer's tensors are keyed by the layer's global index in the model:
  the offset of its stacked group (the layers of the groups before it)
  plus its index within the group. Layer ``i`` of the program and of the
  reference is the same draw whatever segment holds it.
- a stack of routed experts (``moe/w_gate``, ``moe/w_up``, ``moe/w_down``;
  shape (experts, in, out)) is drawn expert by expert, keyed by the
  expert's global index, so a share that holds experts ``[o, o + n)``
  holds exactly those experts of the uncut model.

Scales: projections 1/sqrt(fan-in) (the first axis; attention and
time-mix outputs (heads, head_dim, d) the first two), embeddings, biases
and norm shifts 0.02, norm gains 1 + 0.02 N(0, 1). Biases and norm
parameters are random, not zero and one, so that the reference checks how
they are applied. Recurrent leaves keep the ranges of the program's own
initialiser (``repro/models/ssm.py``) that keep the recurrence stable:
- RWKV6 ``w0`` and Mamba2 ``a_log`` uniform in [-6, -1], the span of the
  program's ``decay`` init, so decays exp(-exp(.)) lie in [0.69, 0.998];
- RWKV6 token-shift mixes, their LoRA, the decay LoRA and the bonus ``u``
  at the program's 0.02, so the data-dependent decay stays near ``w0``;
- Mamba2 ``dt_bias`` and ``conv_b`` as biases (0.02), ``d_skip`` and the
  gated norm's ``norm_g`` (program: ones) and RWKV6 ``ln_out`` as gains.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

SMALL = 0.02
# Leaves the program keeps in float32 whatever the served dtype.
FLOAT32_LEAVES = ("gamma", "beta", "router", "w0", "u", "ln_out", "a_log",
                  "dt_bias", "d_skip", "norm_g")
FAN_IN = ("wq", "wk", "wv", "w_up", "w_gate", "w_down",
          "wq_a", "wq_b", "wkv_a", "wkv_b_k", "wkv_b_v", "router",
          "wr", "wg", "in_z", "in_x", "in_B", "in_C", "in_dt", "out",
          "conv_w", "frontend_proj")
SMALL_LEAVES = ("embedding", "unembed", "bq", "bk", "bv", "beta", "conv_b",
                "dt_bias", "mu_x", "mu_rkvwg", "tm_w1", "tm_w2", "decay_w1",
                "decay_w2", "u")
GAINS = ("gamma", "d_skip", "norm_g", "ln_out")
UNIFORM = {"w0": (-6.0, -1.0), "a_log": (-6.0, -1.0)}
EXPERT_STACKS = ("moe/w_gate", "moe/w_up", "moe/w_down")


def _std(name: str, shape) -> float:
    leaf = name.rsplit("/", 1)[-1]
    if leaf in FAN_IN:
        return 1.0 / math.sqrt(shape[0])
    if leaf == "wo":
        return 1.0 / math.sqrt(shape[0] * shape[1])
    if leaf in SMALL_LEAVES or leaf in GAINS:
        return SMALL
    raise KeyError(f"no initialiser for weight {name!r}")


def tensor(words, name: str, layer, shape, dtype, expert=None):
    """One weight tensor (traceable; ``layer`` and ``expert`` may be
    tracers)."""
    key = jax.random.wrap_key_data(words)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, layer)
    if expert is not None:
        key = jax.random.fold_in(key, expert)
    leaf = name.rsplit("/", 1)[-1]
    if leaf in UNIFORM:
        lo, hi = UNIFORM[leaf]
        return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(
            dtype)
    v = jax.random.normal(key, shape, jnp.float32) * _std(name, shape)
    if leaf in GAINS:
        v = v + 1.0
    return v.astype(dtype)


def is_expert_stack(name: str) -> bool:
    return "/".join(name.split("/")[-2:]) in EXPERT_STACKS


def draw(words, name: str, layer, shape, dtype, expert_offset: int = 0):
    """``tensor``, or for a stack of routed experts, one tensor per expert
    at global indices ``expert_offset + e``."""
    if not is_expert_stack(name):
        return tensor(words, name, layer, shape, dtype)
    return jax.vmap(lambda e: tensor(words, name, layer, shape[1:], dtype, e))(
        expert_offset + jnp.arange(shape[0]))


def expert_offset(cfg: dict) -> int:
    """Global index of the first routed expert a configuration file's
    share holds (``moe.expert_offset``; 0 when it holds them from the
    first)."""
    return cfg.get("moe", {}).get("expert_offset", 0)


def served_dtype(name: str, param_dtype: str):
    if name.rsplit("/", 1)[-1] in FLOAT32_LEAVES:
        return jnp.float32
    return jnp.dtype(param_dtype)


def _first_leaf(node):
    while isinstance(node, dict):
        node = next(iter(node.values()))
    return node


def program_params(words, abstract, stacked, expert_offset: int = 0):
    """The program's parameter tree in one jitted call.

    ``abstract``: the program's tree of ``ShapeDtypeStruct``;
    ``stacked``: paths (``"moe"``, ``"encoder/blocks"``) of its groups
    whose leaves stack layers on the first axis, in the model's layer
    order: layer ``i`` of group ``g`` is drawn at global index
    ``offset(g) + i``, leaf ``g/a/b`` as tensor ``a/b``;
    ``expert_offset``: the global index of the first routed expert held.
    """
    offsets, n = {}, 0
    for g in stacked:
        node = abstract
        for k in g.split("/"):
            node = node[k]
        offsets[g] = n
        n += _first_leaf(node).shape[0]

    def build(words):
        def walk(path, node, group):
            here = "/".join(path)
            if here in offsets:
                group = (len(path), offsets[here])
            if isinstance(node, dict):
                return {k: walk(path + (k,), v, group)
                        for k, v in node.items()}
            if group is None:
                return draw(words, here, 0, node.shape, node.dtype,
                            expert_offset)
            depth, offset = group
            name = "/".join(path[depth:])
            return jax.vmap(lambda i: draw(
                words, name, offset + i, node.shape[1:], node.dtype,
                expert_offset))(jnp.arange(node.shape[0]))
        return walk((), abstract, None)

    return jax.jit(build)(jnp.asarray(words))


def group(words, shapes, param_dtype: str, layer: int = 0,
          expert_offset: int = 0):
    """float32 copies of the tensors ``shapes`` ({name: shape}) as
    served, for the reference."""
    return {name: draw(words, name, layer, shape,
                       served_dtype(name, param_dtype),
                       expert_offset).astype(jnp.float32)
            for name, shape in shapes.items()}
