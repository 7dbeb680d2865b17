"""Mixture-of-Experts block: top-k routing with capacity + scatter dispatch.

TPU adaptation: instead of the GShard one-hot dispatch einsum — whose
``(tokens, experts, capacity)`` one-hot tensor is prohibitively large at
DeepSeek/Kimi expert counts — we compute per-token expert slots with a
cumsum and dispatch with scatter-add into per-expert buffers that are
sharded over the ``model`` mesh axis (expert parallelism). The gather back
uses plain ``take``. Over-capacity tokens are dropped (their combine
weight contribution is zero), matching the capacity-factor semantics of
GShard/Switch.

Shared experts (DeepSeek/Kimi style) are a dense FFN applied to every
token, fused into one wide FFN of width ``num_shared * d_ff_expert``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models.common import (
    ParamSpec,
    apply_ffn,
    constrain,
    ffn_params,
    maybe_model,
)


def moe_params(cfg: ModelConfig, model_axis: int, data_axis: int = 0) -> Dict:
    """Expert-parallel sharding: the expert dim shards over the DATA mesh
    axis and the per-expert hidden dim over the MODEL axis, so expert
    weights shard over the full 2-D mesh (kimi-k2's 2 TB of experts ->
    ~8 GB/chip on 16x16; with experts only on the model axis they were
    125 GB/chip — found by the dry-run)."""
    m = cfg.moe
    E, dff = m.num_experts, m.d_ff_expert
    me = "data" if data_axis and E % data_axis == 0 and E >= data_axis else None
    mf = maybe_model(dff, model_axis)
    p = {
        "router": ParamSpec((cfg.d_model, E), P(None, None), "small", dtype="float32"),
        "w_gate": ParamSpec((E, cfg.d_model, dff), P(me, None, mf)),
        "w_up": ParamSpec((E, cfg.d_model, dff), P(me, None, mf)),
        "w_down": ParamSpec((E, dff, cfg.d_model), P(me, mf, None)),
    }
    if m.num_shared_experts > 0:
        shared_ff = m.num_shared_experts * dff
        p["shared"] = ffn_params(cfg, cfg.d_model, shared_ff, model_axis)
    return p


def capacity(m, tokens: int) -> int:
    cap = int(math.ceil(tokens * m.top_k / m.num_experts * m.capacity_factor))
    return max(8, -(-cap // 8) * 8)  # round up to 8 lanes


def route(m, router_w, x_flat) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (topk_weights (T,k) f32, topk_ids (T,k) i32, aux_loss scalar)."""
    logits = (x_flat.astype(jnp.float32) @ router_w).astype(jnp.float32)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_ids = jax.lax.top_k(probs, m.top_k)
    topk_w = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing auxiliary loss
    E = logits.shape[-1]
    me = probs.mean(axis=0)                                        # mean prob per expert
    ce = jnp.zeros((E,), jnp.float32).at[topk_ids.reshape(-1)].add(1.0)
    ce = ce / jnp.maximum(ce.sum(), 1.0)
    aux = E * jnp.sum(me * ce) * m.router_aux_loss_weight
    return topk_w, topk_ids.astype(jnp.int32), aux


def moe_ffn(cfg: ModelConfig, p: Dict, x: jax.Array,
            mesh=None) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,d) -> (B,S,d), aux_loss.

    Two dispatch paths:
      * ``shard_map`` expert-parallel (production): local slot assignment
        per data shard, one all-to-all to the expert owners, expert FFN,
        psum over the model axis, reverse all-to-all. Chosen when a mesh
        is provided and the batch/expert dims divide it. (The GSPMD
        scatter path all-gathered the full (T*topk, D) dispatch tensor —
        14.4 TB/device/step on kimi-k2 prefill; found by the dry-run.)
      * dense scatter (CPU smoke tests / decode's tiny T): below.
    """
    m = cfg.moe
    B, S, D = x.shape
    if mesh is not None:
        da = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        dp = 1
        for a in da:
            dp *= mesh.shape[a]
        ep = mesh.shape.get("data", 1)       # experts shard over 'data'
        if (dp > 1 and B % dp == 0 and m.num_experts % ep == 0
                and "model" in mesh.axis_names):
            return _moe_ffn_expert_parallel(cfg, p, x, mesh, da)
    return _moe_ffn_dense(cfg, p, x)


def _moe_ffn_dense(cfg: ModelConfig, p: Dict, x: jax.Array):
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    E = m.num_experts
    cap = capacity(m, T)
    xf = x.reshape(T, D)

    topk_w, topk_ids, aux = route(m, p["router"], xf)

    # slot assignment: position of each (token, k) within its expert queue
    flat_ids = topk_ids.reshape(-1)                                # (T*k,)
    oh = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)              # (T*k, E)
    pos_in_expert = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1      # (T*k,)
    in_cap = pos_in_expert < cap
    slot = jnp.where(in_cap, flat_ids * cap + pos_in_expert, E * cap)

    # dispatch: scatter tokens into (E*cap, D) buffers (row E*cap = drop bin)
    src = jnp.repeat(xf, m.top_k, axis=0)                          # (T*k, D)
    buf = jnp.zeros((E * cap + 1, D), x.dtype).at[slot].set(src, mode="drop")
    buf = buf[: E * cap].reshape(E, cap, D)
    # expert-parallel layout: experts over the data axis (matches the
    # expert-weight sharding; the dispatch scatter becomes an all-to-all)
    buf = constrain(buf, P("data", None, None))

    # expert FFN (einsum over expert-sharded weights)
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out = jnp.einsum("ecf,efd->ecd", gate * up, p["w_down"])       # (E,cap,D)
    out = constrain(out, P("data", None, None))

    # combine: gather each (token, k) result and weight it
    outf = out.reshape(E * cap, D)
    gathered = jnp.take(outf, jnp.minimum(slot, E * cap - 1), axis=0)
    w = (topk_w.reshape(-1) * in_cap.astype(jnp.float32)).astype(x.dtype)
    y = (gathered * w[:, None]).reshape(T, m.top_k, D).sum(axis=1)

    if m.num_shared_experts > 0:
        y = y + apply_ffn(cfg, p["shared"], xf)
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map)
# ---------------------------------------------------------------------------


def _sorted_slots(flat_ids: jax.Array, E: int, cap: int):
    """Sort-based slot assignment: position of each (token, k) within its
    expert's queue, O(Tk log Tk) memory O(Tk) — replaces the (Tk, E)
    one-hot cumsum (which is 800 MB/device at kimi-k2 prefill scale)."""
    Tk = flat_ids.shape[0]
    order = jnp.argsort(flat_ids)
    sorted_ids = flat_ids[order]
    run_start = jnp.searchsorted(sorted_ids, jnp.arange(E)).astype(jnp.int32)
    pos_sorted = jnp.arange(Tk, dtype=jnp.int32) - run_start[sorted_ids]
    pos = jnp.zeros((Tk,), jnp.int32).at[order].set(pos_sorted)
    in_cap = pos < cap
    slot = jnp.where(in_cap, flat_ids * cap + pos, E * cap)
    return slot, in_cap


def _moe_ffn_expert_parallel(cfg: ModelConfig, p: Dict, x: jax.Array,
                             mesh, data_axes):
    """shard_map expert parallelism.

    Layout: tokens shard over the data axes; experts shard over 'data'
    (replicated across 'pod': each pod serves its own tokens); the
    per-expert hidden dim shards over 'model'.

    Per layer collectives (the roofline's collective term):
      all-to-all (tokens -> expert owners)     T_l * topk * D bytes
      psum over model (down-proj partial sums) E_l * cap' * D bytes
      all-to-all (results -> token owners)     T_l * topk * D bytes
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, S, D = x.shape
    E = m.num_experts
    ep = mesh.shape["data"]
    batch_entry = data_axes if len(data_axes) > 1 else data_axes[0]

    def local_fn(xl, router_w, wg, wu, wd):
        Bl, Sl, _ = xl.shape
        T_l = Bl * Sl
        xf = xl.reshape(T_l, D)
        cap_l = capacity(m, T_l)
        topk_w, topk_ids, aux = route(m, router_w, xf)
        flat_ids = topk_ids.reshape(-1)
        slot, in_cap = _sorted_slots(flat_ids, E, cap_l)
        src = jnp.repeat(xf, m.top_k, axis=0)
        buf = jnp.zeros((E * cap_l + 1, D), xf.dtype).at[slot].set(
            src, mode="drop")
        buf = buf[: E * cap_l].reshape(E, cap_l, D)
        # exchange: every data shard sends each expert-owner its slice
        buf = jax.lax.all_to_all(buf, "data", split_axis=0, concat_axis=1,
                                 tiled=True)        # (E/ep, ep*cap_l, D)
        gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        up = jnp.einsum("ecd,edf->ecf", buf, wu)
        out = jnp.einsum("ecf,efd->ecd", gate * up, wd)
        out = jax.lax.psum(out, "model")            # dff partial sums
        out = jax.lax.all_to_all(out, "data", split_axis=1, concat_axis=0,
                                 tiled=True)        # (E, cap_l, D)
        outf = out.reshape(E * cap_l, D)
        gathered = jnp.take(outf, jnp.minimum(slot, E * cap_l - 1), axis=0)
        w = (topk_w.reshape(-1) * in_cap.astype(jnp.float32)).astype(
            xf.dtype)
        y = (gathered * w[:, None]).reshape(T_l, m.top_k, D).sum(axis=1)
        aux = jax.lax.pmean(aux, data_axes)
        return y.reshape(Bl, Sl, D), aux

    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(batch_entry, None, None),             # x
            P(None, None),                          # router
            P("data", None, "model"),               # w_gate
            P("data", None, "model"),               # w_up
            P("data", "model", None),               # w_down
        ),
        out_specs=(P(batch_entry, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if m.num_shared_experts > 0:
        y = y + apply_ffn(cfg, p["shared"], x.reshape(B * S, D)).reshape(
            B, S, D)
    return y, aux
