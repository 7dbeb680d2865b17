"""Step functions lowered by the launcher / dry-run driver.

Three entry points, matching the assigned input-shape kinds:

  * ``train``   — one LPT optimizer step (soft-prompt grads ONLY; model
                  weights frozen). Microbatched gradient accumulation via
                  ``jax.lax.scan`` when the global batch doesn't fit.
  * ``prefill`` — batched Eqn-1 scoring: backbone forward + chunked CE,
                  per-example losses. This is the Prompt Bank's hot path
                  and the LPT analog of inference prefill.
  * ``decode``  — one-token serve step against a KV cache of the given
                  length (``serve_step``).

``input_specs`` produces ShapeDtypeStruct stand-ins for every input
(weak-type-correct, shardable, no device allocation); ``step_shardings``
produces the matching ``in_shardings`` trees.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.config import InputShape, ModelConfig, TuneConfig, INPUT_SHAPES
from repro.launch import mesh as mesh_lib
from repro.models import Model, build_model
from repro.train.objectives import lpt_loss_chunked
from repro.train.optimizer import adam, apply_updates
from repro.train.remat import GradProgram

# Sub-quadratic long-context policy (DESIGN.md §5): dense full-attention
# archs run long_500k with a sliding-window cache variant.
LONG_CONTEXT_WINDOW = 8192
SUBQUADRATIC_NATIVE = {"ssm", "hybrid"}      # recurrent state: native O(1)
MLA_COMPRESSED = "mla"                       # deepseek: O(L) latent cache


def model_for_shape(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """Apply per-shape config adaptations (sliding window for long decode
    on full-attention archs)."""
    if (
        shape.name == "long_500k"
        and cfg.arch_type not in SUBQUADRATIC_NATIVE
        and cfg.attention == "gqa"
        and cfg.sliding_window == 0
    ):
        return cfg.with_overrides(sliding_window=LONG_CONTEXT_WINDOW)
    return cfg


def supports_shape(cfg: ModelConfig, shape: InputShape) -> Tuple[bool, str]:
    """All assigned archs support all four shapes (DESIGN.md §5): SSM /
    hybrid / MLA are natively sub-quadratic at 500k; dense GQA archs use
    the sliding-window variant."""
    return True, ""


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def make_train_step(model: Model, tune_cfg: TuneConfig, *,
                    microbatches: int = 1, ce_chunk: int = 512,
                    batch_axes: Tuple[str, ...] = ()):
    """(params, prompt_params, opt_state, batch) ->
    (prompt_params, opt_state, loss). Grads w.r.t. the prompt only.

    The step is a :class:`~repro.train.remat.GradProgram`: call it (or
    its ``lower``) with placed arrays or sharded shape structs, and it
    compiles on the first save rung that fits the devices; inside
    another jit it traces full rematerialisation.

    ``batch_axes``: mesh axes the per-microbatch batch dim must stay
    sharded over (the reshape to (m, B/m, ...) would otherwise let GSPMD
    move the sharding onto the scan axis, silently un-sharding each
    microbatch)."""
    opt = adam(tune_cfg.lr, weight_decay=tune_cfg.weight_decay)
    key = ("make_train_step", tune_cfg, microbatches, ce_chunk,
           tuple(batch_axes))
    return GradProgram(
        lambda m: _train_step(m, opt, microbatches, ce_chunk, batch_axes),
        model, key), opt


def _train_step(model: Model, opt, microbatches: int, ce_chunk: int,
                batch_axes: Tuple[str, ...]):
    def loss_fn(prompt_params, params, batch):
        tot, (loss, _) = lpt_loss_chunked(
            model, params, prompt_params["soft_prompt"], batch, chunk=ce_chunk
        )
        return tot, loss

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, prompt_params, opt_state, batch):
        if microbatches == 1:
            (tot, loss), grads = grad_fn(prompt_params, params, batch)
        else:
            m = microbatches

            ba = (tuple(batch_axes) if len(batch_axes) != 1
                  else batch_axes[0]) or None

            def split(x):
                b = x.shape[0]
                y = x.reshape(m, b // m, *x.shape[1:])
                if ba is not None:
                    y = jax.lax.with_sharding_constraint(
                        y, P(None, ba, *([None] * (y.ndim - 2)))
                    )
                return y

            mb = {k: split(v) for k, v in batch.items()}

            def body(carry, xs):
                g_acc, l_acc = carry
                (tot, loss), g = grad_fn(prompt_params, params, xs)
                g_acc = jax.tree.map(lambda a, b: a + b, g_acc, g)
                return (g_acc, l_acc + loss), None

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), prompt_params
            )
            (grads, loss), _ = jax.lax.scan(
                body, (zero, jnp.zeros((), jnp.float32)), mb
            )
            grads = jax.tree.map(lambda g: g / m, grads)
            loss = loss / m
        updates, new_opt = opt.update(grads, opt_state, prompt_params)
        new_prompt = apply_updates(prompt_params, updates)
        return new_prompt, new_opt, loss

    return train_step


def make_prefill_step(model: Model, *, ce_chunk: int = 512):
    """Batched Eqn-1 scoring: (params, prompt_params, batch) -> (B,) loss."""

    def prefill_step(params, prompt_params, batch):
        tot, (loss, per_ex) = lpt_loss_chunked(
            model, params, prompt_params["soft_prompt"], batch, chunk=ce_chunk
        )
        return per_ex

    return prefill_step


def make_serve_step(model: Model):
    """One-token decode: (params, cache, tokens, cache_len) ->
    (next_token (B,1) i32, new_cache)."""

    def serve_step(params, cache, tokens, cache_len):
        logits, new_cache = model.decode_step(params, cache, tokens, cache_len)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, new_cache

    return serve_step


# ---------------------------------------------------------------------------
# Abstract inputs + shardings
# ---------------------------------------------------------------------------


def _frontend_spec(cfg: ModelConfig, batch: int):
    if cfg.frontend.kind == "none":
        return None
    return jax.ShapeDtypeStruct(
        (batch, cfg.frontend.num_embeddings, cfg.frontend.embed_dim),
        jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32,
    )


def batch_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    B, S = shape.global_batch, shape.seq_len
    d = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "mask": jax.ShapeDtypeStruct((B, S), jnp.float32),
    }
    fe = _frontend_spec(cfg, B)
    if fe is not None:
        d["frontend"] = fe
    return d


def prompt_specs(cfg: ModelConfig, tune_cfg: TuneConfig) -> Dict[str, Any]:
    return {
        "soft_prompt": jax.ShapeDtypeStruct(
            (tune_cfg.prompt_len, cfg.d_model), jnp.float32
        )
    }


def input_specs(model: Model, shape: InputShape,
                tune_cfg: Optional[TuneConfig] = None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every step input, keyed by arg name."""
    cfg = model.cfg
    tune_cfg = tune_cfg or TuneConfig()
    if shape.kind == "train":
        pp = prompt_specs(cfg, tune_cfg)
        opt_state = jax.eval_shape(
            lambda: adam(tune_cfg.lr).init(
                jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), pp)
            )
        )
        return {
            "params": model.abstract_params(),
            "prompt_params": pp,
            "opt_state": opt_state,
            "batch": batch_specs(cfg, shape),
        }
    if shape.kind == "prefill":
        return {
            "params": model.abstract_params(),
            "prompt_params": prompt_specs(cfg, tune_cfg),
            "batch": batch_specs(cfg, shape),
        }
    if shape.kind == "decode":
        B = shape.global_batch
        return {
            "params": model.abstract_params(),
            "cache": model.abstract_cache(B, shape.seq_len),
            "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "cache_len": jax.ShapeDtypeStruct((), jnp.int32),
        }
    raise ValueError(shape.kind)


def step_shardings(model: Model, shape: InputShape, mesh: Mesh,
                   specs: Dict[str, Any]) -> Dict[str, Any]:
    """in_shardings tree matching :func:`input_specs`'s structure."""
    cfg = model.cfg
    B, S = shape.global_batch, shape.seq_len
    param_sh = mesh_lib.tree_named(mesh, model.partition_specs())

    def dsh(ndim, seq_dim=None):
        return mesh_lib.named(
            mesh,
            mesh_lib.batch_spec(mesh, B, ndim, seq_dim=seq_dim, seq_len=S),
        )

    repl = mesh_lib.named(mesh, P())
    out: Dict[str, Any] = {}
    for key, val in specs.items():
        if key == "params":
            out[key] = param_sh
        elif key in ("prompt_params", "opt_state"):
            out[key] = jax.tree.map(lambda _: repl, val)
        elif key == "batch":
            out[key] = {
                k: dsh(v.ndim) for k, v in val.items()
            }
        elif key == "cache":
            cspecs = mesh_lib.cache_partition_specs(val, mesh)
            out[key] = mesh_lib.tree_named(mesh, cspecs)
        elif key == "tokens":
            out[key] = dsh(2)
        elif key == "cache_len":
            out[key] = repl
        else:
            raise KeyError(key)
    return out


def build_step(arch_cfg: ModelConfig, shape_name: str, mesh: Mesh, *,
               tune_cfg: Optional[TuneConfig] = None,
               microbatches: int = 1, ce_chunk: int = 512):
    """Assemble (step, specs, shardings, model) for one (arch, shape).
    ``step`` is jitted (a train step is a ``GradProgram``): lower it with
    the specs placed as ``shardings`` says."""
    shape = INPUT_SHAPES[shape_name]
    tune_cfg = tune_cfg or TuneConfig()
    cfg = model_for_shape(arch_cfg, shape)
    data_size = mesh.shape["data"] if "data" in mesh.axis_names else 0
    model = build_model(cfg, model_axis=mesh_lib.model_axis_size(mesh),
                        data_axis=data_size, mesh=mesh)
    specs = input_specs(model, shape, tune_cfg)
    shardings = step_shardings(model, shape, mesh, specs)
    if shape.kind == "train":
        fn, _ = make_train_step(model, tune_cfg, microbatches=microbatches,
                                ce_chunk=ce_chunk,
                                batch_axes=mesh_lib.data_axes(mesh))
    elif shape.kind == "prefill":
        fn = jax.jit(make_prefill_step(model, ce_chunk=ce_chunk))
    else:
        fn = jax.jit(make_serve_step(model))
    return fn, specs, shardings, model
