"""Model composition: blocks -> segments -> full architectures.

A model is a sequence of *segments*; each segment is ``count`` identical
blocks whose parameters are stacked on a leading axis and executed with
``jax.lax.scan`` (key to keeping HLO size and compile time sane at 40-80
layer depths). Hybrid architectures interleave segments with a *shared*
attention block (single parameter set, Zamba2-style). Encoder-decoder
models own an encoder stack plus cross-attention in every decoder block.

Public API (all pure functions; ``Model`` is a thin namespace):
    build_model(cfg, model_axis) -> Model
    model.param_specs            ParamSpec tree
    model.init(key)              params
    model.partition_specs()      PartitionSpec tree
    model.abstract_params()      ShapeDtypeStruct tree
    model.forward(params, tokens, prompt=None, frontend=None)
        -> logits (B, S_total, V), aux (dict)
    model.init_cache(batch, cache_len) / model.abstract_cache(...)
    model.decode_step(params, cache, tokens, cache_len)
        -> logits (B, 1, V), new_cache
"""
from __future__ import annotations

import copy
import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (
    ParamSpec,
    abstract_tree,
    apply_ffn,
    apply_norm,
    embed_params,
    embed_tokens,
    ffn_params,
    materialize,
    maybe_model,
    norm_params,
    specs_tree,
    stack_specs,
    unembed,
)


@dataclass(frozen=True)
class Segment:
    kind: str          # dense | moe | rwkv | mamba | encoder | decoder_cross
    count: int
    name: str


# ---------------------------------------------------------------------------
# Per-block parameter trees
# ---------------------------------------------------------------------------


def _attn_params(cfg: ModelConfig, model_axis: int):
    if cfg.attention == "mla":
        return attn.mla_params(cfg, model_axis)
    return attn.gqa_params(cfg, model_axis)


def _dense_ffn_width(cfg: ModelConfig) -> int:
    """Width of the dense FFN in MoE models' first dense layers."""
    m = cfg.moe
    if m is None:
        return cfg.d_ff
    return m.d_ff_expert * (m.top_k + m.num_shared_experts)


def block_param_specs(cfg: ModelConfig, kind: str, model_axis: int,
                      data_axis: int = 0) -> Dict:
    d = cfg.d_model
    if kind == "dense":
        return {
            "ln1": norm_params(cfg, d),
            "attn": _attn_params(cfg, model_axis),
            "ln2": norm_params(cfg, d),
            "ffn": ffn_params(cfg, d, _dense_ffn_width(cfg), model_axis),
        }
    if kind == "moe":
        return {
            "ln1": norm_params(cfg, d),
            "attn": _attn_params(cfg, model_axis),
            "ln2": norm_params(cfg, d),
            "moe": moe_mod.moe_params(cfg, model_axis, data_axis),
        }
    if kind == "rwkv":
        return {
            "ln1": norm_params(cfg, d),
            "tmix": ssm_mod.rwkv6_params(cfg, model_axis),
            "ln2": norm_params(cfg, d),
            "ffn": ffn_params(cfg, d, cfg.d_ff, model_axis),
        }
    if kind == "mamba":
        return {
            "ln": norm_params(cfg, d),
            "mixer": ssm_mod.mamba2_params(cfg, model_axis),
        }
    if kind == "encoder":
        return {
            "ln1": norm_params(cfg, d),
            "attn": attn.gqa_params(cfg, model_axis),
            "ln2": norm_params(cfg, d),
            "ffn": ffn_params(cfg, d, cfg.d_ff, model_axis),
        }
    if kind == "decoder_cross":
        return {
            "ln1": norm_params(cfg, d),
            "attn": attn.gqa_params(cfg, model_axis),
            "lnx": norm_params(cfg, d),
            "cross": attn.cross_attention_params(cfg, model_axis),
            "ln2": norm_params(cfg, d),
            "ffn": ffn_params(cfg, d, cfg.d_ff, model_axis),
        }
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Per-block forward / decode
# ---------------------------------------------------------------------------


def _attn_forward(cfg, p, x, positions, causal=True):
    if cfg.attention == "mla":
        return attn.mla_forward(cfg, p, x, positions, causal=causal)
    return attn.gqa_forward(cfg, p, x, positions, causal=causal)


def block_forward(cfg: ModelConfig, kind: str, p: Dict, x, positions, ctx: Dict):
    """Returns (x, aux_scalar, new_state_or_None)."""
    aux = jnp.zeros((), jnp.float32)
    state = None
    if kind in ("dense", "encoder"):
        causal = kind == "dense"
        if cfg.parallel_block:
            h = apply_norm(cfg, p["ln1"], x)
            x = x + _attn_forward(cfg, p["attn"], h, positions, causal) + apply_ffn(
                cfg, p["ffn"], h
            )
        else:
            x = x + _attn_forward(
                cfg, p["attn"], apply_norm(cfg, p["ln1"], x), positions, causal
            )
            x = x + apply_ffn(cfg, p["ffn"], apply_norm(cfg, p["ln2"], x))
    elif kind == "moe":
        x = x + _attn_forward(
            cfg, p["attn"], apply_norm(cfg, p["ln1"], x), positions, True
        )
        y, aux = moe_mod.moe_ffn(cfg, p["moe"], apply_norm(cfg, p["ln2"], x),
                                 mesh=ctx.get("mesh"))
        x = x + y
    elif kind == "rwkv":
        y, state = ssm_mod.rwkv6_forward(
            cfg, p["tmix"], apply_norm(cfg, p["ln1"], x), ctx.get("state")
        )
        x = x + y
        x = x + apply_ffn(cfg, p["ffn"], apply_norm(cfg, p["ln2"], x))
    elif kind == "mamba":
        y, state = ssm_mod.mamba2_forward(
            cfg, p["mixer"], apply_norm(cfg, p["ln"], x), ctx.get("state")
        )
        x = x + y
    elif kind == "decoder_cross":
        x = x + attn.gqa_forward(
            cfg, p["attn"], apply_norm(cfg, p["ln1"], x), positions, causal=True
        )
        x = x + attn.cross_attention(
            cfg, p["cross"], apply_norm(cfg, p["lnx"], x), ctx["enc_kv"]
        )
        x = x + apply_ffn(cfg, p["ffn"], apply_norm(cfg, p["ln2"], x))
    else:
        raise ValueError(kind)
    return x, aux, state


def block_decode(cfg: ModelConfig, kind: str, p: Dict, x, cache, cache_len, ctx):
    """One-token step. Returns (x, new_cache)."""
    if kind in ("dense", "moe"):
        h = apply_norm(cfg, p["ln1"], x)
        if cfg.attention == "mla":
            y, kv = attn.mla_decode(cfg, p["attn"], h, cache["kv"], cache_len)
        else:
            y, kv = attn.gqa_decode(cfg, p["attn"], h, cache["kv"], cache_len)
        if cfg.parallel_block and kind == "dense":
            x = x + y + apply_ffn(cfg, p["ffn"], h)
            return x, {"kv": kv}
        x = x + y
        if kind == "moe":
            y2, _ = moe_mod.moe_ffn(cfg, p["moe"], apply_norm(cfg, p["ln2"], x))
            x = x + y2
        else:
            x = x + apply_ffn(cfg, p["ffn"], apply_norm(cfg, p["ln2"], x))
        return x, {"kv": kv}
    if kind == "rwkv":
        y, st = ssm_mod.rwkv6_decode(
            cfg, p["tmix"], apply_norm(cfg, p["ln1"], x), cache["state"]
        )
        x = x + y
        x = x + apply_ffn(cfg, p["ffn"], apply_norm(cfg, p["ln2"], x))
        return x, {"state": st}
    if kind == "mamba":
        y, st = ssm_mod.mamba2_decode(
            cfg, p["mixer"], apply_norm(cfg, p["ln"], x), cache["state"]
        )
        return x + y, {"state": st}
    if kind == "decoder_cross":
        h = apply_norm(cfg, p["ln1"], x)
        y, kv = attn.gqa_decode(cfg, p["attn"], h, cache["kv"], cache_len)
        x = x + y
        x = x + attn.cross_attention(
            cfg, p["cross"], apply_norm(cfg, p["lnx"], x), ctx["enc_kv"]
        )
        x = x + apply_ffn(cfg, p["ffn"], apply_norm(cfg, p["ln2"], x))
        return x, {"kv": kv}
    raise ValueError(kind)


def block_cache(cfg: ModelConfig, kind: str, batch: int, length: int):
    dt = jnp.dtype(cfg.dtype)
    if kind in ("dense", "moe", "decoder_cross"):
        if cfg.attention == "mla":
            return {"kv": attn.mla_init_cache(cfg, batch, length, dt)}
        L = min(length, cfg.sliding_window) if cfg.sliding_window else length
        return {"kv": attn.gqa_init_cache(cfg, batch, L, dt)}
    if kind == "rwkv":
        return {"state": ssm_mod.rwkv6_init_state(cfg, batch)}
    if kind == "mamba":
        return {"state": ssm_mod.mamba2_init_state(cfg, batch)}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Model assembly
# ---------------------------------------------------------------------------


def plan_segments(cfg: ModelConfig) -> List[Segment]:
    at = cfg.arch_type
    L = cfg.num_layers
    if at in ("dense", "vlm"):
        return [Segment("dense", L, "blocks")]
    if at == "moe":
        fd = cfg.moe.first_dense_layers
        segs = []
        if fd:
            segs.append(Segment("dense", fd, "dense0"))
        segs.append(Segment("moe", L - fd, "moe"))
        return segs
    if at == "ssm":
        kind = "rwkv" if cfg.ssm.kind == "rwkv6" else "mamba"
        return [Segment(kind, L, "blocks")]
    if at == "hybrid":
        every = cfg.hybrid.attn_every
        segs = []
        i = 0
        g = 0
        while i < L:
            n = min(every, L - i)
            segs.append(Segment("mamba", n, f"mamba{g}"))
            i += n
            g += 1
        return segs
    if at == "audio":
        return [Segment("decoder_cross", L, "decoder")]
    raise ValueError(at)


class Model:
    def __init__(self, cfg: ModelConfig, model_axis: int = 1,
                 data_axis: int = 0, mesh=None):
        self.cfg = cfg
        self.model_axis = model_axis
        self.data_axis = data_axis
        self.mesh = mesh            # enables shard_map expert parallelism
        self.segments = plan_segments(cfg)
        self.param_specs = self._build_param_specs()
        self.saved: Tuple[str, ...] = ()    # see saving()

    def saving(self, names: Tuple[str, ...]) -> "Model":
        """This model with its checkpointed layer scans keeping the
        outputs tagged ``names`` (``checkpoint_name``: "q", "k", "v" in
        attention, "gate", "up" in the FFN) for the backward pass instead
        of recomputing them. ``()`` keeps nothing: the backward pass
        recomputes each layer's forward. Forward-only programs do not
        change. ``repro.train.remat`` chooses the names."""
        model = copy.copy(self)
        model.saved = tuple(names)
        return model

    def _remat(self, body):
        if not self.cfg.remat:
            return body
        if not self.saved:
            return jax.checkpoint(body)
        return jax.checkpoint(body, policy=jax.checkpoint_policies
                              .save_only_these_names(*self.saved))

    # -- parameters ---------------------------------------------------------

    def _build_param_specs(self) -> Dict:
        cfg, ma = self.cfg, self.model_axis
        da = self.data_axis
        tree: Dict[str, Any] = {}
        tree.update(embed_params(cfg, ma))
        tree["final_norm"] = norm_params(cfg, cfg.d_model)
        for seg in self.segments:
            blk = block_param_specs(cfg, seg.kind, ma, da)
            tree[seg.name] = stack_specs(blk, seg.count)
        if cfg.hybrid is not None and cfg.hybrid.shared_attn:
            tree["shared_attn"] = {
                "ln": norm_params(cfg, cfg.d_model),
                "attn": attn.gqa_params(cfg, ma),
                "ln2": norm_params(cfg, cfg.d_model),
                "ffn": ffn_params(cfg, cfg.d_model, cfg.d_ff, ma),
            }
        if cfg.encdec is not None:
            enc_blk = block_param_specs(cfg, "encoder", ma)
            tree["encoder"] = {
                "blocks": stack_specs(enc_blk, cfg.encdec.num_encoder_layers),
                "final_norm": norm_params(cfg, cfg.d_model),
            }
        if cfg.frontend.kind != "none":
            tree["frontend_proj"] = ParamSpec(
                (cfg.frontend.embed_dim, cfg.d_model), P(None, None)
            )
        return tree

    def init(self, key: jax.Array):
        return materialize(self.param_specs, key, self.cfg.param_dtype)

    def partition_specs(self):
        return specs_tree(self.param_specs)

    def abstract_params(self):
        return abstract_tree(self.param_specs, self.cfg.param_dtype)

    # -- embedding of mixed inputs ------------------------------------------

    def embed_inputs(self, params, tokens, prompt=None, frontend=None):
        """[frontend embeddings][soft prompt][token embeddings] -> (B,S,d).

        prompt: (P, d) shared or (B, P, d); frontend: (B, F, e_frontend)."""
        cfg = self.cfg
        x = embed_tokens(params, tokens, cfg.dtype)
        B = x.shape[0]
        parts = []
        if frontend is not None:
            fe = (frontend @ params["frontend_proj"]).astype(x.dtype)
            parts.append(fe)
        if prompt is not None:
            # broadcast before the cast: the prompt gradient then sums
            # over the batch in the prompt's own (f32) precision
            pe = prompt
            if pe.ndim == 2:
                pe = jnp.broadcast_to(pe[None], (B, *pe.shape))
            parts.append(pe.astype(x.dtype))
        parts.append(x)
        x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else x
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
        return x, positions

    def _maybe_seq_shard(self, x):
        """Context parallelism: activations (B, S, d) sharded (data,
        model, -) when enabled and divisible. GSPMD then all-gathers K/V
        inside attention instead of replicating every (B, H, S, L) score
        tensor across the model axis."""
        cfg, mesh = self.cfg, self.mesh
        if not (cfg.seq_shard and mesh is not None
                and "model" in mesh.axis_names):
            return x
        from repro.models.common import constrain
        B, S, d = x.shape
        mp = mesh.shape["model"]
        if S % mp != 0:
            return x
        da = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        dp = 1
        for a in da:
            dp *= mesh.shape[a]
        batch_entry = None
        if B % dp == 0 and B >= dp:
            batch_entry = da if len(da) > 1 else da[0]
        return constrain(x, P(batch_entry, "model", None))

    # -- encoder (audio/enc-dec) ---------------------------------------------

    def encode(self, params, frontend):
        cfg = self.cfg
        x = (frontend @ params["frontend_proj"]).astype(jnp.dtype(cfg.dtype))
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
        enc = params["encoder"]

        def body(h, lp):
            h, _, _ = block_forward(cfg, "encoder", lp, h, positions, {})
            return h, None

        fn = self._remat(body)
        x, _ = jax.lax.scan(fn, x, enc["blocks"])
        return apply_norm(cfg, enc["final_norm"], x)

    # -- full forward (train / prefill) --------------------------------------

    def backbone(self, params, tokens, prompt=None, frontend=None):
        """Runs everything up to (and incl.) the final norm; returns
        (hidden (B,S,d), aux). Used by forward() and by the Prompt Bank's
        activation-feature extraction."""
        cfg = self.cfg
        ctx: Dict[str, Any] = {"mesh": self.mesh}
        if cfg.encdec is not None:
            enc_out = self.encode(params, frontend)
            frontend_dec = None
        else:
            enc_out = None
            frontend_dec = frontend
        x, positions = self.embed_inputs(params, tokens, prompt, frontend_dec)
        x = self._maybe_seq_shard(x)
        aux_total = jnp.zeros((), jnp.float32)

        for si, seg in enumerate(self.segments):
            stacked = params[seg.name]
            if seg.kind == "decoder_cross":
                # cross KV differs per layer: compute inside scan from enc_out
                def body(carry, lp):
                    h, aux = carry
                    ctx2 = {"enc_kv": attn.encode_cross_kv(cfg, lp["cross"], enc_out)}
                    h, a, _ = block_forward(cfg, seg.kind, lp, h, positions, ctx2)
                    return (h, aux + a), None

                fn = self._remat(body)
                (x, aux_total), _ = jax.lax.scan(fn, (x, aux_total), stacked)
            elif seg.kind in ("rwkv", "mamba"):
                def body(carry, lp):
                    h, aux = carry
                    h, a, _ = block_forward(cfg, seg.kind, lp, h, positions,
                                            {"state": None})
                    return (h, aux + a), None

                fn = self._remat(body)
                (x, aux_total), _ = jax.lax.scan(fn, (x, aux_total), stacked)
            else:
                def body(carry, lp):
                    h, aux = carry
                    h, a, _ = block_forward(cfg, seg.kind, lp, h, positions, ctx)
                    return (h, aux + a), None

                fn = self._remat(body)
                (x, aux_total), _ = jax.lax.scan(fn, (x, aux_total), stacked)
            # Zamba2-style shared attention between SSM segments
            if (
                cfg.hybrid is not None
                and cfg.hybrid.shared_attn
                and seg.kind == "mamba"
                and si < len(self.segments) - 1
            ):
                sa = params["shared_attn"]
                x = x + attn.gqa_forward(
                    cfg, sa["attn"], apply_norm(cfg, sa["ln"], x), positions,
                    causal=True,
                )
                x = x + apply_ffn(cfg, sa["ffn"], apply_norm(cfg, sa["ln2"], x))

        x = apply_norm(cfg, params["final_norm"], x)
        return x, {"aux_loss": aux_total}

    def forward(self, params, tokens, prompt=None, frontend=None):
        """Returns (logits (B,S_total,V) f32, aux dict)."""
        x, aux = self.backbone(params, tokens, prompt, frontend)
        return unembed(self.cfg, params, x), aux

    # -- caches ---------------------------------------------------------------

    def _seg_cache(self, seg: Segment, batch: int, length: int):
        one = block_cache(self.cfg, seg.kind, batch, length)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (seg.count, *a.shape)), one
        )

    def init_cache(self, batch: int, length: int):
        cache: Dict[str, Any] = {
            seg.name: self._seg_cache(seg, batch, length) for seg in self.segments
        }
        cfg = self.cfg
        if cfg.hybrid is not None and cfg.hybrid.shared_attn:
            # shared WEIGHTS, but one KV cache per application depth
            n_apps = max(len(self.segments) - 1, 0)
            cache["shared_attn"] = {
                f"app{i}": block_cache(cfg, "dense", batch, length)
                for i in range(n_apps)
            }
        if cfg.encdec is not None:
            # cross-attention KV per decoder layer, precomputed at prefill
            Hkv, hd = cfg.kv_heads(), cfg.resolved_head_dim()
            Lenc = cfg.encdec.encoder_seq_len
            n = self.segments[0].count
            cache["cross_kv"] = {
                "k": jnp.zeros((n, batch, Lenc, Hkv, hd), jnp.dtype(cfg.dtype)),
                "v": jnp.zeros((n, batch, Lenc, Hkv, hd), jnp.dtype(cfg.dtype)),
            }
        return cache

    def abstract_cache(self, batch: int, length: int):
        return jax.eval_shape(lambda: self.init_cache(batch, length))

    # -- decode step ------------------------------------------------------------

    def decode_step(self, params, cache, tokens, cache_len):
        """tokens: (B,1) int32; cache_len: scalar int32 (tokens already cached)."""
        cfg = self.cfg
        x = embed_tokens(params, tokens, cfg.dtype)
        new_cache: Dict[str, Any] = {}

        for si, seg in enumerate(self.segments):
            stacked_p = params[seg.name]
            stacked_c = cache[seg.name]
            if seg.kind == "decoder_cross":
                xkv = cache["cross_kv"]

                def body(h, xs):
                    lp, lc, ck, cv = xs
                    h, c2 = block_decode(
                        cfg, seg.kind, lp, h, lc, cache_len,
                        {"enc_kv": (ck, cv)},
                    )
                    return h, c2

                x, seg_cache = jax.lax.scan(
                    body, x, (stacked_p, stacked_c, xkv["k"], xkv["v"])
                )
                new_cache["cross_kv"] = xkv
            else:
                def body(h, xs):
                    lp, lc = xs
                    h, c2 = block_decode(cfg, seg.kind, lp, h, lc, cache_len, {})
                    return h, c2

                x, seg_cache = jax.lax.scan(body, x, (stacked_p, stacked_c))
            new_cache[seg.name] = seg_cache
            if (
                cfg.hybrid is not None
                and cfg.hybrid.shared_attn
                and seg.kind == "mamba"
                and si < len(self.segments) - 1
            ):
                sa = params["shared_attn"]
                app = f"app{si}"
                y, kv = attn.gqa_decode(
                    cfg, sa["attn"], apply_norm(cfg, sa["ln"], x),
                    cache["shared_attn"][app]["kv"], cache_len,
                )
                x = x + y
                x = x + apply_ffn(cfg, sa["ffn"], apply_norm(cfg, sa["ln2"], x))
                new_cache.setdefault("shared_attn", {})[app] = {"kv": kv}

        x = apply_norm(cfg, params["final_norm"], x)
        logits = unembed(cfg, params, x)
        return logits, new_cache


def build_model(cfg: ModelConfig, model_axis: int = 1,
                data_axis: int = 0, mesh=None) -> Model:
    return Model(cfg, model_axis, data_axis, mesh)
