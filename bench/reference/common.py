"""What the reference decoders share: plain float32 layers, the masked
cross-entropy of Eqn 1, Adam, and the drivers that run a model one layer
at a time, so that a layer's float32 weights are drawn, used and
dropped before the next.

Callers run these under ``jax.default_matmul_precision("highest")``:
on a TPU a float32 matmul is otherwise done in bfloat16 passes.

``quant`` selects the control's precision: ``None`` is float32;
``"fp8"`` rounds both operands of every matmul (weights, activations,
attention scores and probabilities) to float8 e4m3, and the gradients
flowing back into them to e5m2, each with one scale per tensor,
accumulating in float32 -- the step below the bfloat16 that the
configurations state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import weights

F32 = jnp.float32
E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _round(x, dtype, top):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round(x, jnp.float8_e4m3fn, E4M3_MAX)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    # the usual fp8 training recipe: gradients in e5m2, scaled per tensor
    return (_round(g, jnp.float8_e5m2, E5M2_MAX),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def quant(x, mode):
    if mode is None:
        return x
    if mode != "fp8":
        raise ValueError(f"unknown precision {mode!r}")
    return _fp8(x)


def mm(spec, a, b, mode):
    return jnp.einsum(spec, quant(a, mode), quant(b, mode))


def layer_norm(x, gamma, beta, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gamma + beta


def rms_norm(x, gamma, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gamma


def rope(x, theta):
    """Rotary embedding, rotate-half convention; x: (B, T, H, hd)."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, w, h, mode):
    """Causal multi-head attention with grouped K/V heads and QKV bias;
    positions count from the first (prompt) position."""
    H, Hkv = cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg["d_model"] // H
    q = mm("btd,dhk->bthk", h, w["attn/wq"], mode) + w["attn/bq"]
    k = mm("btd,dhk->bthk", h, w["attn/wk"], mode) + w["attn/bk"]
    v = mm("btd,dhk->bthk", h, w["attn/wv"], mode) + w["attn/bv"]
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = mm("bqhk,bshk->bhqs", q, k, mode) / math.sqrt(hd)
    T = h.shape[1]
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm("bhqs,bshk->bqhk", p, v, mode)
    return mm("bqhk,hkd->bqd", o, w["attn/wo"], mode)


def masked_ce(logits, labels, mask):
    """Mean next-token cross-entropy over the masked positions."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return ((logz - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)


class Reference:
    """One configuration's reference, driven layer by layer.

    A family with one kind of layer defines ``layer_shapes(cfg)`` and
    ``block(cfg, w, x, mode)``. A family with more than one defines
    ``kinds(cfg)``, one kind per layer in order, and takes the kind as a
    last argument of both. Layer ``i``'s tensors are drawn at global index
    ``i`` (``weights.py``); a stack of routed experts from the file's
    ``moe.expert_offset``."""

    def __init__(self, family, cfg, words, mode=None):
        self.fam, self.cfg, self.words, self.mode = family, cfg, words, mode
        dt = cfg["param_dtype"]
        top_shapes = family.top_shapes(cfg)
        experts = weights.expert_offset(cfg)
        if hasattr(family, "kinds"):
            self.kinds = list(family.kinds(cfg))
            if len(self.kinds) != cfg["num_layers"]:
                raise ValueError(f"{len(self.kinds)} layer kinds for "
                                 f"{cfg['num_layers']} layers")
        else:
            self.kinds = [None] * cfg["num_layers"]

        def layer(kind, words, i, x):
            of = () if kind is None else (kind,)
            w = weights.group(words, family.layer_shapes(cfg, *of), dt, i,
                              experts)
            return family.block(cfg, w, x, mode, *of)

        def head_loss(words, h, labels, mask):
            top = weights.group(words, top_shapes, dt)
            logits = family.logits(cfg, top, family.final(cfg, top, h), mode)
            return masked_ce(logits, labels, mask)

        def embed(words, tokens):
            top = weights.group(words, {"embedding": top_shapes["embedding"]},
                                dt)
            return top["embedding"][tokens]

        self._layers, self._layer_vjps = {}, {}
        for kind in set(self.kinds):
            fn = functools.partial(layer, kind)
            self._layers[kind] = jax.jit(fn)
            self._layer_vjps[kind] = jax.jit(
                lambda words, i, x, g, fn=fn: jax.vjp(
                    functools.partial(fn, words, i), x)[1](g)[0])
        self._head = jax.jit(head_loss)
        self._head_grad = jax.jit(jax.value_and_grad(head_loss, argnums=1))
        self._embed = jax.jit(embed)

    def _layer(self, words, i, x):
        return self._layers[self.kinds[i]](words, i, x)

    def _layer_vjp(self, words, i, x, g):
        return self._layer_vjps[self.kinds[i]](words, i, x, g)

    def inputs(self, prompts, tokens):
        """[prompt; token embeddings]; prompts (B, P, d), tokens (B, S)."""
        return jnp.concatenate(
            [jnp.asarray(prompts, F32), self._embed(self.words, tokens)], 1)

    def scores(self, prompts, batches):
        """Eqn-1 score of each (prompt (P, d), batch) pair: the mean
        masked cross-entropy over the batch's target tokens. All pairs
        pass through one layer before the next."""
        n = [b["tokens"].shape[0] for b in batches]
        x = self.inputs(
            jnp.concatenate([jnp.broadcast_to(jnp.asarray(p, F32)[None],
                                              (k, *p.shape))
                             for p, k in zip(prompts, n)]),
            jnp.concatenate([jnp.asarray(b["tokens"]) for b in batches]))
        for i in range(self.cfg["num_layers"]):
            x = self._layer(self.words, i, x)
        P, out, lo = prompts[0].shape[0], [], 0
        for b, k in zip(batches, n):
            out.append(float(self._head(self.words, x[lo:lo + k, P:],
                                        b["labels"], b["mask"])))
            lo += k
        return out

    def loss_and_grad(self, prompt, batch):
        """Loss and its gradient with respect to the prompt (P, d), the
        prompt shared by every row of the batch."""
        B = batch["tokens"].shape[0]
        P = prompt.shape[0]
        x = self.inputs(jnp.broadcast_to(prompt[None], (B, *prompt.shape)),
                        batch["tokens"])
        xs = []
        for i in range(self.cfg["num_layers"]):
            xs.append(x)
            x = self._layer(self.words, i, x)
        loss, g_tok = self._head_grad(self.words, x[:, P:], batch["labels"],
                                      batch["mask"])
        g = jnp.concatenate([jnp.zeros_like(x[:, :P]), g_tok], 1)
        for i in reversed(range(self.cfg["num_layers"])):
            g = self._layer_vjp(self.words, i, xs[i], g)
        return float(loss), g[:, :P].sum(0)


def adam_steps(ref, p0, batches, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam on the prompt over ``batches``; returns the losses, the first
    gradient, and the prompt after the last step."""
    p = jnp.asarray(p0, F32)
    mu = nu = jnp.zeros_like(p)
    losses, first_grad = [], None
    for t, batch in enumerate(batches, 1):
        loss, g = ref.loss_and_grad(p, batch)
        losses.append(loss)
        first_grad = g if first_grad is None else first_grad
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * g * g
        p = p - lr * (mu / (1 - b1 ** t)) / (jnp.sqrt(nu / (1 - b2 ** t)) + eps)
    return losses, first_grad, p
