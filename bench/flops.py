"""Floating-point operations the work requires, from a configuration
file's shapes (one multiply-add counts 2), and the bytes of a layer's
weights.

Required, not executed:
- the forward counts every position (prompt and task) through every
  layer -- projections, the causal half of the attention products, the
  MLP or expert layer -- and the unembedding only over the S task
  positions, whose logits the loss reads;
- the tuning step adds an activation-only backward (the weights are
  frozen): one product per weight product for dX, two per attention
  product (dQ/dK, dP/dV), one for the unembedding;
- norms, softmax, routing's top-k and other elementwise work are left
  out, as are rematerialised recomputation and prompt-position logits.

Layers are counted by kind (``layer_kinds``): a file with a ``moe``
section has ``moe.first_dense_layers`` leading dense layers of width
``d_ff`` and expert layers after them; otherwise every layer is dense.
- Attention: grouped-query at ``head_dim`` (or d_model / num_heads), or,
  with an ``mla`` section, latent attention: q through ``q_lora_rank``
  (or directly when it is 0), the joint ``kv_a`` projection to
  ``kv_lora_rank + qk_rope_head_dim``, its decompression to the nope keys
  and the values, and the output projection. QK^T at head dim
  ``qk_nope_head_dim + qk_rope_head_dim``, PV at ``v_head_dim``.
- Expert layer: the router over all ``num_experts``, the
  ``num_shared_experts`` shared experts whole, and of the routed experts
  the balanced expectation of this chip's share: tokens x ``top_k`` x
  ``experts_held`` / ``num_experts`` SwiGLU expert FFNs of width
  ``d_ff_expert`` (``experts_held`` defaults to all). A step whose
  routing is uneven does more or less than this on the experts held; the
  expectation is what the share requires on average.

So a reading computed from these can only undercount what the chip did,
up to the spread of routing around its expectation.
"""
from __future__ import annotations

from typing import Dict, List

# Bytes of a weight as served, by the file's ``param_dtype``; norm
# parameters and the router are kept in float32.
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
F32 = 4
# A routed expert is a SwiGLU FFN: gate, up and down.
EXPERT_MATS = 3


def layer_kinds(cfg: Dict) -> List[str]:
    """One kind per layer, in order: ``"dense"`` or ``"moe"``."""
    for section in ("ssm", "hybrid", "encdec"):
        if section in cfg:
            raise ValueError(f"no FLOP count for a configuration with a "
                             f"{section!r} section")
    L = cfg["num_layers"]
    if "moe" not in cfg:
        return ["dense"] * L
    fd = cfg["moe"]["first_dense_layers"]
    return ["dense"] * fd + ["moe"] * (L - fd)


def _head_dim(cfg: Dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def _mlp_mats(cfg: Dict) -> int:
    return 3 if cfg["activation"] == "swiglu" else 2


def _attention(cfg: Dict):
    """(weight multiply-adds per token, QK^T head dim, PV head dim)."""
    d, H = cfg["d_model"], cfg["num_heads"]
    m = cfg.get("mla")
    if m is None:
        hd = _head_dim(cfg)
        return d * hd * (2 * H + 2 * cfg["num_kv_heads"]), hd, hd
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    r_q, r_kv = m["q_lora_rank"], m["kv_lora_rank"]
    q = d * r_q + r_q * H * (nope + rope) if r_q else d * H * (nope + rope)
    kv = d * (r_kv + rope) + r_kv * H * (nope + v)
    return q + kv + H * v * d, nope + rope, v


def _ffn(cfg: Dict, kind: str, tokens: int) -> int:
    """Multiply-adds of one layer's MLP or expert layer over ``tokens``."""
    d, mats = cfg["d_model"], _mlp_mats(cfg)
    if kind == "dense":
        return tokens * d * cfg["d_ff"] * mats
    m = cfg["moe"]
    E, f = m["num_experts"], m["d_ff_expert"]
    held = m.get("experts_held", E)
    return (tokens * d * E                                       # router
            + tokens * d * m["num_shared_experts"] * f * mats
            + tokens * m["top_k"] * held * d * f * EXPERT_MATS // E)


def _parts(cfg: Dict, batch: int, prompt_len: int, seq: int):
    T = prompt_len + seq
    tokens = batch * T
    attn_w, qk, v = _attention(cfg)
    H = cfg["num_heads"]
    weights = attn = 0
    for kind in layer_kinds(cfg):
        weights += tokens * 2 * attn_w + 2 * _ffn(cfg, kind, tokens)
        attn += batch * H * (qk + v) * T * (T + 1) // 2 * 2   # QK^T, PV
    unembed = batch * seq * 2 * cfg["d_model"] * cfg["vocab_size"]
    return weights, attn, unembed


def forward(cfg: Dict, batch: int, prompt_len: int, seq: int) -> int:
    """Eqn-1 score of one batch: forward and loss over task positions."""
    w, a, u = _parts(cfg, batch, prompt_len, seq)
    return w + a + u


def tune_step(cfg: Dict, batch: int, prompt_len: int, seq: int) -> int:
    """One prompt-tuning step: forward plus activation-only backward."""
    w, a, u = _parts(cfg, batch, prompt_len, seq)
    return 2 * w + 3 * a + 2 * u


def weight_bytes(cfg: Dict, kind: str) -> int:
    """Bytes of one layer's weights of ``kind`` as served: what a step
    reads at least once per layer. Expert layers count the experts held."""
    d, H = cfg["d_model"], cfg["num_heads"]
    wb = ITEMSIZE[cfg["param_dtype"]]
    norm = F32 * (2 if cfg["norm"] == "layernorm" else 1)   # per channel
    attn_w, _, _ = _attention(cfg)
    size = wb * attn_w + 2 * norm * d
    if "mla" in cfg:
        m = cfg["mla"]
        size += norm * (m["kv_lora_rank"] + m["q_lora_rank"])   # their norms
    elif cfg.get("qkv_bias"):
        size += wb * _head_dim(cfg) * (H + 2 * cfg["num_kv_heads"])
    if kind == "dense":
        return size + wb * d * cfg["d_ff"] * _mlp_mats(cfg)
    m = cfg["moe"]
    E, f = m["num_experts"], m["d_ff_expert"]
    return (size + F32 * d * E
            + wb * d * f * EXPERT_MATS * m.get("experts_held", E)
            + wb * d * f * _mlp_mats(cfg) * m["num_shared_experts"])
