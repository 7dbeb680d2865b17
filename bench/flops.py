"""Floating-point operations the work requires, from a configuration's
shapes (one multiply-add counts 2).

Required, not executed:
- the forward counts every position (prompt and task) through every
  layer -- projections, the causal half of the attention products, the
  MLP -- and the unembedding only over the S task positions, whose
  logits the loss reads;
- the tuning step adds an activation-only backward (the weights are
  frozen): one product per weight product for dX, two per attention
  product (dQ/dK, dP/dV), one for the unembedding;
- norms, softmax and other elementwise work are left out, as are
  rematerialised recomputation and prompt-position logits.

So a reading computed from these can only undercount what the chip did.
"""
from __future__ import annotations

from typing import Dict


def _parts(cfg: Dict, batch: int, prompt_len: int, seq: int):
    d, H, Hkv = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    hd = d // H
    T = prompt_len + seq
    tokens = batch * T
    mlp_mats = 3 if cfg["activation"] == "swiglu" else 2
    weights = tokens * 2 * d * hd * (2 * H + 2 * Hkv)      # q, k, v, o
    weights += tokens * 2 * d * cfg["d_ff"] * mlp_mats
    attn = batch * H * hd * T * (T + 1) // 2 * 2 * 2       # QK^T and PV
    unembed = batch * seq * 2 * d * cfg["vocab_size"]
    L = cfg["num_layers"]
    return L * weights, L * attn, unembed


def forward(cfg: Dict, batch: int, prompt_len: int, seq: int) -> int:
    """Eqn-1 score of one batch: forward and loss over task positions."""
    w, a, u = _parts(cfg, batch, prompt_len, seq)
    return w + a + u


def tune_step(cfg: Dict, batch: int, prompt_len: int, seq: int) -> int:
    """One prompt-tuning step: forward plus activation-only backward."""
    w, a, u = _parts(cfg, batch, prompt_len, seq)
    return 2 * w + 3 * a + 2 * u
