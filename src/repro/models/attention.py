"""Attention families: GQA (full / sliding-window / blockwise online-softmax)
and MLA (DeepSeek-V2 multi-head latent attention, with the absorbed decode).

All functions are pure; caches are dicts of arrays. Sequence positions are
absolute (soft prompt / frontend embeddings occupy the leading positions).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models.common import (
    ParamSpec,
    apply_rope,
    maybe_model,
    norm_params,
    apply_norm,
)

NEG_INF = -1e30
_PLAIN_ATTN_MAX_KV = 4096   # use blockwise online softmax above this
_KV_BLOCK = 1024


def _flash_decode_default() -> bool:
    """Auto-gate for the Pallas decode kernels: on for real TPUs, off on
    CPU so the sim/test XLA paths (and their goldens) are untouched."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Core softmax attention (shared by GQA / MLA / cross-attention)
# ---------------------------------------------------------------------------


def _plain_attention(q, k, v, mask, scale):
    """q: (B,S,Hkv,G,hd) k,v: (B,L,Hkv,hd) mask: (B,S,L) or None."""
    scores = jnp.einsum("bshgd,blhd->bhgsl", q, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgsl,blhd->bshgd", probs, v)
    return out


def _blockwise_attention(q, k, v, q_pos, kv_pos, kv_valid, scale, causal, window):
    """Online-softmax attention, scanning KV blocks. Memory O(S * block).

    q: (B,S,Hkv,G,hd); k,v: (B,L,Hkv,hd); q_pos: (B,S); kv_pos: (B,L).
    kv_valid: (B,L) bool. Returns (B,S,Hkv,G,hd).
    """
    B, S, Hkv, G, hd = q.shape
    hd_v = v.shape[-1]              # MLA: value head dim != qk head dim
    L = k.shape[1]
    nb = -(-L // _KV_BLOCK)
    pad = nb * _KV_BLOCK - L
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)))
        kv_valid = jnp.pad(kv_valid, ((0, 0), (0, pad)))
    kb = k.reshape(B, nb, _KV_BLOCK, Hkv, hd)
    vb = v.reshape(B, nb, _KV_BLOCK, Hkv, hd_v)
    pb = kv_pos.reshape(B, nb, _KV_BLOCK)
    validb = kv_valid.reshape(B, nb, _KV_BLOCK)

    m0 = jnp.full((B, Hkv, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, S), jnp.float32)
    acc0 = jnp.zeros((B, S, Hkv, G, hd_v), jnp.float32)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, pblk, vldblk = blk
        s = jnp.einsum("bshgd,blhd->bhgsl", q, kblk).astype(jnp.float32) * scale
        ok = vldblk[:, None, :]                                   # (B,1,L)
        if causal:
            ok = ok & (pblk[:, None, :] <= q_pos[:, :, None])
        if window and window > 0:
            ok = ok & (pblk[:, None, :] > q_pos[:, :, None] - window)
        s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + pexp.sum(axis=-1)
        acc_new = acc * alpha.transpose(0, 3, 1, 2)[..., None] + jnp.einsum(
            "bhgsl,blhd->bshgd", pexp.astype(vblk.dtype), vblk
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    blks = (
        kb.transpose(1, 0, 2, 3, 4),
        vb.transpose(1, 0, 2, 3, 4),
        pb.transpose(1, 0, 2),
        validb.transpose(1, 0, 2),
    )
    # flash-attention memory behaviour in the backward pass too: recompute
    # per-block scores instead of saving every (B,H,G,S,block) tensor
    body = jax.checkpoint(body, prevent_cse=False)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), blks)
    denom = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return (acc / denom).astype(q.dtype)


def scaled_attention(
    q, k, v, *, q_pos, kv_pos, kv_valid=None, causal=True, window=0, scale=None
):
    """Dispatcher: plain masked attention for short KV, blockwise otherwise."""
    B, S, Hkv, G, hd = q.shape
    L = k.shape[1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    if kv_valid is None:
        kv_valid = jnp.ones((B, L), bool)
    if L <= _PLAIN_ATTN_MAX_KV:
        mask = kv_valid[:, None, :]
        if causal:
            mask = mask & (kv_pos[:, None, :] <= q_pos[:, :, None])
        if window and window > 0:
            mask = mask & (kv_pos[:, None, :] > q_pos[:, :, None] - window)
        return _plain_attention(q, k, v, mask, scale)
    return _blockwise_attention(q, k, v, q_pos, kv_pos, kv_valid, scale, causal, window)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_params(cfg: ModelConfig, model_axis: int) -> Dict:
    hd = cfg.resolved_head_dim()
    H, Hkv = cfg.num_heads, cfg.kv_heads()
    mh = maybe_model(H, model_axis)
    mkv = maybe_model(Hkv, model_axis)
    p = {
        "wq": ParamSpec((cfg.d_model, H, hd), P(None, mh, None)),
        "wk": ParamSpec((cfg.d_model, Hkv, hd), P(None, mkv, None)),
        "wv": ParamSpec((cfg.d_model, Hkv, hd), P(None, mkv, None)),
        "wo": ParamSpec((H, hd, cfg.d_model), P(mh, None, None)),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamSpec((H, hd), P(mh, None), "zeros")
        p["bk"] = ParamSpec((Hkv, hd), P(mkv, None), "zeros")
        p["bv"] = ParamSpec((Hkv, hd), P(mkv, None), "zeros")
    return p


def _qkv(cfg: ModelConfig, p, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    # names a checkpoint policy may keep (``Model.saving``)
    q = checkpoint_name(apply_rope(q, positions, cfg.rope_theta), "q")
    k = checkpoint_name(apply_rope(k, positions, cfg.rope_theta), "k")
    return q, k, checkpoint_name(v, "v")


def gqa_forward(
    cfg: ModelConfig,
    p: Dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    return_kv: bool = False,
):
    """Full-sequence attention (training / prefill)."""
    B, S, _ = x.shape
    H, Hkv = cfg.num_heads, cfg.kv_heads()
    hd = cfg.resolved_head_dim()
    G = H // Hkv
    q, k, v = _qkv(cfg, p, x, positions)
    qg = q.reshape(B, S, Hkv, G, hd)
    w = cfg.sliding_window if window is None else window
    out = scaled_attention(
        qg, k, v, q_pos=positions, kv_pos=positions, causal=causal, window=w
    )
    y = jnp.einsum("bshgd,hgdk->bsk", out.reshape(B, S, Hkv, G, hd),
                   p["wo"].reshape(Hkv, G, hd, cfg.d_model))
    if return_kv:
        return y, (k, v)
    return y


def gqa_init_cache(cfg: ModelConfig, batch: int, length: int, dtype) -> Dict:
    Hkv, hd = cfg.kv_heads(), cfg.resolved_head_dim()
    return {
        "k": jnp.zeros((batch, length, Hkv, hd), dtype),
        "v": jnp.zeros((batch, length, Hkv, hd), dtype),
        "pos": jnp.full((batch, length), -1, jnp.int32),
    }


def gqa_decode(
    cfg: ModelConfig,
    p: Dict,
    x: jax.Array,                 # (B, 1, d_model)
    cache: Dict,
    cache_len: jax.Array,         # scalar int32: tokens already in cache
    *,
    window: Optional[int] = None,
    use_flash: Optional[bool] = None,
) -> Tuple[jax.Array, Dict]:
    """One decode step against a (possibly ring-buffered) KV cache.

    The cache stores roped keys with absolute positions in ``pos``
    (-1 = empty). With a sliding window the buffer length equals the
    window and insertion wraps.

    ``use_flash`` routes the attention through the split-KV Pallas
    kernel (``repro.kernels.flash_decode``). Valid only while the cache
    is a contiguous prefix (no ring wrap: cache_len < buffer length),
    which holds whenever the buffer is sized to max_seq_len — so the
    auto default enables it on TPU for the unwindowed path only.
    """
    B = x.shape[0]
    H, Hkv, hd = cfg.num_heads, cfg.kv_heads(), cfg.resolved_head_dim()
    G = H // Hkv
    L = cache["k"].shape[1]
    positions = jnp.broadcast_to(cache_len[None], (B,))[:, None]   # (B,1)
    q, k, v = _qkv(cfg, p, x, positions)
    slot = (cache_len % L).astype(jnp.int32)
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    pos_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], positions.astype(jnp.int32), slot, axis=1
    )
    valid = pos_cache >= 0
    w = cfg.sliding_window if window is None else window
    if use_flash is None:
        use_flash = _flash_decode_default() and not w
    if use_flash:
        from repro.kernels.ops import gqa_flash_decode

        out = gqa_flash_decode(
            q, k_cache, v_cache, kv_len=cache_len + 1, q_pos=cache_len,
            window=w or 0,
        ).reshape(B, 1, Hkv, G, hd)
    else:
        qg = q.reshape(B, 1, Hkv, G, hd)
        out = scaled_attention(
            qg, k_cache, v_cache,
            q_pos=positions, kv_pos=pos_cache, kv_valid=valid, causal=True,
            window=w,
        )
    y = jnp.einsum("bshgd,hgdk->bsk", out,
                   p["wo"].reshape(Hkv, G, hd, cfg.d_model))
    return y, {"k": k_cache, "v": v_cache, "pos": pos_cache}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_params(cfg: ModelConfig, model_axis: int) -> Dict:
    m = cfg.mla
    H = cfg.num_heads
    mh = maybe_model(H, model_axis)
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    p = {
        "wkv_a": ParamSpec((cfg.d_model, m.kv_lora_rank + m.qk_rope_head_dim), P(None, None)),
        "kv_norm": norm_params(cfg, m.kv_lora_rank),
        "wkv_b_k": ParamSpec((m.kv_lora_rank, H, m.qk_nope_head_dim), P(None, mh, None)),
        "wkv_b_v": ParamSpec((m.kv_lora_rank, H, m.v_head_dim), P(None, mh, None)),
        "wo": ParamSpec((H, m.v_head_dim, cfg.d_model), P(mh, None, None)),
    }
    if m.q_lora_rank > 0:
        p["wq_a"] = ParamSpec((cfg.d_model, m.q_lora_rank), P(None, None))
        p["q_norm"] = norm_params(cfg, m.q_lora_rank)
        p["wq_b"] = ParamSpec((m.q_lora_rank, H, qk), P(None, mh, None))
    else:
        p["wq"] = ParamSpec((cfg.d_model, H, qk), P(None, mh, None))
    return p


def _mla_q(cfg: ModelConfig, p, x, positions):
    m = cfg.mla
    if m.q_lora_rank > 0:
        cq = apply_norm(cfg, p["q_norm"], x @ p["wq_a"])
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim :], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(cfg: ModelConfig, p, x, positions):
    m = cfg.mla
    kv = x @ p["wkv_a"]
    c_kv = apply_norm(cfg, p["kv_norm"], kv[..., : m.kv_lora_rank])
    k_rope = kv[..., m.kv_lora_rank :][:, :, None, :]              # (B,S,1,rd)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_rope


def mla_forward(cfg: ModelConfig, p, x, positions, *, causal=True):
    """Training/prefill MLA. Decompresses K/V per head (standard form)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    c_kv, k_rope = _mla_latent(cfg, p, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b_k"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b_v"])
    scale = 1.0 / jnp.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    # combine nope + rope score parts by concatenating feature dims
    q_full = jnp.concatenate(
        [q_nope, q_rope], axis=-1
    )                                                              # (B,S,H,qk)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, m.qk_rope_head_dim))],
        axis=-1,
    )
    qg = q_full[:, :, :, None, :]                                  # G=1 over H kv-heads
    out = scaled_attention(
        qg, k_full, v, q_pos=positions, kv_pos=positions, causal=causal,
        window=cfg.sliding_window, scale=scale,
    )[:, :, :, 0, :]
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"])


def mla_init_cache(cfg: ModelConfig, batch: int, length: int, dtype) -> Dict:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, length, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, length, m.qk_rope_head_dim), dtype),
        "pos": jnp.full((batch, length), -1, jnp.int32),
    }


def mla_decode(cfg: ModelConfig, p, x, cache, cache_len, *,
               use_flash: Optional[bool] = None):
    """Absorbed MLA decode: attention runs in the latent space, so the cache
    is only (L, kv_lora + rope_dim) — O(L) memory, the property that lets
    deepseek-v2 run long_500k without a sliding window.

    ``use_flash`` routes the latent attention through the split-KV
    Pallas kernel (``repro.kernels.mla_decode``); same contiguous-prefix
    requirement as ``gqa_decode`` (the MLA cache never windows, so any
    buffer sized to max_seq_len qualifies)."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    L = cache["c_kv"].shape[1]
    positions = jnp.broadcast_to(cache_len[None], (B,))[:, None]
    q_nope, q_rope = _mla_q(cfg, p, x, positions)                  # (B,1,H,*)
    c_new, kr_new = _mla_latent(cfg, p, x, positions)
    slot = (cache_len % L).astype(jnp.int32)
    c_kv = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_new, slot, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(cache["k_rope"], kr_new, slot, axis=1)
    pos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], positions.astype(jnp.int32), slot, axis=1
    )
    # absorb wkv_b_k into the query: q_lat (B,1,H,kv_lora)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wkv_b_k"])
    scale = 1.0 / (m.qk_nope_head_dim + m.qk_rope_head_dim) ** 0.5
    if use_flash is None:
        use_flash = _flash_decode_default()
    if use_flash:
        from repro.kernels.ops import mla_flash_decode

        out_lat = mla_flash_decode(
            q_lat, q_rope, c_kv, k_rope, scale=scale,
            kv_len=cache_len + 1, q_pos=cache_len,
        )                                                          # (B,1,H,r)
    else:
        scores = (
            jnp.einsum("bshr,blr->bhsl", q_lat, c_kv)
            + jnp.einsum("bshk,blk->bhsl", q_rope, k_rope)
        ).astype(jnp.float32)
        valid = (pos >= 0) & (pos <= positions[:, :1])             # (B, L)
        scores = jnp.where(valid[:, None, None, :], scores * scale, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(c_kv.dtype)
        out_lat = jnp.einsum("bhsl,blr->bshr", probs, c_kv)        # (B,1,H,r)
    out = jnp.einsum("bshr,rhv->bshv", out_lat, p["wkv_b_v"])
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    return y, {"c_kv": c_kv, "k_rope": k_rope, "pos": pos}


# ---------------------------------------------------------------------------
# Cross attention (encoder-decoder)
# ---------------------------------------------------------------------------


def cross_attention_params(cfg: ModelConfig, model_axis: int) -> Dict:
    return gqa_params(cfg, model_axis)


def cross_attention(cfg: ModelConfig, p, x, enc_kv, enc_valid=None):
    """x: (B,S,d); enc_kv: (k, v) each (B,Lenc,Hkv,hd) precomputed."""
    B, S, _ = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.kv_heads(), cfg.resolved_head_dim()
    G = H // Hkv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    k, v = enc_kv
    qg = q.reshape(B, S, Hkv, G, hd)
    q_pos = jnp.zeros((B, S), jnp.int32)
    kv_pos = jnp.zeros((B, k.shape[1]), jnp.int32)
    out = scaled_attention(
        qg, k, v, q_pos=q_pos, kv_pos=kv_pos, kv_valid=enc_valid,
        causal=False, window=0,
    )
    return jnp.einsum("bshgd,hgdk->bsk", out,
                      p["wo"].reshape(Hkv, G, hd, cfg.d_model))


def encode_cross_kv(cfg: ModelConfig, p, enc_out):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    return k, v
