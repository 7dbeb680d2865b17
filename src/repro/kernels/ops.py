"""Jit'd wrappers around the Pallas kernels, in MODEL layouts.

On the TPU the kernels compile to Mosaic; on the CPU backend they run
with ``interpret=True``. Any other backend raises: there is no silent
fallback. The mode is resolved from the default backend at trace time,
so callers never pass it explicitly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.mla_decode import mla_decode
from repro.kernels.rwkv_wkv import rwkv6_wkv
from repro.kernels.score_ce import score_ce

MAX_HEAD_DIM = 256   # VMEM tiling budget of the flash kernels


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}")


def fused_score_ce(hidden, emb, labels, mask, *, bt: int = 256,
                   bv: int = 512):
    """Eqn-1 scoring in model layout: hidden (B,S,d), labels/mask (B,S).

    Returns (mean_loss, per_example (B,)). A vocabulary that is not a
    multiple of ``bv`` is padded and masked inside ``score_ce``; a vocab
    smaller than one tile shrinks the tile to V rounded up to 128 lanes."""
    B, S, d = hidden.shape
    bv = min(bv, -(-emb.shape[0] // 128) * 128)
    nll = score_ce(hidden.reshape(B * S, d), emb, labels.reshape(-1),
                   bt=bt, bv=bv, interpret=_interpret())
    nll = nll.reshape(B, S) * mask
    tok = jnp.maximum(mask.sum(axis=-1), 1.0)
    per_ex = nll.sum(axis=-1) / tok
    mean = nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return mean, per_ex


def gqa_flash(q, k, v, *, causal=True, window=0, q_offset=0, kv_len=None,
              bq: int = 512, bk: int = 512):
    """Model layout adapter: q (B,S,H,hd), k/v (B,L,Hkv,hd) ->
    (B,S,H,hd).

    Ergonomics the raw kernel doesn't provide: head dims over the VMEM
    tiling budget raise here (instead of a Mosaic shape error deep in
    the Pallas call), and a KV length that is not a lane multiple of 128
    is zero-padded with ``kv_len`` masking the tail — the kernel then
    always sees 128-aligned tiles."""
    hd = q.shape[-1]
    if hd > MAX_HEAD_DIM:
        raise ValueError(
            f"gqa_flash: head_dim={hd} exceeds the flash kernel's VMEM "
            f"tiling budget ({MAX_HEAD_DIM}); use "
            "repro.models.attention.scaled_attention for this shape")
    L = k.shape[1]
    pad = (-L) % 128
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # mask the padded tail; honor a tighter caller-supplied kv_len
        kv_len = L if kv_len is None else jnp.minimum(
            jnp.asarray(kv_len, jnp.int32), L)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention(qt, kt, vt, causal=causal, window=window,
                          q_offset=q_offset, kv_len=kv_len, bq=bq, bk=bk,
                          interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


def gqa_flash_decode(q, k, v, *, kv_len=None, q_pos=None, window=0,
                     splits: int = 8, bk: int = 256):
    """Single-token decode adapter: q (B,1,H,hd) or (B,H,hd),
    k/v (B,L,Hkv,hd) -> same rank as q.

    ``kv_len`` / ``q_pos`` are dynamic scalars (contiguous-prefix cache
    convention; see ``flash_decode``)."""
    squeeze = q.ndim == 4
    if squeeze:
        assert q.shape[1] == 1, "decode takes exactly one query token"
        q = q[:, 0]
    if q.shape[-1] > MAX_HEAD_DIM:
        raise ValueError(
            f"gqa_flash_decode: head_dim={q.shape[-1]} exceeds the flash "
            f"kernel's VMEM tiling budget ({MAX_HEAD_DIM})")
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_decode(q, kt, vt, kv_len=kv_len, q_pos=q_pos, window=window,
                       splits=splits, bk=bk, interpret=_interpret())
    return out[:, None] if squeeze else out


def mla_flash_decode(q_lat, q_pe, ckv, kpe, *, scale, kv_len=None,
                     q_pos=None, splits: int = 8, bk: int = 256):
    """Absorbed-MLA decode adapter: q_lat (B,1,H,r) or (B,H,r), q_pe
    likewise, ckv (B,L,r), kpe (B,L,rd) -> latent output, rank of q_lat.

    ``scale`` is 1/sqrt(qk_nope_head_dim + qk_rope_head_dim) — the
    pre-absorption head dim."""
    squeeze = q_lat.ndim == 4
    if squeeze:
        assert q_lat.shape[1] == 1, "decode takes exactly one query token"
        q_lat, q_pe = q_lat[:, 0], q_pe[:, 0]
    out = mla_decode(q_lat, q_pe, ckv, kpe, scale=float(scale),
                     kv_len=kv_len, q_pos=q_pos, splits=splits, bk=bk,
                     interpret=_interpret())
    return out[:, None] if squeeze else out


def wkv(r, k, v, logw, u, state, *, chunk: int = 128):
    """Model layout adapter: r/k/v/logw (B,H,T,hd), u (H,hd),
    state (B,H,hd,hd) -> (y (B,H,T,hd), state')."""
    B, H, T, hd = r.shape
    fl = lambda t: t.reshape(B * H, T, hd)
    uu = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, hd)
    s0 = state.reshape(B * H, hd, hd)
    y, s = rwkv6_wkv(fl(r), fl(k), fl(v), fl(logw), uu, s0, chunk=chunk,
                     interpret=_interpret())
    return y.reshape(B, H, T, hd), s.reshape(B, H, hd, hd)
