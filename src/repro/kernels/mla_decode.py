"""MLA latent decode kernel (DeepSeek-V2/V3 matrix-absorbed attention).

With the matrix-absorption trick (W_UK folded into the query, W_UV into
the output projection — pie's ``DsmaAttention`` convention) decode
attention runs entirely in the compressed latent space:

    scores = q_lat @ ckv^T + q_pe @ kpe^T        (nope + rope parts)
    out    = softmax(scores) @ ckv               (ckv doubles as V)

so the per-step HBM floor is ONE read of the latent cache
``(L, r + rd)`` — not the H-times-larger decompressed K/V. The score
matrix is the only O(H * L) object and it never leaves VMEM.

Layout (all H query heads share the single latent KV "head"):
  q_lat (B, H, r)   q_pe (B, H, rd)   ckv (B, L, r)   kpe (B, L, rd)
with r = kv_lora_rank (512 for deepseek-v2/kimi-k2) and
rd = qk_rope_head_dim (64). H itself forms the MXU rows (128 heads on
deepseek-v2 — a full systolic tile per score matmul).

Grid (B, splits, nk): split-KV exactly like ``flash_decode`` — each
partition keeps (m, l, acc) VMEM scratch across its ``nk`` KV tiles and
emits an l-normalized partial plus its LSE; partials merge with the
shared ``combine_partials`` rescale (exact).

Masking is dynamic (SMEM): column j live iff j < kv_len and j <= q_pos.
``scale`` is static: 1/sqrt(qk_nope_head_dim + qk_rope_head_dim) — the
*pre-absorption* head dim, NOT the latent rank.

TPU sizing: bk = 256 tiles: ckv tile (256, 512) f32 + kpe (256, 64)
+ scores (H', 256) + acc (H', 512) ~= 1.1 MB at H' = 128 — VMEM-light,
so wide splits keep every core busy on long caches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode import NEG_INF, combine_partials


def _kernel(meta_ref, ql_ref, qp_ref, ckv_ref, kpe_ref, o_ref, lse_ref,
            m_ref, l_ref, acc_ref, *, scale, bk):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    isplit = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ql = ql_ref[0].astype(jnp.float32)                    # (H', r)
    qp = qp_ref[0].astype(jnp.float32)                    # (H', rd)
    ckv = ckv_ref[0].astype(jnp.float32)                  # (bk, r)
    kpe = kpe_ref[0].astype(jnp.float32)                  # (bk, rd)
    s = (jax.lax.dot_general(ql, ckv, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qp, kpe, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)) * scale

    kv_len, q_pos = meta_ref[0], meta_ref[1]
    nh = s.shape[0]
    kpos = (isplit * nk + ik) * bk + jax.lax.broadcasted_iota(
        jnp.int32, (nh, bk), 1)
    ok = (kpos < kv_len) & (kpos <= q_pos)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                   # (H', 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)          # fully-masked tile: exp(0) guard
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, ckv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        denom = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(l > 0.0, m_ref[...] + jnp.log(denom),
                                  NEG_INF)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "splits", "bk", "interpret"),
)
def mla_decode(q_lat: jax.Array, q_pe: jax.Array, ckv: jax.Array,
               kpe: jax.Array, *, scale: float, kv_len=None, q_pos=None,
               splits: int = 8, bk: int = 256,
               interpret: bool = False) -> jax.Array:
    """q_lat: (B,H,r); q_pe: (B,H,rd); ckv: (B,L,r); kpe: (B,L,rd)
    -> (B,H,r) latent attention output (decompress with W_UV outside).

    ``kv_len`` / ``q_pos`` are dynamic scalars with the same contiguous-
    prefix convention as ``flash_decode``."""
    B, H, r = q_lat.shape
    rd = q_pe.shape[-1]
    L = ckv.shape[1]

    nh = max(8, -(-H // 8) * 8)                           # f32 sublane pad
    if nh != H:
        q_lat = jnp.pad(q_lat, ((0, 0), (0, nh - H), (0, 0)))
        q_pe = jnp.pad(q_pe, ((0, 0), (0, nh - H), (0, 0)))

    bk = min(bk, max(128, -(-L // 128) * 128))
    nsplit = min(splits, -(-L // bk))
    per = nsplit * bk
    Lp = -(-L // per) * per
    if Lp != L:
        ckv = jnp.pad(ckv, ((0, 0), (0, Lp - L), (0, 0)))
        kpe = jnp.pad(kpe, ((0, 0), (0, Lp - L), (0, 0)))
    nk = Lp // per

    if kv_len is None:
        kv_len = L
    kv_len = jnp.asarray(kv_len, jnp.int32)
    if q_pos is None:
        q_pos = kv_len - 1
    meta = jnp.stack([kv_len, jnp.asarray(q_pos, jnp.int32)])

    grid = (B, nsplit, nk)
    o_part, lse = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # meta (2,)
            pl.BlockSpec((1, nh, r), lambda b, s, j: (b, 0, 0)),
            pl.BlockSpec((1, nh, rd), lambda b, s, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, r),
                         lambda b, s, j, nk=nk: (b, s * nk + j, 0)),
            pl.BlockSpec((1, bk, rd),
                         lambda b, s, j, nk=nk: (b, s * nk + j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, nh, r), lambda b, s, j: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, nh, 1), lambda b, s, j: (b, s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nsplit, nh, r), jnp.float32),
            jax.ShapeDtypeStruct((B, nsplit, nh, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nh, 1), jnp.float32),             # running max
            pltpu.VMEM((nh, 1), jnp.float32),             # running sum
            pltpu.VMEM((nh, r), jnp.float32),             # latent accumulator
        ],
        interpret=interpret,
    )(meta, q_lat, q_pe, ckv, kpe)
    out = combine_partials(o_part, lse[..., 0], axis=1)   # (B, nh, r)
    return out[:, :H].astype(q_lat.dtype)
