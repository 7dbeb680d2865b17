"""Split-KV flash decode (GQA single-token inference attention).

The serving-side hot loop is one query token against a long KV cache.
A plain flash grid gives that token ONE grid cell per (batch, head) —
on a 128k cache that is a single sequential pass over HBM with no
parallelism across cores. Flash-decoding fixes this by partitioning the
KV cache across grid cells: every partition keeps its own online-softmax
state ``(m, l, acc)`` while streaming its KV tiles through VMEM, then
emits a *normalized partial output* plus its log-sum-exp. The partials
are merged with the standard LSE rescale/combine reduction
(AttentionEngine's ``combine``: ``o_scale = exp(lse_i - logsumexp_i
lse_i)``), which is exact — no approximation anywhere.

Layout (GQA group packed into MXU rows so S=1 still feeds a matmul):
  q (B, Hkv, G, hd)     k,v (B, Hkv, L, hd)      G = H // Hkv
Grid (B, Hkv, splits, nk): the inner KV-tile index is minor; VMEM
scratch carries (m, l, acc) across the ``nk`` tiles of one partition.

Masking is positional and dynamic (SMEM): KV column j is live iff
  j <  kv_len                 (valid cache prefix)
  j <= q_pos                  (causal; q_pos defaults to kv_len - 1)
  j >  q_pos - window         (sliding window, if window > 0)

Outputs per partition: o_part (B, Hkv, splits, G, hd) normalized by the
partition's own ``l``, and lse (B, Hkv, splits, G); empty partitions
(fully masked) emit lse = -inf so their combine weight is exactly 0.

TPU sizing: tiles default to bk = 256, G padded to a multiple of 8
(f32 sublane): live set k/v (256, hd) + scores (G', 256) + acc (G', hd)
~= 0.6 MB at hd = 128 bf16 — tiny, so ``splits`` can go wide and the
kernel stays HBM-bound at ~2*L*hd*Hkv bytes per (batch, kv-head), the
roofline floor for reading the cache once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
            acc_ref, *, scale, window, bk):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    isplit = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # (G', hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                             # (G', bk)

    kv_len, q_pos = meta_ref[0], meta_ref[1]
    gq = q.shape[0]
    kpos = (isplit * nk + ik) * bk + jax.lax.broadcasted_iota(
        jnp.int32, (gq, bk), 1)
    ok = (kpos < kv_len) & (kpos <= q_pos)
    if window and window > 0:
        ok &= kpos > q_pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                   # (G', 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)          # exp(NEG_INF - NEG_INF) = 1 guard
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        denom = jnp.maximum(l, 1e-30)
        o_ref[0, 0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = jnp.where(l > 0.0, m_ref[...] + jnp.log(denom),
                                     NEG_INF)


def combine_partials(o_part: jax.Array, lse: jax.Array,
                     axis: int = 2) -> jax.Array:
    """LSE rescale/combine across split-KV partitions (exact).

    o_part: (..., splits, ..., hd) partials each normalized by their own
    softmax sum; lse: matching shape without the trailing hd. Weights are
    ``exp(lse_i - max_i lse_i)`` renormalized — an all-empty row (every
    lse = -inf) combines to exactly 0.
    """
    m = lse.max(axis=axis, keepdims=True)
    w = jnp.exp(lse - jnp.maximum(m, NEG_INF))            # (..., splits, ...)
    w = jnp.where(lse > NEG_INF / 2, w, 0.0)
    denom = jnp.maximum(w.sum(axis=axis, keepdims=True), 1e-30)
    return ((o_part * w[..., None]).sum(axis=axis) /
            denom[..., None].squeeze(axis)).astype(o_part.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "splits", "bk", "interpret"),
)
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 kv_len=None, q_pos=None, window: int = 0,
                 splits: int = 8, bk: int = 256,
                 interpret: bool = False) -> jax.Array:
    """q: (B,H,hd); k,v: (B,Hkv,L,hd) -> (B,H,hd).

    ``kv_len``: dynamic valid-cache length (defaults to L); ``q_pos``:
    dynamic absolute position of the query token (defaults to
    ``kv_len - 1``, i.e. the token attends to the whole valid prefix
    including itself). Both are scalars shared across the batch, the
    contiguous-prefix convention of ``gqa_init_cache``.
    """
    B, H, hd = q.shape
    Hkv, L = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    G = H // Hkv

    # pack the GQA group into MXU rows, padded to the f32 sublane count
    gq = max(8, -(-G // 8) * 8)
    qg = q.reshape(B, Hkv, G, hd)
    if gq != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gq - G), (0, 0)))

    bk = min(bk, max(128, -(-L // 128) * 128))
    nsplit = min(splits, -(-L // bk))
    per = nsplit * bk
    Lp = -(-L // per) * per
    if Lp != L:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Lp - L), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Lp - L), (0, 0)))
    nk = Lp // per

    if kv_len is None:
        kv_len = L
    kv_len = jnp.asarray(kv_len, jnp.int32)
    if q_pos is None:
        q_pos = kv_len - 1
    meta = jnp.stack([kv_len, jnp.asarray(q_pos, jnp.int32)])

    grid = (B, Hkv, nsplit, nk)
    o_part, lse = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / (hd ** 0.5), window=window,
                          bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # meta (2,)
            pl.BlockSpec((1, 1, gq, hd), lambda b, h, s, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, s, j, nk=nk: (b, h, s * nk + j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, s, j, nk=nk: (b, h, s * nk + j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, gq, hd), lambda b, h, s, j: (b, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, gq, 1),
                         lambda b, h, s, j: (b, h, s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, nsplit, gq, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, nsplit, gq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((gq, 1), jnp.float32),             # running max
            pltpu.VMEM((gq, 1), jnp.float32),             # running sum
            pltpu.VMEM((gq, hd), jnp.float32),            # accumulator
        ],
        interpret=interpret,
    )(meta, qg, k, v)
    out = combine_partials(o_part, lse[..., 0], axis=2)   # (B, Hkv, gq, hd)
    return out[:, :, :G].reshape(B, H, hd).astype(q.dtype)
