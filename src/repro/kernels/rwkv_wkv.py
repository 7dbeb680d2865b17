"""RWKV6 chunked WKV kernel (data-dependent decay linear attention).

The Finch recurrence per head (key dim i, value dim j):

    y_t  = r_t @ (S_{t-1} + diag(u) k_t v_t^T)
    S_t  = diag(w_t) S_{t-1} + k_t v_t^T        w_t = exp(logw_t) in (0,1]

The GPU reference implementations are sequential CUDA scans; the
TPU-native adaptation processes the sequence in chunks: the intra-chunk
token-vs-token decay matrix is materialized in VMEM (exponents <= 0 —
numerically safe), the cross-chunk state (hd x hd per head) rides in VMEM
scratch across the sequential chunk grid dimension, and all heavy ops are
MXU matmuls.

Layout: r,k,v,logw (BH, T, hd); u (BH, hd); state0 (BH, hd, hd).
``u`` enters the kernel as (BH, 1, hd) so its block (1, 1, hd) spans
the array's last two dimensions, as the TPU lowering requires.
Grid (BH, T/C): chunk index minor/sequential.

TPU sizing: hd = 64 (Finch), chunk C = 128: decay tensor (C, C, hd) f32 is
8 MB — inside VMEM; the scores/gemm ops are (C, hd)x(hd, C) and
(C, C)x(C, hd) matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
            s_ref, *, chunk):
    ic = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)                      # (C, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    logw = w_ref[0].astype(jnp.float32)                   # (C, hd), <= 0
    u = u_ref[0, 0].astype(jnp.float32)                   # (hd,)
    s = s_ref[...]                                        # (hd, hd)
    C, hd = r.shape

    c = jnp.cumsum(logw, axis=0)                          # inclusive
    b = c - logw                                          # exclusive
    # intra-chunk decay D[t, s, :] = exp(b_t - c_s) for s < t ; u at s == t
    diff = b[:, None, :] - c[None, :, :]                  # (C, C, hd)
    tt = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ss = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    lower = (tt > ss)[:, :, None]
    diag = (tt == ss)[:, :, None]
    D = jnp.where(lower, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    D = D + diag * u[None, None, :]
    score = ((r[:, None, :] * k[None, :, :]) * D).sum(-1)  # (C, C)
    y = jax.lax.dot_general(score, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk: queries attend to the carried state
    y = y + jax.lax.dot_general(r * jnp.exp(b), s, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: S' = exp(c_C) * (S + k~^T v), k~_s = k_s exp(-c_s)
    # (stable form: exp(c_C - c_s) <= 1 applied per term)
    kd = k * jnp.exp(c[-1:, :] - c)                       # (C, hd)
    s_new = jnp.exp(c[-1])[:, None] * s + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s_ref[...] = s_new

    @pl.when(ic == nc - 1)
    def _finish():
        sout_ref[0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_wkv(r, k, v, logw, u, state0, *, chunk: int = 128,
              interpret: bool = False):
    """Chunked WKV: returns (y (BH,T,hd), final state (BH,hd,hd))."""
    BH, T, hd = r.shape
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        zp = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        r, k, v = zp(r), zp(k), zp(v)
        logw = zp(logw)          # logw = 0 -> w = 1: padding is a no-op
    Tp = T + pad
    grid = (BH, Tp // chunk)
    y, sout = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tp, hd), r.dtype),
            jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u.reshape(BH, 1, hd), state0)
    return y[:, :T], sout
