"""Fused prompt-score cross-entropy kernel (the Prompt Bank hot spot).

Eqn 1 evaluates ``score(p) = mean NLL of concat(p, d_in) -> d_tgt``: a
forward pass whose final ``hidden @ E^T -> log_softmax -> gather(gold)``
dominates time and memory at LLM vocab sizes (V up to 257k here). The
naive path materializes (T, V) logits in HBM; this kernel streams vocab
tiles through VMEM with an online logsumexp, so the logits never exist.

Layout:
  hidden (T, D)   - flattened (batch*seq) token hiddens
  emb    (V, D)   - (tied) unembedding matrix
  labels (T,)     - gold token ids
  out    nll (T,) - per-token negative log-likelihood, f32

Grid (nt, nv): vocab is the minor (fastest) dimension; VMEM scratch
carries the running max ``m``, running sum ``l`` and the gold logit
across vocab tiles; the final tile writes ``log(l) + m - gold``.
Per-token vectors travel as (T, 1) columns: Mosaic tiles a 1-D block
differently from XLA, so a (bt,) block is refused on the TPU.

A vocabulary that is not a multiple of ``bv`` (GPT-2's 50257 is odd)
is zero-padded to one, and the padded columns are masked to -inf in
the last tile, so they add nothing to the logsumexp.

TPU sizing: tiles default to (bt, bv) = (256, 512); VMEM live set is
hidden tile (bt, D) + emb tile (bv, D) + logits tile (bt, bv), i.e.
~7.9 MB at D = 4096 in bf16 — under the ~16 MB v5e VMEM budget. MXU work
is the (bt, D) x (D, bv) matmul with all dims 128-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(h_ref, e_ref, lab_ref, nll_ref, m_ref, l_ref, gold_ref, *,
            vocab):
    iv = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(iv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        gold_ref[...] = jnp.zeros_like(gold_ref)

    h = h_ref[...].astype(jnp.float32)                    # (bt, D)
    e = e_ref[...].astype(jnp.float32)                    # (bv, D)
    logits = jax.lax.dot_general(
        h, e, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                     # (bt, bv)
    bt, bv = logits.shape
    v0 = iv * bv
    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, bv), 1)
    if vocab % bv:                     # mask the zero-padded vocab tail
        logits = jnp.where(v0 + cols < vocab, logits, NEG_INF)

    # online logsumexp
    m_prev = m_ref[...]                                   # (bt, 1)
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.exp(logits - m_new).sum(
        axis=-1, keepdims=True)
    m_ref[...] = m_new

    # gold logit if it falls inside this vocab tile
    local = lab_ref[...] - v0                             # (bt, 1) i32
    hit = cols == local
    gold_ref[...] = gold_ref[...] + jnp.where(hit, logits, 0.0).sum(
        axis=-1, keepdims=True)

    @pl.when(iv == nv - 1)
    def _finish():
        nll_ref[...] = (jnp.log(jnp.maximum(l_ref[...], 1e-30)) + m_ref[...]
                        - gold_ref[...])


@functools.partial(jax.jit, static_argnames=("bt", "bv", "interpret"))
def score_ce(hidden: jax.Array, emb: jax.Array, labels: jax.Array, *,
             bt: int = 256, bv: int = 512,
             interpret: bool = False) -> jax.Array:
    """Per-token NLL (T,) f32 of ``softmax(hidden @ emb.T)`` at ``labels``.

    T is zero-padded to a multiple of ``bt`` and V to a multiple of
    ``bv``; the padded vocab columns are masked inside the kernel."""
    T, D = hidden.shape
    V = emb.shape[0]
    tpad, vpad = (-T) % bt, (-V) % bv
    if tpad:
        hidden = jnp.pad(hidden, ((0, tpad), (0, 0)))
        labels = jnp.pad(labels, ((0, tpad),))
    if vpad:
        emb = jnp.pad(emb, ((0, vpad), (0, 0)))
    Tp = T + tpad
    grid = (Tp // bt, (V + vpad) // bv)
    nll = pl.pallas_call(
        functools.partial(_kernel, vocab=V),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, D), lambda it, iv: (it, 0)),
            pl.BlockSpec((bv, D), lambda it, iv: (iv, 0)),
            pl.BlockSpec((bt, 1), lambda it, iv: (it, 0)),
        ],
        out_specs=pl.BlockSpec((bt, 1), lambda it, iv: (it, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),  # running max m
            pltpu.VMEM((bt, 1), jnp.float32),  # running sum l
            pltpu.VMEM((bt, 1), jnp.float32),  # gold logit
        ],
        interpret=interpret,
    )(hidden, emb, labels.astype(jnp.int32).reshape(Tp, 1))
    return nll[:T, 0]
