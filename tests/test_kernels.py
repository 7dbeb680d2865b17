"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret=True on CPU per the brief)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.mla_decode import mla_decode
from repro.kernels.ops import (
    fused_score_ce,
    gqa_flash,
    gqa_flash_decode,
    mla_flash_decode,
    wkv,
)
from repro.kernels.ref import (
    flash_attention_ref,
    flash_decode_ref,
    mla_decode_ref,
    rwkv6_wkv_ref,
    score_ce_ref,
)
from repro.kernels.rwkv_wkv import rwkv6_wkv
from repro.kernels.score_ce import score_ce


# -- score_ce ----------------------------------------------------------------

@pytest.mark.parametrize("T,D,V,bt,bv", [
    (64, 64, 512, 32, 128),
    (100, 128, 1024, 32, 256),       # T not a tile multiple
    (17, 32, 256, 16, 256),          # single vocab tile
    (256, 256, 2048, 128, 512),
    (40, 32, 300, 8, 128),           # V not a tile multiple: masked tail
    (24, 64, 517, 8, 256),           # odd V, like GPT-2's 50257
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_score_ce_sweep(T, D, V, bt, bv, dtype):
    key = jax.random.key(T + D)
    h = jax.random.normal(key, (T, D), dtype)
    e = (jax.random.normal(jax.random.fold_in(key, 1), (V, D)) * 0.05).astype(dtype)
    lab = jax.random.randint(jax.random.fold_in(key, 2), (T,), 0, V)
    out = score_ce(h, e, lab, bt=bt, bv=bv, interpret=True)
    ref = score_ce_ref(h.astype(jnp.float32), e.astype(jnp.float32), lab)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_fused_score_ce_matches_naive(pre_base):
    """Model-layout wrapper vs the framework's naive CE on real data."""
    from repro.data import LoaderConfig, TaskLoader, batch_to_jnp
    from repro.models.common import unembed
    from repro.train.objectives import token_cross_entropy

    pre = pre_base
    loader = TaskLoader(pre.tasks[3], LoaderConfig(batch_size=4))
    b = batch_to_jnp(next(loader))
    hidden, _ = pre.model.backbone(pre.params, b["tokens"])
    mean, per = fused_score_ce(hidden, pre.params["embedding"],
                               b["labels"], b["mask"])
    logits = unembed(pre.model.cfg, pre.params, hidden)
    m2, p2 = token_cross_entropy(logits, b["labels"], b["mask"])
    np.testing.assert_allclose(float(mean), float(m2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(per), np.asarray(p2), rtol=1e-5)


def test_fused_score_ce_odd_vocab_keeps_the_tile(monkeypatch):
    """An odd vocabulary is padded and masked, never turned into one
    whole-vocabulary tile."""
    from repro.kernels import ops
    from repro.train.objectives import token_cross_entropy

    tiles = []

    def spy(*args, **kw):
        tiles.append(kw["bv"])
        return score_ce(*args, **kw)

    monkeypatch.setattr(ops, "score_ce", spy)
    key = jax.random.key(21)
    B, S, d, V = 2, 12, 32, 1031
    hidden = jax.random.normal(key, (B, S, d))
    emb = jax.random.normal(jax.random.fold_in(key, 1), (V, d)) * 0.1
    labels = jax.random.randint(jax.random.fold_in(key, 2), (B, S), 0, V)
    labels = labels.at[0, 0].set(V - 1)          # gold in the last tile
    mask = jnp.ones((B, S)).at[1, :3].set(0.0)
    mean, per = ops.fused_score_ce(hidden, emb, labels, mask, bv=256)
    assert tiles == [256]
    m2, p2 = token_cross_entropy(hidden @ emb.T, labels, mask)
    np.testing.assert_allclose(float(mean), float(m2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(per), np.asarray(p2), rtol=1e-5)


@pytest.mark.parametrize("backend,expected", [("cpu", True), ("tpu", False)])
def test_kernels_interpret_only_on_cpu(monkeypatch, backend, expected):
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    assert ops._interpret() is expected


def test_kernels_refuse_other_backends(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops._interpret()


# -- flash attention -----------------------------------------------------------

@pytest.mark.parametrize("B,H,Hkv,S,L,hd,bq,bk", [
    (1, 2, 1, 16, 16, 32, 8, 8),
    (2, 4, 2, 48, 80, 32, 16, 32),
    (1, 8, 1, 33, 130, 64, 16, 64),    # MQA + ragged tiles
    (2, 2, 2, 64, 64, 16, 64, 64),     # single tile
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, Hkv, S, L, hd, bq, bk, dtype):
    key = jax.random.key(B * H + S)
    q = jax.random.normal(key, (B, H, S, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, L, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, L, hd), dtype)
    off = max(L - S, 0)
    out = flash_attention(q, k, v, causal=True, q_offset=off,
                          bq=bq, bk=bk, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, q_offset=off)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [8, 24])
def test_flash_sliding_window(window):
    key = jax.random.key(7)
    q = jax.random.normal(key, (1, 2, 32, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 64, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 64, 16))
    out = flash_attention(q, k, v, causal=True, window=window, q_offset=32,
                          bq=16, bk=16, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=window,
                              q_offset=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_dynamic_kv_len():
    key = jax.random.key(9)
    q = jax.random.normal(key, (1, 2, 8, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 64, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 64, 16))
    for kv_len in (8, 33, 64):
        out = flash_attention(q, k, v, causal=False, kv_len=kv_len,
                              bq=8, bk=16, interpret=True)
        ref = flash_attention_ref(q, k, v, causal=False, kv_len=kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_gqa_flash_model_layout_matches_model_attention():
    """ops.gqa_flash must agree with the XLA attention the models use."""
    from repro.models.attention import scaled_attention

    key = jax.random.key(3)
    B, S, H, Hkv, hd = 2, 24, 4, 2, 32
    q = jax.random.normal(key, (B, S, Hkv, H // Hkv, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, hd))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ref = scaled_attention(q, k, v, q_pos=pos, kv_pos=pos, causal=True)
    qm = q.reshape(B, S, H, hd)
    out = gqa_flash(qm, k, v, causal=True, bq=8, bk=8)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.reshape(B, S, H, hd)),
        rtol=2e-5, atol=2e-5)


# -- gqa_flash ergonomics ----------------------------------------------------

def test_gqa_flash_rejects_oversized_head_dim():
    """hd > 256 must raise a clear ValueError, not a Mosaic shape error
    from inside the Pallas call."""
    q = jnp.zeros((1, 8, 2, 512))
    k = v = jnp.zeros((1, 8, 2, 512))
    with pytest.raises(ValueError, match="head_dim=512"):
        gqa_flash(q, k, v)
    with pytest.raises(ValueError, match="head_dim=512"):
        gqa_flash_decode(jnp.zeros((1, 2, 512)), k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3))


@pytest.mark.parametrize("L", [130, 200, 100])
def test_gqa_flash_pads_non_128_multiple_kv(L):
    """KV lengths that aren't lane multiples are zero-padded + masked;
    the result must still match the unpadded XLA oracle."""
    key = jax.random.key(L)
    B, S, H, Hkv, hd = 1, 16, 4, 2, 32
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, L, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, L, Hkv, hd))
    off = L - S
    out = gqa_flash(q, k, v, causal=True, q_offset=off)
    ref = flash_attention_ref(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3),
                              causal=True, q_offset=off)
    np.testing.assert_allclose(np.asarray(out.transpose(0, 2, 1, 3)),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)
    # a caller-supplied kv_len tighter than L must survive the padding
    out = gqa_flash(q, k, v, causal=False, kv_len=L - 7)
    ref = flash_attention_ref(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3),
                              causal=False, kv_len=L - 7)
    np.testing.assert_allclose(np.asarray(out.transpose(0, 2, 1, 3)),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)


# -- flash decode (split-KV) -------------------------------------------------

@pytest.mark.parametrize("B,H,Hkv,hd,L,splits,bk", [
    (1, 4, 4, 32, 64, 2, 32),          # MHA (G=1)
    (2, 8, 2, 64, 200, 4, 64),         # GQA 4, ragged partitions
    (1, 16, 2, 32, 256, 8, 32),        # GQA 8, many splits
    (2, 8, 1, 64, 96, 16, 32),         # MQA, splits > L/bk (clamped)
    (1, 28, 4, 128, 320, 4, 128),      # qwen2-7b head geometry
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(B, H, Hkv, hd, L, splits, bk, dtype):
    key = jax.random.key(B * H + L)
    q = jax.random.normal(key, (B, H, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, L, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, L, hd), dtype)
    out = flash_decode(q, k, v, splits=splits, bk=bk, interpret=True)
    ref = flash_decode_ref(q, k, v)
    tol = 1e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("kv_len", [1, 7, 64, 129, 200])
def test_flash_decode_ragged_kv_len(kv_len):
    """Dynamic cache lengths, including ones that leave whole partitions
    empty (their LSE combine weight must be exactly 0)."""
    key = jax.random.key(kv_len)
    B, H, Hkv, hd, L = 2, 8, 2, 32, 200
    q = jax.random.normal(key, (B, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, L, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, L, hd))
    out = flash_decode(q, k, v, kv_len=kv_len, splits=4, bk=32,
                       interpret=True)
    ref = flash_decode_ref(q, k, v, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_flash_decode_matches_flash_attention_at_s1():
    """The decode kernel must agree with the prefill flash kernel run at
    S=1 with the matching q_offset (the ISSUE's S=1 parity gate)."""
    key = jax.random.key(17)
    B, H, Hkv, hd, L = 2, 8, 2, 64, 160
    q = jax.random.normal(key, (B, H, 1, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, L, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, L, hd))
    for kv_len in (40, 160):
        dec = flash_decode(q[:, :, 0], k, v, kv_len=kv_len, splits=4,
                           bk=32, interpret=True)
        pre = flash_attention(q, k, v, causal=True, q_offset=kv_len - 1,
                              kv_len=kv_len, bq=8, bk=32, interpret=True)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(pre[:, :, 0]),
                                   rtol=1e-3, atol=1e-3)
        ref = flash_attention_ref(q, k, v, causal=True, q_offset=kv_len - 1,
                                  kv_len=kv_len)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(ref[:, :, 0]),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("window", [16, 48])
def test_flash_decode_sliding_window(window):
    key = jax.random.key(window)
    B, H, Hkv, hd, L = 1, 4, 2, 32, 128
    q = jax.random.normal(key, (B, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, L, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, L, hd))
    out = flash_decode(q, k, v, kv_len=100, window=window, splits=4, bk=32,
                       interpret=True)
    ref = flash_decode_ref(q, k, v, kv_len=100, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_gqa_decode_model_wiring_matches_xla_path():
    """models.attention.gqa_decode(use_flash=True) must reproduce the
    XLA cache path bit-for-tolerance over a multi-step decode."""
    from repro.configs import smoke_config
    from repro.models import attention as attn
    from repro.models import build_model

    cfg = smoke_config("qwen2-7b")
    model = build_model(cfg)
    p = jax.tree.map(lambda t: t[0],
                     model.init(jax.random.key(0))["blocks"]["attn"])
    B = 2
    c_xla = c_flash = attn.gqa_init_cache(cfg, B, 32, jnp.float32)
    for t in range(4):
        xt = jax.random.normal(jax.random.key(100 + t), (B, 1, cfg.d_model))
        y1, c_xla = attn.gqa_decode(cfg, p, xt, c_xla, jnp.int32(t),
                                    use_flash=False)
        y2, c_flash = attn.gqa_decode(cfg, p, xt, c_flash, jnp.int32(t),
                                      use_flash=True)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-4, atol=1e-4)


# -- mla decode (absorbed latent) --------------------------------------------

# (qk_nope, qk_rope, kv_lora, H): scaled sweep + the real deepseek-v2 /
# kimi-k2 latent dims (kv_lora_rank=512, rope=64) at reduced head count
MLA_DIMS = [
    (32, 16, 64, 8),
    (64, 32, 128, 16),
    (128, 64, 512, 8),      # deepseek-v2 / kimi-k2 latent geometry
]


@pytest.mark.parametrize("nope,rope,r,H", MLA_DIMS)
@pytest.mark.parametrize("kv_len", [1, 37, 96])
def test_mla_decode_sweep(nope, rope, r, H, kv_len):
    key = jax.random.key(nope + kv_len)
    B, L = 2, 96
    scale = 1.0 / np.sqrt(nope + rope)
    ql = jax.random.normal(key, (B, H, r)) * 0.1
    qp = jax.random.normal(jax.random.fold_in(key, 1), (B, H, rope))
    ckv = jax.random.normal(jax.random.fold_in(key, 2), (B, L, r)) * 0.1
    kpe = jax.random.normal(jax.random.fold_in(key, 3), (B, L, rope))
    out = mla_decode(ql, qp, ckv, kpe, scale=scale, kv_len=kv_len,
                     splits=4, bk=32, interpret=True)
    ref = mla_decode_ref(ql, qp, ckv, kpe, scale=scale, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_mla_decode_model_wiring_matches_xla_path():
    """models.attention.mla_decode(use_flash=True) vs the dense latent
    path, multi-step, on the deepseek smoke config."""
    from repro.configs import smoke_config
    from repro.models import attention as attn
    from repro.models import build_model

    cfg = smoke_config("deepseek-v2-236b")
    model = build_model(cfg)
    p = jax.tree.map(lambda t: t[0],
                     model.init(jax.random.key(0))["dense0"]["attn"])
    B = 2
    c_xla = c_flash = attn.mla_init_cache(cfg, B, 32, jnp.float32)
    for t in range(4):
        xt = jax.random.normal(jax.random.key(200 + t), (B, 1, cfg.d_model))
        y1, c_xla = attn.mla_decode(cfg, p, xt, c_xla, jnp.int32(t),
                                    use_flash=False)
        y2, c_flash = attn.mla_decode(cfg, p, xt, c_flash, jnp.int32(t),
                                      use_flash=True)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-4, atol=1e-4)


def test_decode_wrappers_model_layout():
    """ops wrappers accept the (B,1,...) model layout and round-trip it."""
    key = jax.random.key(5)
    B, H, Hkv, hd, L = 1, 8, 2, 32, 64
    q = jax.random.normal(key, (B, 1, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, L, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, L, Hkv, hd))
    out = gqa_flash_decode(q, k, v, kv_len=50)
    assert out.shape == (B, 1, H, hd)
    ref = flash_decode_ref(q[:, 0], k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), kv_len=50)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)

    r, rope = 64, 16
    ql = jax.random.normal(jax.random.fold_in(key, 3), (B, 1, H, r))
    qp = jax.random.normal(jax.random.fold_in(key, 4), (B, 1, H, rope))
    ckv = jax.random.normal(jax.random.fold_in(key, 5), (B, L, r))
    kpe = jax.random.normal(jax.random.fold_in(key, 6), (B, L, rope))
    out = mla_flash_decode(ql, qp, ckv, kpe, scale=0.1, kv_len=50)
    assert out.shape == (B, 1, H, r)
    ref = mla_decode_ref(ql[:, 0], qp[:, 0], ckv, kpe, scale=0.1, kv_len=50)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_decode_roofline_traffic_below_xla_baseline():
    """The modeled per-step HBM traffic of the fused decode kernels must
    beat the naive XLA path on every priced arch config, and the memory
    roofline term must shrink accordingly."""
    from repro.configs import get_config
    from repro.roofline import (
        gqa_decode_hbm_bytes,
        mla_decode_hbm_bytes,
        roofline_terms,
    )

    for arch in ("qwen2-7b", "phi3-medium-14b", "command-r-plus-104b"):
        cfg = get_config(arch)
        t = gqa_decode_hbm_bytes(B=8, H=cfg.num_heads, Hkv=cfg.kv_heads(),
                                 hd=cfg.resolved_head_dim(), L=16384)
        assert t["fused_bytes"] < t["naive_bytes"], arch
        assert t["fused_bytes"] >= t["floor_bytes"], arch
        naive = roofline_terms(t["flops"], t["naive_bytes"], 0.0)
        fused = roofline_terms(t["flops"], t["fused_bytes"], 0.0)
        assert fused["memory_s"] < naive["memory_s"], arch
        assert fused["dominant"] == "memory", arch     # decode stays HBM-bound

    for arch in ("deepseek-v2-236b", "kimi-k2-1t-a32b"):
        m = get_config(arch).mla
        t = mla_decode_hbm_bytes(B=8, H=get_config(arch).num_heads,
                                 r=m.kv_lora_rank, rd=m.qk_rope_head_dim,
                                 L=16384)
        assert t["fused_bytes"] < t["naive_bytes"], arch
        assert t["fused_bytes"] >= t["floor_bytes"], arch


# -- rwkv wkv --------------------------------------------------------------------

@pytest.mark.parametrize("BH,T,hd,chunk", [
    (2, 32, 16, 8),
    (3, 50, 16, 16),      # ragged chunks
    (1, 128, 64, 32),
    (4, 17, 8, 32),       # chunk > T
])
def test_rwkv_wkv_sweep(BH, T, hd, chunk):
    key = jax.random.key(BH * T)
    r = jax.random.normal(key, (BH, T, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (BH, T, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (BH, T, hd))
    logw = jnp.maximum(-jnp.exp(
        jax.random.normal(jax.random.fold_in(key, 3), (BH, T, hd))), -8.0)
    u = jax.random.normal(jax.random.fold_in(key, 4), (BH, hd)) * 0.5
    s0 = jax.random.normal(jax.random.fold_in(key, 5), (BH, hd, hd)) * 0.3
    y, s = rwkv6_wkv(r, k, v, logw, u, s0, chunk=chunk, interpret=True)
    yr, sr = rwkv6_wkv_ref(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=2e-4, atol=2e-4)


def test_wkv_matches_model_rwkv_chunk():
    """The kernel must agree with the model's XLA chunked scan
    (ssm._rwkv6_chunk composed over chunks)."""
    from repro.models import ssm as ssm_mod

    key = jax.random.key(11)
    B, H, T, hd = 1, 2, 32, 16
    r = jax.random.normal(key, (B, H, T, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, T, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, T, hd))
    logw = jnp.maximum(-jnp.exp(
        jax.random.normal(jax.random.fold_in(key, 3), (B, H, T, hd))), -8.0)
    u = jax.random.normal(jax.random.fold_in(key, 4), (H, hd)) * 0.5
    s0 = jnp.zeros((B, H, hd, hd))
    y_kernel, s_kernel = wkv(r, k, v, logw, u, s0, chunk=8)
    y_model, s_model = ssm_mod._rwkv6_chunk(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_kernel), np.asarray(s_model),
                               rtol=2e-4, atol=2e-4)
