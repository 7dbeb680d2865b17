"""Per-arch smoke tests (deliverable f): reduced same-family variants run
one forward + one LPT train step on CPU; output shapes + no NaNs. Decode
parity: replaying a short sequence token-by-token through the serve path
must reproduce the full forward's logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import TuneConfig
from repro.configs import ASSIGNED_ARCHS, smoke_config
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.models import build_model
from repro.train.optimizer import adam


def _inputs(cfg, B=2, S=16, key=0):
    k = jax.random.key(key)
    batch = {
        "tokens": jax.random.randint(k, (B, S), 3, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.fold_in(k, 1), (B, S), 3,
                                     cfg.vocab_size),
        "mask": jnp.ones((B, S), jnp.float32),
    }
    if cfg.frontend.kind != "none":
        batch["frontend"] = jax.random.normal(
            jax.random.fold_in(k, 2),
            (B, cfg.frontend.num_embeddings, cfg.frontend.embed_dim),
            jnp.float32,
        )
    return batch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_forward_and_train_step(arch):
    cfg = smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _inputs(cfg)
    tc = TuneConfig(prompt_len=4, lr=0.1)
    step, opt = make_train_step(model, tc)
    pp = {"soft_prompt": jnp.zeros((4, cfg.d_model), jnp.float32)}
    opt_state = opt.init(pp)
    pp2, opt_state2, loss = step(params, pp, opt_state, batch)
    assert jnp.isfinite(loss), arch
    assert pp2["soft_prompt"].shape == (4, cfg.d_model)
    # the step must actually move the prompt
    assert float(jnp.abs(pp2["soft_prompt"] - pp["soft_prompt"]).max()) > 0


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_prefill_scores(arch):
    cfg = smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _inputs(cfg)
    fn = make_prefill_step(model, ce_chunk=8)
    pp = {"soft_prompt": jnp.zeros((4, cfg.d_model), jnp.float32)}
    per_ex = jax.jit(fn)(params, pp, batch)
    assert per_ex.shape == (2,)
    assert bool(jnp.isfinite(per_ex).all()), arch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_serve_step_shapes(arch):
    cfg = smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cache = model.init_cache(2, 32)
    fn = make_serve_step(model)
    nxt, cache2 = jax.jit(fn)(params, cache,
                              jnp.full((2, 1), 3, jnp.int32), jnp.int32(0))
    assert nxt.shape == (2, 1) and nxt.dtype == jnp.int32
    assert jax.tree.structure(cache2) == jax.tree.structure(cache)


@pytest.mark.parametrize("arch", ["qwen2-7b", "phi3-medium-14b",
                                  "command-r-plus-104b", "rwkv6-7b",
                                  "deepseek-v2-236b"])
def test_decode_matches_forward(arch):
    """Teacher-forced decode must reproduce the full forward logits."""
    cfg = smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B, S = 1, 8
    toks = jax.random.randint(jax.random.key(1), (B, S), 3, cfg.vocab_size)
    full_logits, _ = model.forward(params, toks)
    cache = model.init_cache(B, 16)
    outs = []
    for t in range(S):
        logits, cache = model.decode_step(
            params, cache, toks[:, t:t + 1], jnp.int32(t))
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full_logits),
                               rtol=2e-3, atol=2e-3)


def test_hybrid_decode_matches_forward():
    cfg = smoke_config("zamba2-7b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 6), 3, cfg.vocab_size)
    full_logits, _ = model.forward(params, toks)
    cache = model.init_cache(1, 8)
    outs = []
    for t in range(6):
        logits, cache = model.decode_step(
            params, cache, toks[:, t:t + 1], jnp.int32(t))
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full_logits),
                               rtol=5e-3, atol=5e-3)
