"""LPT algorithms: soft prompt + prefix (reparameterized) variants."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import TuneConfig
from repro.configs import smoke_config
from repro.data import LoaderConfig, TaskLoader
from repro.models import build_model
from repro.train.remat import SAVE_LADDER
from repro.tuning import PromptTuner, activation_features


def test_soft_prompt_tuning_reduces_loss(pre_base):
    pre = pre_base
    task = pre.tasks[5]
    tc = TuneConfig(lr=0.5, batch_size=16, eval_every=5, max_iters=60)
    tuner = PromptTuner(pre.model, tc)
    loader = TaskLoader(task, LoaderConfig(batch_size=16))
    pp = tuner.init_prompt(pre.params, jax.random.key(0))
    eb = loader.eval_batch(16)
    before = tuner.score(pp, pre.params, eb)
    res = tuner.tune(pre.params, loader, pp, max_iters=60)
    after = tuner.score(res["prompt"], pre.params, eb)
    assert after < before


def test_prefix_variant_runs(pre_base):
    pre = pre_base
    tc = TuneConfig(algorithm="prefix", lr=0.3, batch_size=8,
                    eval_every=5, max_iters=10)
    tuner = PromptTuner(pre.model, tc)
    loader = TaskLoader(pre.tasks[0], LoaderConfig(batch_size=8))
    pp = tuner.init_prompt(pre.params, jax.random.key(1))
    assert "reparam_w" in pp and "reparam_v" in pp
    res = tuner.tune(pre.params, loader, pp, max_iters=10)
    assert res["iters"] == 10
    assert np.isfinite(res["history"][-1][2]) if res["history"] else True


def test_tune_returns_zero_ita_when_target_met(pre_base):
    """Prompt reusing's endgame: an init already at target has ITA=0."""
    pre = pre_base
    task = pre.tasks[3]
    tc = TuneConfig(lr=0.5, batch_size=16)
    tuner = PromptTuner(pre.model, tc)
    loader = TaskLoader(task, LoaderConfig(batch_size=16))
    own = {"soft_prompt": jnp.asarray(pre.task_prompts[task.task_id])}
    score = tuner.score(own, pre.params, loader.eval_batch(16))
    res = tuner.tune(pre.params, loader, own, target_loss=score + 1.0,
                     max_iters=50)
    assert res["iters"] == 0 and res["reached"]


def test_activation_features_discriminate_tasks(pre_base):
    """Features of prompts for the same family must be closer than
    across families (the property K-medoid clustering relies on)."""
    pre = pre_base
    fam = {}
    for tid in ["shift:0", "shift:1", "xor:0", "xor:1"]:
        fam[tid] = activation_features(
            pre.model, pre.params, jnp.asarray(pre.task_prompts[tid]))
    def cos(a, b):
        return float(np.dot(a, b)
                     / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-9))
    within = cos(fam["shift:0"], fam["shift:1"])
    across = cos(fam["shift:0"], fam["xor:0"])
    assert within > across


def test_init_prompt_from_tokens(pre_base):
    pre = pre_base
    tc = TuneConfig(prompt_len=4)
    tuner = PromptTuner(pre.model, tc)
    toks = jnp.array([3, 4, 5, 6])
    pp = tuner.init_prompt(pre.params, jax.random.key(0), token_ids=toks)
    expected = np.asarray(pre.params["embedding"])[np.asarray(toks)]
    np.testing.assert_allclose(np.asarray(pp["soft_prompt"]), expected,
                               rtol=1e-6)


# GQA + SwiGLU (gate and up tagged), MHA + GELU (up only)
LAYER_TYPES = {"gqa_swiglu": ("qwen2-7b", {}),
               "mha_gelu": ("gpt2-large", {"num_kv_heads": 4})}


@functools.lru_cache(maxsize=None)
def _rung_setup(layers, dtype):
    arch, over = LAYER_TYPES[layers]
    cfg = smoke_config(arch).with_overrides(remat=True, dtype=dtype,
                                            param_dtype=dtype, **over)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tuner = PromptTuner(model, TuneConfig(prompt_len=4, batch_size=2))
    rng = np.random.default_rng(0)
    pp = {"soft_prompt": jnp.asarray(
        rng.normal(0, 0.1, (4, cfg.d_model)), jnp.float32)}
    batch = {k: jnp.asarray(rng.integers(3, cfg.vocab_size, (2, 9)),
                            jnp.int32) for k in ("tokens", "labels")}
    batch["mask"] = jnp.ones((2, 9), jnp.float32)
    return model, tuner, (pp, tuner.init_opt(pp), params, batch)


def _run_rung(layers, dtype, rung):
    """Loss, Adam's first moment (0.1 x the prompt gradient) and the
    compiled text of the tuner's step on ``rung``."""
    _, tuner, args = _rung_setup(layers, dtype)
    jitted = tuner._step._jit(rung)
    _, opt, loss = jitted(*args)
    text = jitted.lower(*args).compile().as_text()
    return float(loss), np.asarray(opt.mu["soft_prompt"]), text


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("layers", sorted(LAYER_TYPES))
@pytest.mark.parametrize("rung", range(len(SAVE_LADDER)))
def test_saving_outputs_changes_no_number(rung, layers, dtype):
    """Each rung of the save ladder gives the loss and prompt gradient of
    full rematerialisation (the last rung) and recomputes fewer dots;
    the forward-only score program lowers as without a save policy."""
    model, tuner, (pp, _, params, batch) = _rung_setup(layers, dtype)
    last = len(SAVE_LADDER) - 1
    loss, mu, text = _run_rung(layers, dtype, rung)
    full_loss, full_mu, full_text = _run_rung(layers, dtype, last)
    np.testing.assert_allclose(loss, full_loss, rtol=1e-6, atol=0)
    np.testing.assert_allclose(mu, full_mu, rtol=1e-6, atol=0)
    assert np.abs(mu).max() > 0
    if rung < last:
        assert text.count(" dot(") < full_text.count(" dot(")
    saving = PromptTuner(model.saving(SAVE_LADDER[rung]), tuner.tune_cfg)
    assert (saving._score.lower(pp, params, batch).as_text()
            == tuner._score.lower(pp, params, batch).as_text())
