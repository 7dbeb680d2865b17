"""The required-FLOP functions count no more than the compiler says the
program executes (``cost_analysis``), at smoke widths on one layer (a
scan's body is counted once), and not much less."""
import jax
import jax.numpy as jnp
import pytest

import flops
import generator
import run
import smoke

from repro.config import TuneConfig
from repro.models import build_model
from repro.tuning import PromptTuner

CONFIGS = ["gpt2-large", "qwen2-7b.14of28"]


def setup(name, batch):
    cfg = smoke.config(name)
    cfg.update(num_layers=1, dtype="float32", param_dtype="float32")
    mix = smoke.traffic("tune_manual")
    model = build_model(run.program_config(cfg).with_overrides(remat=False))
    params = model.abstract_params()
    P, S = mix["prompt_len"], generator.seq_len(mix)
    tuner = PromptTuner(model, TuneConfig(prompt_len=P, batch_size=batch))
    pp = {"soft_prompt": jax.ShapeDtypeStruct((P, cfg["d_model"]),
                                              jnp.float32)}
    b = {"tokens": jax.ShapeDtypeStruct((batch, S), jnp.int32),
         "labels": jax.ShapeDtypeStruct((batch, S), jnp.int32),
         "mask": jax.ShapeDtypeStruct((batch, S), jnp.float32)}
    return cfg, tuner, params, pp, b, P, S


def executed(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    return (cost[0] if isinstance(cost, list) else cost)["flops"]


@pytest.mark.parametrize("name", CONFIGS)
def test_forward_required_at_most_executed(name):
    cfg, tuner, params, pp, b, P, S = setup(name, 4)
    required = flops.forward(cfg, 4, P, S)
    done = executed(lambda p, w, x: tuner._score(p, w, x), pp, params, b)
    assert 0.5 * done <= required <= done


@pytest.mark.parametrize("name", CONFIGS)
def test_tune_step_required_at_most_executed(name):
    cfg, tuner, params, pp, b, P, S = setup(name, 4)
    opt = jax.eval_shape(tuner.init_opt, pp)
    required = flops.tune_step(cfg, 4, P, S)
    done = executed(lambda p, o, w, x: tuner._step(p, o, w, x), pp, opt,
                    params, b)
    assert 0.5 * done <= required <= done
