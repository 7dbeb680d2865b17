"""The required-FLOP functions count no more than the compiler says the
program executes (``cost_analysis``), at smoke widths on one layer of
each kind (a scan's body is counted once), and not much less; for latent
attention and routed experts they equal a count by hand. A layer's weight
bytes are those of the program's tree."""
import math

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


def qwen2_head_dim_48():
    cfg = smoke.config("qwen2-7b.14of28")
    cfg["head_dim"] = 48                   # not d_model / num_heads (32)
    return cfg


CASES = {
    "gpt2-large": lambda: smoke.config("gpt2-large"),
    "qwen2-7b.14of28": lambda: smoke.config("qwen2-7b.14of28"),
    "qwen2.head_dim": qwen2_head_dim_48,
    "deepseek-v2.stage": lambda: smoke.data_config(smoke.DEEPSEEK),
}


def setup(name, batch):
    cfg = CASES[name]()
    # one layer of each kind
    cfg.update(num_layers=len(set(flops.layer_kinds(cfg))), dtype="float32",
               param_dtype="float32")
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


@pytest.mark.parametrize("name", CASES)
def test_forward_required_at_most_executed(name):
    cfg, tuner, params, pp, b, P, S = setup(name, 4)
    required = flops.forward(cfg, 4, P, S)
    done = executed(lambda p, w, x: tuner._score(p, w, x), pp, params, b)
    assert 0.5 * done <= required <= done


@pytest.mark.parametrize("name", CASES)
def test_tune_step_required_at_most_executed(name):
    cfg, tuner, params, pp, b, P, S = setup(name, 4)
    opt = jax.eval_shape(tuner.init_opt, pp)
    required = flops.tune_step(cfg, 4, P, S)
    done = executed(lambda p, o, w, x: tuner._step(p, o, w, x), pp, opt,
                    params, b)
    assert 0.5 * done <= required <= done


# The DeepSeek-V2 stand-in by hand: d 128, 4 heads, vocabulary 512;
# latent attention q_lora 48, kv_lora 64, nope 32, rope 16, v 32;
# layers dense (width 384), expert, expert: 4 experts of width 128, top 2,
# 1 shared. Multiply-adds per token:
MLA_PER_TOKEN = (128 * 48 + 48 * 4 * (32 + 16)   # q_a, q_b
                 + 128 * (64 + 16)               # kv_a
                 + 64 * 4 * (32 + 32)            # kv_b to nope keys and v
                 + 4 * 32 * 128)                 # o
DENSE_PER_TOKEN = 128 * 384 * 3
EXPERT = 128 * 128 * 3                           # one SwiGLU expert
MOE_PER_TOKEN = 128 * 4 + EXPERT + 2 * EXPERT    # router, shared, top 2


def test_latent_attention_and_experts_equal_a_hand_count():
    cfg = smoke.data_config(smoke.DEEPSEEK)
    assert flops.layer_kinds(cfg) == ["dense", "moe", "moe"]
    B, P, S = 2, 1, 3
    T, tokens = P + S, B * (P + S)
    assert MLA_PER_TOKEN == 58368
    weights = 2 * tokens * (3 * MLA_PER_TOKEN + DENSE_PER_TOKEN
                            + 2 * MOE_PER_TOKEN)
    attn = 3 * B * 4 * (48 + 32) * T * (T + 1) // 2 * 2  # QK^T 48, PV 32
    unembed = B * S * 2 * 128 * 512
    assert flops.forward(cfg, B, P, S) == weights + attn + unembed
    assert flops.tune_step(cfg, B, P, S) == (2 * weights + 3 * attn
                                             + 2 * unembed)
    # a share of 2 of the 4 experts: half the routed work's expectation
    cfg["moe"].update(experts_held=2, expert_offset=2)
    assert (flops.forward(dict(cfg), B, P, S)
            == weights + attn + unembed - 2 * 2 * tokens * EXPERT)
    # without a q projection's low rank
    cfg["mla"]["q_lora_rank"] = 0
    fewer = 3 * 2 * tokens * (128 * 48 + 48 * 4 * 48 - 128 * 4 * 48)
    assert flops.forward(cfg, B, P, S) == (weights + attn + unembed
                                           - 2 * 2 * tokens * EXPERT - fewer)


def test_explicit_head_dim_is_counted():
    cfg = qwen2_head_dim_48()
    base = smoke.config("qwen2-7b.14of28")
    B, P, S = 2, 1, 3
    T, tokens, L = P + S, B * (P + S), cfg["num_layers"]
    more = L * (2 * tokens * 128 * 16 * (2 * 4 + 2 * 2)       # projections
                + B * 4 * 2 * 16 * T * (T + 1) // 2 * 2)       # QK^T, PV
    assert flops.forward(cfg, B, P, S) == flops.forward(base, B, P, S) + more


def test_recurrent_configuration_has_no_count():
    cfg = dict(smoke.config("qwen2-7b.14of28"), ssm={"state_size": 32})
    with pytest.raises(ValueError, match="ssm"):
        flops.forward(cfg, 2, 1, 3)


@pytest.mark.parametrize("name", CASES)
def test_weight_bytes_are_the_program_trees(name):
    cfg = dict(CASES[name](), dtype="bfloat16", param_dtype="bfloat16")
    model = build_model(run.program_config(cfg))
    tree = model.abstract_params()
    for seg in model.segments:
        leaves = jax.tree.leaves(tree[seg.name])
        per_layer = sum(math.prod(a.shape[1:]) * a.dtype.itemsize
                        for a in leaves)
        assert flops.weight_bytes(cfg, seg.kind) == per_layer, seg.kind
