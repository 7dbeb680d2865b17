"""The float32 references against the program's models at smoke widths on
seeded weights, and the layer-at-a-time path against the whole model."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import generator
import run
import smoke
import two_kinds
import weights
from reference.common import Reference, masked_ce

from repro.config import TuneConfig
from repro.models import build_model
from repro.train.objectives import lpt_loss
from repro.tuning import PromptTuner

CONFIGS = ["gpt2-large", "qwen2-7b.14of28"]
SEED = 2**31 + 5


def f32_case(name):
    cfg = smoke.config(name)
    cfg.update(dtype="float32", param_dtype="float32")
    mix = smoke.traffic("bank_routed")
    model = build_model(run.program_config(cfg).with_overrides(remat=False))
    words = generator.key_words(SEED)
    params = weights.program_params(words, model.abstract_params(),
                                    tuple(s.name for s in model.segments))
    prompt = generator.prompt(SEED, "t", 0, mix["prompt_len"],
                              cfg["d_model"])
    batch = generator.eval_rows(SEED, 1, mix, cfg["vocab_size"])
    return cfg, model, params, words, prompt, batch


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_score_matches_program(name):
    cfg, model, params, words, prompt, batch = f32_case(name)
    tuner = PromptTuner(model, TuneConfig(prompt_len=prompt.shape[0]))
    with jax.default_matmul_precision("highest"):
        prog = tuner.score({"soft_prompt": jnp.asarray(prompt)}, params,
                           batch)
        ref, = Reference(run.reference_family(cfg), cfg, words).scores(
            [prompt], [batch])
    assert ref == pytest.approx(prog, rel=2e-6)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_prompt_gradient_matches_program(name):
    cfg, model, params, words, prompt, batch = f32_case(name)
    P = prompt.shape[0]
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(
            lambda p: lpt_loss(model, params, p, jb, P), has_aux=True)(
            jnp.asarray(prompt))
        ref = Reference(run.reference_family(cfg), cfg, words)
        ref_loss, ref_g = ref.loss_and_grad(jnp.asarray(prompt), batch)
    assert ref_loss == pytest.approx(float(loss), rel=2e-6)
    np.testing.assert_allclose(ref_g, g, rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(g).max()))


@pytest.mark.parametrize("name", CONFIGS)
def test_layer_at_a_time_equals_whole_model(name):
    """The layer-by-layer forward and backward give the loss and prompt
    gradient of one differentiated function over all layers at once."""
    cfg = smoke.config(name)
    fam = run.reference_family(cfg)
    mix = smoke.traffic("bank_routed")
    words = generator.key_words(SEED)
    prompt = jnp.asarray(generator.prompt(SEED, "t", 0, mix["prompt_len"],
                                          cfg["d_model"]))
    batch = generator.eval_rows(SEED, 2, mix, cfg["vocab_size"])
    dt = cfg["param_dtype"]
    layers = [weights.group(words, fam.layer_shapes(cfg), dt, i)
              for i in range(cfg["num_layers"])]
    top = weights.group(words, fam.top_shapes(cfg), dt)
    P = prompt.shape[0]

    def whole(p):
        B = batch["tokens"].shape[0]
        x = jnp.concatenate([jnp.broadcast_to(p[None], (B, *p.shape)),
                             top["embedding"][batch["tokens"]]], 1)
        for w in layers:
            x = fam.block(cfg, w, x, None)
        h = fam.final(cfg, top, x[:, P:])
        return masked_ce(fam.logits(cfg, top, h, None), batch["labels"],
                         batch["mask"])

    with jax.default_matmul_precision("highest"):
        loss, g = jax.value_and_grad(whole)(prompt)
        ref = Reference(fam, cfg, words)
        l2, g2 = ref.loss_and_grad(prompt, batch)
        s, = ref.scores([np.asarray(prompt)], [batch])
    assert l2 == pytest.approx(float(loss), rel=1e-6)
    assert s == pytest.approx(float(loss), rel=1e-6)
    # the two orders of float32 work differ by rounding, which the prompt
    # gradient of a random-weight model amplifies; a lost or reordered
    # layer moves it by O(1)
    np.testing.assert_allclose(g2, g, rtol=1e-3,
                               atol=1e-3 * float(jnp.abs(g).max()))


def test_two_kinds_of_layer_equal_the_whole_model():
    """A family with two kinds of layer: the layer-at-a-time loss, prompt
    gradient and score equal one differentiated function of the model
    written out whole, each layer drawn at its global index."""
    cfg = dict(smoke.config("qwen2-7b.14of28"), num_layers=5,
               pattern=["attn", "mlp", "mlp", "attn", "mlp"])
    mix = smoke.traffic("bank_routed")
    words = generator.key_words(SEED)
    prompt = jnp.asarray(generator.prompt(SEED, "k", 0, mix["prompt_len"],
                                          cfg["d_model"]))
    batch = generator.eval_rows(SEED, 3, mix, cfg["vocab_size"])
    dt, P = cfg["param_dtype"], prompt.shape[0]
    layers = [(kind, weights.group(words, two_kinds.layer_shapes(cfg, kind),
                                   dt, i))
              for i, kind in enumerate(cfg["pattern"])]
    top = weights.group(words, two_kinds.top_shapes(cfg), dt)

    def whole(p):
        B = batch["tokens"].shape[0]
        x = jnp.concatenate([jnp.broadcast_to(p[None], (B, *p.shape)),
                             top["embedding"][batch["tokens"]]], 1)
        for kind, w in layers:
            x = two_kinds.block(cfg, w, x, None, kind)
        h = two_kinds.final(cfg, top, x[:, P:])
        return masked_ce(two_kinds.logits(cfg, top, h, None),
                         batch["labels"], batch["mask"])

    with jax.default_matmul_precision("highest"):
        loss, g = jax.value_and_grad(whole)(prompt)
        ref = Reference(two_kinds, cfg, words)
        l2, g2 = ref.loss_and_grad(prompt, batch)
        s, = ref.scores([np.asarray(prompt)], [batch])
    assert ref.kinds == cfg["pattern"]
    assert l2 == pytest.approx(float(loss), rel=1e-6)
    assert s == pytest.approx(float(loss), rel=1e-6)
    np.testing.assert_allclose(g2, g, rtol=1e-3,
                               atol=1e-3 * float(jnp.abs(g).max()))
    with pytest.raises(ValueError, match="5 layer kinds for 4 layers"):
        Reference(two_kinds, dict(cfg, num_layers=4), words)


def test_weights_drawn_per_layer_match_the_program_tree():
    cfg = smoke.config("qwen2-7b.14of28")
    model = build_model(run.program_config(cfg))
    words = generator.key_words(SEED)
    params = weights.program_params(words, model.abstract_params(),
                                    ("blocks",))
    fam = run.reference_family(cfg)
    one = weights.group(words, fam.layer_shapes(cfg), cfg["param_dtype"], 1)
    for name, v in one.items():
        a, b = name.split("/")
        # bf16 leaves agree bit for bit; float32 gains may differ in the
        # last place (1 + x fused or not)
        np.testing.assert_allclose(
            np.asarray(params["blocks"][a][b][1], np.float32), v,
            rtol=2.4e-7, atol=0)


def test_fp8_control_rounds_to_e4m3():
    from reference.common import quant
    x = jnp.linspace(-3.0, 3.0, 1001)
    q = quant(x, "fp8")
    # e4m3 keeps 3 mantissa bits: relative rounding error at most 2**-4
    assert bool(jnp.all(jnp.abs(q - x) <= jnp.abs(x) / 16 + 1e-6))
    assert float(jnp.abs(q - x).max()) > 1e-2
    assert len(np.unique(np.asarray(q))) < 300
    assert quant(x, None) is x
