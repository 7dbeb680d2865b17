"""Seeded weights for every leaf the program's architectures build, keyed
by the layer's global index and, in a stack of routed experts, by the
expert's global index."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import generator
import run
import smoke
import weights
from record_golden import flat

from repro.configs import list_archs, smoke_config
from repro.models import build_model

SEED = 2**31 + 21
WORDS = generator.key_words(SEED)


def bf16_smoke(arch):
    return build_model(smoke_config(arch).with_overrides(
        dtype="bfloat16", param_dtype="bfloat16"))


def per_layer(model, params):
    """{(global layer, leaf name below its group): tensor}."""
    out, n = {}, 0
    for g in run.stacked_groups(model):
        node = params
        for k in g.split("/"):
            node = node[k]
        leaves = flat(node)
        count = next(iter(leaves.values())).shape[0]
        for i in range(count):
            out.update({(n + i, name): v[i] for name, v in leaves.items()})
        n += count
    return out


@pytest.mark.parametrize("arch", list_archs())
def test_registry_smoke_tree_draws(arch):
    model = bf16_smoke(arch)
    abstract = flat(model.abstract_params())
    params = flat(weights.program_params(WORDS, model.abstract_params(),
                                         run.stacked_groups(model)))
    assert params.keys() == abstract.keys()
    for name, a in abstract.items():
        v = np.asarray(params[name], np.float32)
        assert params[name].shape == a.shape and params[name].dtype == a.dtype
        # the reference reads each leaf back in the dtype the program keeps
        assert weights.served_dtype(name, "bfloat16") == a.dtype, name
        assert np.isfinite(v).all() and np.unique(v).size > 1, name


def test_an_expert_is_drawn_at_the_scale_of_its_own_fan_in():
    cfg = smoke_config("deepseek-v2-236b")
    model = build_model(cfg.with_overrides(
        moe=dataclasses.replace(cfg.moe, num_experts=64)))
    params = weights.program_params(WORDS, model.abstract_params(),
                                    run.stacked_groups(model))
    d = model.cfg.d_model
    for name in ("w_gate", "w_up"):
        std = float(jnp.std(params["moe"]["moe"][name]))
        assert std == pytest.approx(d ** -0.5, rel=0.05), name


def reference_draw(name, shape, i, expert_offset=0):
    """A tensor as the reference draws it: inside its jitted layer, the
    layer's index traced."""
    return jax.jit(lambda words, i: weights.group(
        words, {name: shape}, "bfloat16", i, expert_offset)[name])(
        jnp.asarray(WORDS), i)


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "kimi-k2-1t-a32b",
                                  "zamba2-7b"])
def test_no_two_layers_share_a_tensor(arch):
    """In a model of several segments every layer draws its own tensors,
    and layer ``i`` of the program is the reference's draw at ``i``."""
    model = bf16_smoke(arch)
    params = weights.program_params(WORDS, model.abstract_params(),
                                    run.stacked_groups(model))
    layers = per_layer(model, params)
    assert len({i for i, _ in layers}) == model.cfg.num_layers
    by_name = {}
    for (i, name), v in layers.items():
        by_name.setdefault(name, []).append((i, np.asarray(v, np.float32)))
        ref = reference_draw(name, v.shape, i)
        np.testing.assert_array_equal(np.asarray(v, np.float32), ref,
                                      err_msg=f"layer {i} {name}")
    shared = 0
    for name, vs in by_name.items():
        for (i, a), (j, b) in zip(vs, vs[1:]):
            if a.size > 1:
                shared += 1
                assert not np.array_equal(a, b), (name, i, j)
    assert shared > 0


def expert_tree(held):
    return {"moe": {"moe": {
        "w_gate": jax.ShapeDtypeStruct((2, held, 8, 16), jnp.bfloat16),
        "w_down": jax.ShapeDtypeStruct((2, held, 16, 8), jnp.bfloat16),
        "shared": {"w_up": jax.ShapeDtypeStruct((2, 8, 16), jnp.bfloat16)},
        "router": jax.ShapeDtypeStruct((2, 8, 6), jnp.float32)}}}


@pytest.mark.parametrize("offset,held", [(0, 2), (2, 3), (5, 1)])
def test_held_experts_are_that_slice_of_the_uncut_draw(offset, held):
    uncut = weights.program_params(WORDS, expert_tree(6), ("moe",))["moe"]
    share = weights.program_params(WORDS, expert_tree(held), ("moe",),
                                   offset)["moe"]
    for name in ("w_gate", "w_down"):
        np.testing.assert_array_equal(
            share["moe"][name], uncut["moe"][name][:, offset:offset + held])
        for i in range(2):
            np.testing.assert_array_equal(
                np.asarray(share["moe"][name][i], np.float32),
                reference_draw(f"moe/{name}", share["moe"][name].shape[1:],
                               i, offset))
    # what is not stacked over experts is the same in every share
    np.testing.assert_array_equal(share["moe"]["router"],
                                  uncut["moe"]["router"])
    np.testing.assert_array_equal(share["moe"]["shared"]["w_up"],
                                  uncut["moe"]["shared"]["w_up"])


def test_unknown_leaf_raises():
    with pytest.raises(KeyError, match="attn/w_mystery"):
        weights.tensor(WORDS, "attn/w_mystery", 0, (4, 4), jnp.float32)
    tree = {"blocks": {"attn": {"w_mystery": jax.ShapeDtypeStruct(
        (2, 4, 4), jnp.float32)}}}
    with pytest.raises(KeyError, match="w_mystery"):
        weights.program_params(WORDS, tree, ("blocks",))


@pytest.mark.parametrize("arch,leaf", [("rwkv6-7b", "tmix/w0"),
                                       ("zamba2-7b", "mixer/a_log")])
def test_decays_stay_in_the_programs_stable_range(arch, leaf):
    model = bf16_smoke(arch)
    params = weights.program_params(WORDS, model.abstract_params(),
                                    run.stacked_groups(model))
    a, b = leaf.split("/")
    for g in run.stacked_groups(model):
        v = np.asarray(params[g][a][b])
        assert v.min() >= -6.0 and v.max() <= -1.0
        assert v.max() - v.min() > 2.0


def test_new_configuration_draws_from_its_file_alone():
    """A configuration file with ``moe`` and ``mla`` sections gives a
    ModelConfig, and the program's tree draws."""
    cfg = smoke.data_config(smoke.DEEPSEEK)
    mc = run.program_config(cfg)
    model = build_model(mc)
    assert [s.kind for s in model.segments] == ["dense", "moe"]
    assert mc.num_layers == 3 and mc.moe.num_experts == 4
    params = weights.program_params(WORDS, model.abstract_params(),
                                    run.stacked_groups(model))
    assert params["moe"]["attn"]["wkv_a"].shape == (2, 128, 64 + 16)
