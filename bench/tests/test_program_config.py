"""A configuration file's keys as the program's ModelConfig: nested
sections replace the registry's sub-fields, and what the program does not
have fails loudly."""
import pytest

import run
import smoke

from repro.config import MLAConfig, MoEConfig
from repro.configs import get_config


def test_sections_replace_the_registrys_sub_fields():
    cfg = smoke.load("tests", "data", f"{smoke.DEEPSEEK}.json")
    cfg["moe"] = dict(cfg["moe"], num_experts=20, capacity_factor=2.0)
    mc = run.program_config(cfg)
    base = get_config("deepseek-v2-236b")
    assert isinstance(mc.moe, MoEConfig) and isinstance(mc.mla, MLAConfig)
    assert (mc.moe.num_experts, mc.moe.capacity_factor) == (20, 2.0)
    # sub-fields the file leaves out keep the registry's values
    assert mc.moe.router_aux_loss_weight == base.moe.router_aux_loss_weight
    assert mc.mla == base.mla
    assert (mc.num_layers, mc.d_ff) == (5, 12288)


def test_a_section_the_registry_lacks_starts_from_the_defaults():
    cfg = dict(smoke.load("configs", "qwen2-7b.14of28.json"),
               mla={"kv_lora_rank": 64})
    mc = run.program_config(cfg)
    assert mc.mla == MLAConfig(kv_lora_rank=64)


def test_descriptive_keys_are_ignored():
    cfg = smoke.load("configs", "gpt2-large.json")
    mc = run.program_config(dict(cfg, published={"n_layer": 1},
                                 name="other", source="elsewhere"))
    assert mc == run.program_config(cfg)
    assert mc.name == "gpt2-large"


@pytest.mark.parametrize("section,key", [("moe", "experts_held"),
                                         ("mla", "rope_scaling")])
def test_unknown_sub_key_raises_naming_it(section, key):
    cfg = smoke.load("tests", "data", f"{smoke.DEEPSEEK}.json")
    cfg[section] = dict(cfg[section], **{key: 1})
    with pytest.raises(KeyError, match=key):
        run.program_config(cfg)


def test_section_the_program_has_must_be_stated():
    cfg = smoke.load("tests", "data", f"{smoke.DEEPSEEK}.json")
    del cfg["moe"]
    with pytest.raises(KeyError, match="'moe'"):
        run.program_config(cfg)


def test_dict_under_a_plain_field_raises():
    cfg = dict(smoke.load("configs", "gpt2-large.json"), d_ff={"x": 1})
    with pytest.raises(TypeError, match="d_ff"):
        run.program_config(cfg)


def test_smoke_stand_in_cuts_sections():
    cfg = smoke.load("tests", "data", f"{smoke.DEEPSEEK}.json")
    cfg["moe"] = dict(cfg["moe"], experts_held=20, expert_offset=20)
    small = smoke.shrink(cfg)
    assert small["moe"] == dict(num_experts=4, top_k=2, num_shared_experts=1,
                                d_ff_expert=128, first_dense_layers=1,
                                experts_held=2, expert_offset=2)
    assert (small["num_layers"], small["d_ff"]) == (3, 384)
    assert small["mla"] == dict(kv_lora_rank=64, q_lora_rank=48,
                                qk_nope_head_dim=32, qk_rope_head_dim=16,
                                v_head_dim=32)
    assert smoke.shrink(dict(cfg, mla=dict(cfg["mla"], q_lora_rank=0)))[
        "mla"]["q_lora_rank"] == 0
    # the published file is left as it was
    assert cfg["moe"]["num_experts"] == 160
