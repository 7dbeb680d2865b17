"""Smoke-width stand-ins for the cells' configuration and traffic files,
so that a whole run of the harness fits a CPU test."""
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


# float32: at these widths bf16 rounding alone would approach the limits
# set for the cells' own sizes, and the tests plant faults far above them
SMOKE_SIZES = dict(num_layers=2, d_model=128, num_heads=4, d_ff=256,
                   vocab_size=512, dtype="float32", param_dtype="float32")


def shrink(cfg):
    """A configuration file's smoke stand-in: small widths, and its
    sections cut as ``repro.configs.smoke_config`` cuts the registry's (at
    most 4 experts, small ranks and state, 2-5 layers)."""
    mha = cfg["num_kv_heads"] == cfg["num_heads"]
    cfg = dict(cfg, **SMOKE_SIZES, num_kv_heads=4 if mha else 2)
    if "head_dim" in cfg:
        cfg["head_dim"] = SMOKE_SIZES["d_model"] // SMOKE_SIZES["num_heads"]
    if "moe" in cfg:
        m = dict(cfg["moe"], num_experts=4, top_k=2, d_ff_expert=128)
        m["num_shared_experts"] = min(cfg["moe"]["num_shared_experts"], 1)
        m["first_dense_layers"] = min(cfg["moe"]["first_dense_layers"], 1)
        if "experts_held" in m:
            whole = cfg["moe"]["experts_held"] == cfg["moe"]["num_experts"]
            m["experts_held"] = 4 if whole else 2
            m["expert_offset"] = min(m.get("expert_offset", 0),
                                     4 - m["experts_held"])
        cfg["moe"] = m
        # the leading dense layers as wide as top-k plus shared experts,
        # as in the published models
        cfg["d_ff"] = m["d_ff_expert"] * (m["top_k"]
                                          + m["num_shared_experts"])
        cfg["num_layers"] = m["first_dense_layers"] + 2
    if "mla" in cfg:
        # a q projected straight from d_model (rank 0) stays so
        q_rank = 48 if cfg["mla"].get("q_lora_rank", 1) else 0
        cfg["mla"] = dict(cfg["mla"], kv_lora_rank=64, q_lora_rank=q_rank,
                          qk_nope_head_dim=32, qk_rope_head_dim=16,
                          v_head_dim=32)
    if "ssm" in cfg:
        cfg["ssm"] = dict(cfg["ssm"], state_size=32, num_heads=0,
                          chunk_size=16, expand=2)
    if "hybrid" in cfg:
        cfg["hybrid"] = dict(cfg["hybrid"], attn_every=2)
        cfg["num_layers"] = 5
    return cfg


def config(name):
    return shrink(load("configs", f"{name}.json"))


# A configuration with latent attention, routed experts and two kinds of
# layer, kept as test data (no cell runs it yet)
DEEPSEEK = "deepseek-v2-236b.stage"


def data_config(name):
    return shrink(load("tests", "data", f"{name}.json"))


def traffic(name):
    mix = load("traffic", f"{name}.json")
    mix.update(input_len=8, target_len=8, prompt_len=4, eval_samples=4)
    if mix["kind"] == "bank_lookup":
        mix.update(bank_sources=4, variants_per_source=5, num_clusters=4,
                   tasks=6)
    else:
        mix.update(batch_size=4, iters_median=8, iters_min=4, iters_max=12,
                   eval_every=2, jobs=4)
    return mix


def patch(monkeypatch, run):
    """Point the harness's file reads at the smoke stand-ins."""
    real = run.load_json

    def load_json(*parts):
        if len(parts) == 3 and parts[1] == "configs":
            return config(parts[2][:-len(".json")])
        if len(parts) == 3 and parts[1] == "traffic":
            return traffic(parts[2][:-len(".json")])
        return real(*parts)
    monkeypatch.setattr(run, "load_json", load_json)
