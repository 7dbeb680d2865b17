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


def config(name):
    cfg = load("configs", f"{name}.json")
    cfg.update(SMOKE_SIZES, num_kv_heads=(4 if cfg["num_kv_heads"] ==
                                          cfg["num_heads"] else 2))
    return cfg


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
