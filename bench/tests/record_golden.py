"""Record what the benchmark's own code gives for the existing cells'
configurations, so that a change to the harness can be held to it bit for
bit (``test_golden.py``): every drawn weight tensor, the required FLOPs,
the program's ``ModelConfig`` and the reference's outputs.

    JAX_PLATFORMS=cpu python3 bench/tests/record_golden.py bench/tests/data/golden.json

Tensors are recorded by the SHA-256 of their bytes, at smoke widths as the
program's tree (float32 and bfloat16) and at GPT2-Large's full width as the
reference draws one layer and the top group.
"""
import dataclasses
import hashlib
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for _p in (HERE, BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import flops  # noqa: E402
import generator  # noqa: E402
import run  # noqa: E402
import smoke  # noqa: E402
import weights  # noqa: E402
from reference.common import Reference  # noqa: E402

from repro.models import build_model  # noqa: E402

CONFIGS = ["gpt2-large", "qwen2-7b.14of28"]
MIXES = ["bank_routed", "tune_manual"]
SEED = 2**31 + 77
FULL_WIDTH_LAYER = 3


def sha(x) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(x)).tobytes()
                          ).hexdigest()


def flat(tree, prefix=""):
    """{"a/b/c": leaf} of a nested dict."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        out.update(flat(v, path) if isinstance(v, dict) else {path: v})
    return out


def draws(name):
    """SHA-256 of every leaf of the program's tree at smoke widths, as
    float32 and as bfloat16 (norm parameters stay float32)."""
    out = {}
    words = generator.key_words(SEED)
    for dt in ("float32", "bfloat16"):
        cfg = smoke.config(name)
        cfg.update(dtype=dt, param_dtype=dt)
        model = build_model(run.program_config(cfg))
        params = weights.program_params(
            words, model.abstract_params(),
            tuple(s.name for s in model.segments))
        out[dt] = {k: sha(v) for k, v in flat(params).items()}
    return out


def flop_counts(name):
    """Required FLOPs at the cells' traffic shapes (full width) and at
    smoke widths."""
    out = {}
    for label, cfg, load_mix in (
            ("full", run.load_json(BENCH, "configs", f"{name}.json"),
             lambda m: run.load_json(BENCH, "traffic", f"{m}.json")),
            ("smoke", smoke.config(name), smoke.traffic)):
        for m in MIXES:
            mix = load_mix(m)
            P, S = mix["prompt_len"], generator.seq_len(mix)
            out[f"{label}.{m}.forward"] = flops.forward(
                cfg, mix["eval_samples"], P, S)
            out[f"{label}.{m}.tune_step"] = flops.tune_step(
                cfg, mix.get("batch_size", 8), P, S)
    return out


def program_config(name):
    cfg = run.load_json(BENCH, "configs", f"{name}.json")
    return json.loads(json.dumps(dataclasses.asdict(run.program_config(cfg))))


def reference(name):
    """The float32 reference at smoke widths: two Eqn-1 scores, and a
    loss with its prompt gradient."""
    cfg = smoke.config(name)
    mix = smoke.traffic("bank_routed")
    words = generator.key_words(SEED)
    prompts = [generator.prompt(SEED, "g", i, mix["prompt_len"],
                                cfg["d_model"]) for i in range(2)]
    batches = [generator.eval_rows(SEED, t, mix, cfg["vocab_size"])
               for t in range(2)]
    with jax.default_matmul_precision("highest"):
        ref = Reference(run.reference_family(cfg), cfg, words)
        scores = ref.scores(prompts, batches)
        loss, grad = ref.loss_and_grad(jnp.asarray(prompts[0]), batches[1])
    return {"scores": scores, "loss": loss, "grad": sha(grad)}


def full_width():
    """GPT2-Large at its published widths, as the reference draws one
    layer and the top group (bf16 as served, read back as float32)."""
    cfg = run.load_json(BENCH, "configs", "gpt2-large.json")
    fam = run.reference_family(cfg)
    words = generator.key_words(SEED)
    dt = cfg["param_dtype"]
    layer = weights.group(words, fam.layer_shapes(cfg), dt, FULL_WIDTH_LAYER)
    top = weights.group(words, fam.top_shapes(cfg), dt)
    return {"layer": {k: sha(v) for k, v in layer.items()},
            "top": {k: sha(v) for k, v in top.items()}}


def readings():
    return {
        "seed": SEED,
        "configs": {name: {"draws": draws(name), "flops": flop_counts(name),
                           "program_config": program_config(name),
                           "reference": reference(name)}
                    for name in CONFIGS},
        "gpt2-large.full_width": full_width(),
    }


if __name__ == "__main__":
    with open(sys.argv[1], "w") as f:
        json.dump(readings(), f, indent=1, sort_keys=True)
        f.write("\n")
