"""Readings of the control and of planted faults, on the chip at a cell's
own size, for setting the cell's limits (the benchmark's runs do not run
this).

    python3 bench/control.py --workload gpt2l.lookup --seeds 1 2 3

The control is the reference put in the program's place, computed in the
precision below the configuration's (float8 e4m3 matmuls for bf16). A
fault is the float32 reference with one planted error: half of each
batch left out (the mean over the rest), or the labels shifted by one
position. Each is compared with the float32 reference by the cell's own
numbers (``check.py``); one JSON line per seed and reading.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np

import run
import check
import generator
from reference.common import Reference, adam_steps


def _half(b):
    return {k: v[: len(v) // 2] for k, v in b.items()}


def _shifted(b):
    return dict(b, labels=np.roll(b["labels"], 1, axis=1))


def lookup_readings(seed, cfg, mix, family):
    """Score gaps of the control and of a half eval set, over as many
    (candidate, task) pairs as a run compares."""
    n = mix["check_lookups"] * mix["check_calls_per_lookup"]
    rng = generator.rng(seed, "control")
    prompts = [generator.prompt(seed, "bank", int(i), mix["prompt_len"],
                                cfg["d_model"])
               for i in rng.integers(0, mix["bank_sources"], n)]
    batches = [generator.eval_rows(seed, int(t), mix, cfg["vocab_size"])
               for t in rng.integers(0, mix["tasks"], n)]
    words = generator.key_words(seed)
    ref = Reference(family, cfg, words).scores(prompts, batches)
    out = {}
    for name, mode, bs in [("control_fp8", "fp8", batches),
                           ("fault_half_batch", None,
                            [_half(b) for b in batches])]:
        got = Reference(family, cfg, words, mode).scores(prompts, bs)
        out[name] = {"score_gap": check.widest_gap(got, ref)}
    return out


def tune_readings(seed, cfg, mix, family):
    task = generator.job_plan(seed, mix)[0]["task"]
    p0 = generator.prompt(seed, "job", 0, mix["prompt_len"], cfg["d_model"])
    batches = [generator.train_batch(seed, task, 0, k, mix,
                                     cfg["vocab_size"])
               for k in range(run.TuneJobs.RECORDED_STEPS)]
    words = generator.key_words(seed)

    def steps(mode, fault=None):
        ref = Reference(family, cfg, words, mode)
        losses, g, p = adam_steps(ref, p0, [fault(b) if fault else b
                                            for b in batches], mix["lr"])
        return dict(losses=losses, first_grad=np.asarray(g),
                    prompt=np.asarray(p))

    base = steps(None)
    return {name: check.tune_numbers(p0, steps(mode, fault), base)
            for name, mode, fault in [("control_fp8", "fp8", None),
                                      ("fault_half_batch", None, _half),
                                      ("fault_labels_shifted", None,
                                       _shifted)]}


READINGS = {"bank_lookup": lookup_readings, "tune_jobs": tune_readings}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    cfg = run.load_json(run.BENCH, "configs", f"{cell['config']}.json")
    mix = run.load_json(run.BENCH, "traffic", f"{cell['traffic']}.json")
    family = run.reference_family(cfg)
    for seed in args.seeds:
        with jax.default_matmul_precision("highest"):
            got = READINGS[mix["kind"]](seed, cfg, mix, family)
        for name, numbers in got.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": name, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
