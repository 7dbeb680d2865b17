"""Whole runs of the harness at smoke widths on the CPU: a sound program
is judged correct, and each fault the cells can have, planted in the
timed path, is judged not correct. Also: no TPU, no result."""
import dataclasses
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import run
import smoke

from repro.core.prompt_bank import PromptBank
from repro.tuning import PromptTuner

SEED = 2**31 + 99


@pytest.fixture
def harness(monkeypatch):
    smoke.patch(monkeypatch, run)
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    bench = run.load_json(run.ROOT, "BENCHMARK.json")

    def go(workload):
        return run.run(bench, workload, SEED, 1.0, trace=False)
    return go


def _half(batch):
    return {k: v[: len(v) // 2] for k, v in batch.items()}


def half_eval_batch(monkeypatch):
    score = PromptTuner.score
    monkeypatch.setattr(PromptTuner, "score", lambda self, pp, params, eb:
                        score(self, pp, params, _half(eb)))


def other_pick(monkeypatch):
    lookup = PromptBank.lookup

    def altered(self, fn):
        res = lookup(self, fn)
        other = self.medoid_ids[(res.cluster + 1) % len(self.medoid_ids)]
        return dataclasses.replace(res, entry=self.entries[other])
    monkeypatch.setattr(PromptBank, "lookup", altered)


def state_unchanged(monkeypatch):
    step = PromptTuner.step

    def same(self, pp, opt, params, batch):
        return (pp, opt, step(self, pp, opt, params, batch)[2])
    monkeypatch.setattr(PromptTuner, "step", same)


def half_train_batch(monkeypatch):
    step = PromptTuner.step
    monkeypatch.setattr(PromptTuner, "step", lambda self, pp, opt, params, b:
                        step(self, pp, opt, params, _half(b)))


def labels_shifted(monkeypatch):
    step = PromptTuner.step

    def shifted(self, pp, opt, params, b):
        b = dict(b, labels=np.roll(b["labels"], 1, axis=1))
        return step(self, pp, opt, params, b)
    monkeypatch.setattr(PromptTuner, "step", shifted)


@pytest.mark.parametrize("workload", ["gpt2l.lookup", "qwen2.tune"])
def test_sound_program_is_correct(harness, workload):
    res = harness(workload)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "setup_s" in res["metrics"]


@pytest.mark.parametrize("workload,fault", [
    ("gpt2l.lookup", half_eval_batch),
    ("gpt2l.lookup", other_pick),
    ("qwen2.tune", state_unchanged),
    ("qwen2.tune", half_train_batch),
    ("qwen2.tune", labels_shifted),
])
def test_fault_in_timed_path_is_not_correct(harness, monkeypatch, workload,
                                            fault):
    fault(monkeypatch)
    res = harness(workload)
    assert not res["correct"], res["checks"]


def test_no_tpu_exits_nonzero_without_result(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert run.main(["--workload", "gpt2l.lookup", "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and bench/ has no program."""
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gpt2l.lookup",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout == ""
