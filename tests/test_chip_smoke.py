"""CPU rehearsal of ``chip_smoke.py``: its phases at smoke widths, the
data-parallel step on four virtual CPU devices, its refusal of a CPU
device, and where the compile cache lives."""
import importlib.util
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.config import TuneConfig
from repro.configs import smoke_config
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


chip_smoke = _load_chip_smoke()


@pytest.fixture(scope="module")
def service_run():
    """The one-chip phases end to end at smoke widths, on the CPU."""
    cfg = smoke_config(chip_smoke.LLM)
    tune_cfg = TuneConfig(prompt_len=4, batch_size=4, eval_samples=4)
    pre = chip_smoke.make_fixture(cfg, partitions=1, prompt_len=4,
                                  input_len=8, target_len=8)
    task = pre.tasks[5]
    bank, _ = chip_smoke.phase_bank(pre, variants=2)
    service, handle, score_ctx, _ = chip_smoke.phase_submit(
        pre, bank, tune_cfg, task)
    tuned, history, times = chip_smoke.phase_tune(
        pre, score_ctx.tuner, task, handle.initial_prompt, steps=4,
        timed_steps=2)
    n0, n1 = chip_smoke.phase_insert(service, pre, task, tuned)
    return dict(pre=pre, bank=bank, handle=handle, score_ctx=score_ctx,
                tuned=tuned, history=history, times=times, n0=n0, n1=n1)


def test_service_phases_route_tune_and_insert(service_run):
    r = service_run
    assert len(r["bank"].entries) == len(r["pre"].tasks) * 2 + 1
    assert r["handle"].routed_through_bank
    assert r["handle"].bank_origin is not None
    assert [h[0] for h in r["history"]] == [2, 4]
    assert len(r["times"]) == 2
    assert r["n1"] == r["n0"] + 1


def test_cpu_reference_and_chunked_prefill_checks(service_run):
    r = service_run
    ref, diff = chip_smoke.check_cpu_reference(
        r["pre"], r["score_ctx"], r["handle"].initial_prompt,
        r["handle"].bank_score)
    assert np.isfinite(ref) and diff <= chip_smoke.CPU_REF_RTOL
    full, chunked, diff = chip_smoke.check_chunked_prefill(
        r["pre"], r["score_ctx"], r["tuned"], ce_chunk=8)
    assert diff <= chip_smoke.CHUNKED_RTOL


def test_cpu_reference_check_fails_on_a_wrong_score(service_run):
    r = service_run
    with pytest.raises(chip_smoke.SmokeError, match="check 1"):
        chip_smoke.check_cpu_reference(
            r["pre"], r["score_ctx"], r["handle"].initial_prompt,
            r["handle"].bank_score * 1.1)


DP_SCRIPT = r"""
import importlib.util, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.config import TuneConfig
from repro.configs import smoke_config
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
assert len(jax.devices()) == 4, jax.devices()
out = cs.phase_data_parallel(
    smoke_config(cs.LLM), n_data=4, global_batch=8, steps=2,
    tune_cfg=TuneConfig(prompt_len=4), input_len=8, target_len=8)
print("PER_STEP", out["per_step"])
cs.check_data_parallel(out)
print("OK")
"""


def test_data_parallel_phase_on_four_cpu_devices():
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src"),
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", DP_SCRIPT], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "OK" in r.stdout


def test_data_parallel_check_fails_on_a_gradient_gap():
    out = {"dp": {"losses": [1.0]}, "one": {"losses": [1.0]},
           "per_step": [{"loss": 0.0, "prompt": 0.0,
                         "grad": 10 * chip_smoke.DP_GRAD_RTOL}]}
    with pytest.raises(chip_smoke.SmokeError, match="gradient moment"):
        chip_smoke.check_data_parallel(out)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_a_cpu_device(argv, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr()
    assert "needs a TPU" in out.err
    assert not any(line.startswith("{") for line in out.out.splitlines())


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    want = str(tmp_path / "xla-cache")
    monkeypatch.setenv(compile_cache.ENV_VAR, want)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    assert first == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
