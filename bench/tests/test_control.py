"""The control -- the reference put in the program's place at float8, the
precision below the configurations' bfloat16 -- and the planted faults
fail each cell's limits, at smoke widths on the CPU, on three seeds.
(On the chip at the cells' own sizes: ``python3 bench/control.py``.)"""
import jax
import pytest

import check
import control
import run
import smoke

CELLS = [("gpt2l.lookup", "gpt2-large", "bank_routed"),
         ("qwen2.tune", "qwen2-7b.14of28", "tune_manual")]


@pytest.mark.parametrize("workload,config,traffic", CELLS)
@pytest.mark.parametrize("seed", [11, 2**31 + 12, 3 * 10**9 + 13])
def test_control_and_faults_fail_the_limits(workload, config, traffic, seed):
    cfg, mix = smoke.config(config), smoke.traffic(traffic)
    limits = check.load_limits(workload)
    with jax.default_matmul_precision("highest"):
        readings = control.READINGS[mix["kind"]](
            seed, cfg, mix, run.reference_family(cfg))
    assert set(readings) >= {"control_fp8", "fault_half_batch"}
    for name, numbers in readings.items():
        ok, checks = check.verdict(
            dict(numbers, pick_errors=0) if "pick_errors" in limits
            else numbers, limits)
        assert not ok, (name, checks)
