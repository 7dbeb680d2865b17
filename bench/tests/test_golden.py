"""The existing cells' configurations get from the harness exactly what
they got when ``data/golden.json`` was recorded (``record_golden.py``):
the same weights bit for bit, the same required FLOPs, the same
``ModelConfig`` and the same reference outputs."""
import json
import os

import pytest

import record_golden

with open(os.path.join(os.path.dirname(__file__), "data", "golden.json")) as f:
    GOLDEN = json.load(f)


def test_golden_seed_is_the_recorders():
    assert GOLDEN["seed"] == record_golden.SEED


@pytest.mark.parametrize("name", record_golden.CONFIGS)
@pytest.mark.parametrize("part", ["draws", "flops", "program_config",
                                  "reference"])
def test_existing_configuration_reads_as_recorded(name, part):
    fn = {"draws": record_golden.draws, "flops": record_golden.flop_counts,
          "program_config": record_golden.program_config,
          "reference": record_golden.reference}[part]
    assert fn(name) == GOLDEN["configs"][name][part]


def test_full_width_draws_as_recorded():
    assert record_golden.full_width() == GOLDEN["gpt2-large.full_width"]
