"""The per-layer metrics read from the program's own spans and ``jit.*``
counters (``repro.obs.device``): a traced run of each cell at smoke
widths reports them, and each reader gives nothing where nothing was
recorded."""
import pytest

import run
import smoke

from repro.obs import device

SEED = 2**31 + 5
PROGRAM_METRICS = {
    "gpt2l.lookup": ("bank.score_host_ms", "bank.jit_ms_per_lookup"),
    "qwen2.tune": ("tune.jit_ms_per_job", "tune.host_ms_per_step"),
}


@pytest.mark.parametrize("workload", sorted(PROGRAM_METRICS))
def test_traced_run_reports_program_metrics(monkeypatch, workload):
    smoke.patch(monkeypatch, run)
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    device.reset()
    res = run.run(bench, workload, SEED, 1.0, trace=True)
    assert res["correct"], res["checks"]
    for name in PROGRAM_METRICS[workload]:
        value = res["metrics"][name]["value"]
        assert value > 0, (name, value)
    # the program's jit seconds of the window: each job or lookup traces
    # its programs again
    jit_metric = PROGRAM_METRICS[workload][1 if "lookup" in workload else 0]
    assert res["metrics"][jit_metric]["value"] > 1.0


@pytest.mark.parametrize("name", sorted({n for ns in PROGRAM_METRICS.values()
                                         for n in ns}))
def test_reader_gives_nothing_on_an_empty_snapshot(name):
    device.reset()
    assert run.metric_reader(name)({}) is None
