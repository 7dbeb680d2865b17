"""Device-path spans and compile counters (``repro.obs.device``): off
without a profiler session, counted and on the profiler's host plane
with one. Each test that turns tracing on runs one profiler session and
stops it in ``finally``."""
import contextlib
import itertools
import os
import sys
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import TuneConfig
from repro.configs import smoke_config
from repro.core.prompt_bank import PromptBank, PromptEntry
from repro.data import LoaderConfig, TaskLoader, TaskSpec
from repro.models import build_model
from repro.obs import device
from repro.tuning import PromptTuner

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "bench")


@contextlib.contextmanager
def tracing(tmp_path):
    device.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def rows(snap, **match):
    return [r for r in snap["spans"]
            if all(r[k] == v for k, v in match.items())]


def test_off_records_nothing_and_allocates_nothing():
    device.reset()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    ctx = device.span("bank.score", layer=1)
    assert ctx is device.NO_SPAN and device.span("tune.step") is ctx
    for _ in range(100):                     # warm any lazy state first
        with device.span("tune.step"):
            pass
    tracemalloc.start()
    try:
        before, _ = tracemalloc.get_traced_memory()
        for _ in itertools.repeat(None, 10_000):
            with device.span("bank.score", layer=2):
                pass
        after, _ = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert after - before == 0
    assert device.snapshot() == {"spans": [], "jit": [], "remat": []}


def test_nested_spans_count_total_self_and_share_root_id(tmp_path):
    with tracing(tmp_path):
        with device.span("tune.job") as job:
            for _ in range(2):
                with device.span("tune.step") as step:
                    with device.span("tuner.upload") as up:
                        pass
            with pytest.raises(RuntimeError):    # spans close on raise
                with device.span("tune.eval"):
                    raise RuntimeError("window closed")
        with device.span("tune.job") as other:
            pass
    snap = device.snapshot()
    job_row, = rows(snap, span="tune.job")
    step_row, = rows(snap, span="tune.step", parent="tune.job")
    up_row, = rows(snap, span="tuner.upload", parent="tune.step")
    eval_row, = rows(snap, span="tune.eval", parent="tune.job")
    assert (job_row["count"], step_row["count"], up_row["count"],
            eval_row["count"]) == (2, 2, 2, 1)
    assert job_row["parent"] == ""
    assert step_row["self_s"] == pytest.approx(
        step_row["total_s"] - up_row["total_s"], abs=1e-9)
    assert job_row["self_s"] == pytest.approx(
        job_row["total_s"] - step_row["total_s"] - eval_row["total_s"],
        abs=1e-9)
    ids = {s.ids["trace_id"] for s in (job, step, up)}
    assert len(ids) == 1 and other.ids["trace_id"] not in ids
    assert device.TRACER._stack() == []


def test_fresh_jit_is_one_trace_and_one_compile(tmp_path):
    x = np.arange(8, dtype=np.float32)
    with tracing(tmp_path):
        with device.span("tune.job"):
            with device.span("tuner.dispatch"):
                jax.jit(lambda a: jax.lax.sin(a) * 2.0 + 1.0)(
                    x).block_until_ready()
    jit = device.snapshot()["jit"]
    where = dict(span="tuner.dispatch", root="tune.job")
    for metric in ("jit.trace_s", "jit.lower_s", "jit.compile_s"):
        row, = [r for r in jit if r["metric"] == metric]
        assert {k: row[k] for k in where} == where
    assert device.total(jit, "count", metric="jit.lower_s") == 1
    assert device.total(jit, "count", metric="jit.compile_s") == 1
    assert device.jit_seconds(device.snapshot(), root="tune.job") > 0


def test_nested_jit_events_count_each_second_once(tmp_path):
    event = "/jax/core/compile/jaxpr_trace_duration"
    with tracing(tmp_path):
        with device.span("tune.job"):
            # an outer trace over [0, 5] that traced two inner ones
            for a, b in ((1.0, 2.0), (3.0, 3.5), (0.0, 5.0), (6.0, 7.0)):
                jax.monitoring.record_event_time_span(event, a, b)
    snap = device.snapshot()
    assert device.jit_seconds(snap, root="tune.job") == pytest.approx(6.0)
    assert device.total(snap["jit"], "count", metric="jit.trace_s") == 4


def test_lookup_scores_are_spans(tmp_path):
    rng = np.random.default_rng(0)
    entries = [PromptEntry(prompt=np.zeros((2, 2), np.float32),
                           feature=rng.normal(size=4).astype(np.float32)
                           + 5 * (i % 3), origin=f"e{i}")
               for i in range(24)]
    bank = PromptBank(num_clusters=3, seed=0)
    bank.add_candidates(entries)
    bank.build()
    with tracing(tmp_path):
        res = bank.lookup(lambda e: float(e.feature.sum()))
        flat = bank.lookup_flat(lambda e: float(e.feature.sum()))
    snap = device.snapshot()
    assert device.total(snap["spans"], "count", span="bank.score") == (
        res.evaluations + flat.evaluations)
    assert device.total(snap["spans"], "count", span="bank.lookup") == 2
    assert flat.evaluations == len(entries)


def test_tune_spans_count_steps_and_evals(tmp_path):
    cfg = smoke_config("gpt2-base")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tc = TuneConfig(prompt_len=4, batch_size=4, eval_every=3, eval_samples=4,
                    lr=0.1)
    tuner = PromptTuner(model, tc)
    loader = TaskLoader(TaskSpec("shift", 1, cfg.vocab_size - 8),
                        LoaderConfig(batch_size=4))
    pp = {"soft_prompt": jnp.zeros((4, cfg.d_model), jnp.float32)}
    iters = 7
    with tracing(tmp_path):
        res = tuner.tune(params, loader, pp, max_iters=iters)
    snap = device.snapshot()
    count = lambda **m: device.total(snap["spans"], "count", **m)  # noqa
    assert res["iters"] == iters
    assert count(span="tune.job") == 1
    assert count(span="tune.step") == count(span="tune.batch") == iters
    assert count(span="tune.eval") == iters // tc.eval_every
    assert count(span="tuner.dispatch", parent="tune.step") == iters
    assert count(span="tuner.upload", parent="tune.step") == iters
    # the step and eval programs are traced and compiled inside the job
    assert device.total(snap["jit"], "count", metric="jit.compile_s",
                        root="tune.job") >= 2


def test_tune_job_counts_its_step_program_on_a_rung(tmp_path):
    cfg = smoke_config("qwen2-7b").with_overrides(
        remat=True, dtype="bfloat16", param_dtype="bfloat16")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tc = TuneConfig(prompt_len=4, batch_size=2, eval_every=2, eval_samples=2)
    loader = TaskLoader(TaskSpec("shift", 1, cfg.vocab_size - 8),
                        LoaderConfig(batch_size=2))
    pp = {"soft_prompt": jnp.zeros((4, cfg.d_model), jnp.float32)}
    device.reset()
    PromptTuner(model, tc).tune(params, loader, pp, max_iters=3)  # untraced
    assert device.snapshot()["remat"] == []
    with tracing(tmp_path):
        PromptTuner(model, tc).tune(params, loader, pp, max_iters=3)
    row, = device.snapshot()["remat"]
    # one step program, compiled on the first rung (the CPU has room)
    assert (row["rung"], row["count"]) == (1, 1)
    assert (row["span"], row["root"]) == ("tuner.dispatch", "tune.job")
    # the five outputs of every layer, in bf16, are among them
    B, S = 2, tc.prompt_len + next(loader)["tokens"].shape[1]
    kept = cfg.num_layers * B * S * 2 * (
        (cfg.num_heads + 2 * cfg.kv_heads()) * cfg.resolved_head_dim()
        + 2 * cfg.d_ff)
    assert row["temp_bytes"] >= kept


def test_span_names_on_the_host_plane(tmp_path):
    sys.path.insert(0, BENCH)
    try:
        import reduce_trace
    finally:
        sys.path.remove(BENCH)
    with tracing(tmp_path):
        with device.span("service.submit", job=3):
            with device.span("bank.lookup"):
                with device.span("bank.score", layer=1):
                    jnp.ones(4).block_until_ready()
    _, spans = reduce_trace.load(str(tmp_path))
    names = {n for n, _, _ in spans}
    assert {"service.submit", "bank.lookup", "bank.score"} <= names
