"""Run one benchmark cell once on the chip(s) of this machine.

    python3 bench/run.py --workload gpt2l.lookup --seed 7 --seconds 30 --trace 0

The cell, its configuration (``bench/configs/<config>.json``), its
traffic (``bench/traffic/<traffic>.json``), its limits
(``bench/limits/<workload>.json``) and its per-layer metrics
(``bench/metrics/<metric>.py``) are found by the names in
``BENCHMARK.json``. A traffic file's ``kind`` names the driver below that
runs it.

Every run: refuses anything but a TPU with the cell's chip count (exit
2, no result); draws weights on the device and inputs from ``--seed``;
warms up the cell's own shapes (set-up); measures for ``--seconds``;
then compares what the timed path produced with the plain float32
reference in ``bench/reference/`` and prints the numbers compared, each
with its limit, last on standard error and as the last key of the
result. The last line of standard output is the result as JSON. With
``--trace 1`` the window is traced and the cell's per-layer metrics are
reported in place of its end-to-end ones.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import typing  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import check  # noqa: E402
import flops  # noqa: E402
import generator  # noqa: E402
import reduce_trace  # noqa: E402
import weights  # noqa: E402
from reference.common import Reference, adam_steps  # noqa: E402

from repro.api import PromptTunerService, SubmitRequest  # noqa: E402
from repro.cluster import SimConfig  # noqa: E402
from repro.config import TuneConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.bank_builder import (  # noqa: E402
    ScoreContext,
    build_bank_from_pretrain,
)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.train.pretrain import PretrainResult  # noqa: E402
from repro.tuning import PromptTuner  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# File keys that describe the configuration rather than set a model field.
NOT_MODEL_FIELDS = ("name", "source")
# Sections the benchmark's arithmetic reads from the file, not the registry.
SECTIONS = ("moe", "mla", "ssm", "hybrid", "encdec")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events (a cache hit reports its load time as a compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = collections.defaultdict(float)
        self.compiles = 0
        self.cache_hits = 0

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.seconds[kw.get("fun_name", "?")] += duration
            self.compiles += 1

    def on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def install(self) -> "CompileLog":
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def snapshot(self):
        return self.compiles, self.cache_hits, sum(self.seconds.values())


@dataclasses.dataclass
class Run:
    """What a driver is given: the seed, the cell's files, the
    program's model and weights."""
    seed: int
    cfg: dict            # configuration file
    mix: dict            # traffic file
    model: object
    params: object


class WindowEnd(Exception):
    """The measured window has closed."""


# ---------------------------------------------------------------------------
# Drivers, one per traffic kind
# ---------------------------------------------------------------------------


class Recorder:
    """Wraps the Eqn-1 score function the service calls: records each
    call's candidate, score and host seconds, in the current lookup's
    list."""

    def __init__(self, ctx, driver):
        self.ctx, self.driver = ctx, driver

    def __call__(self, entry):
        with jax.profiler.TraceAnnotation("score"):
            t0 = time.perf_counter()
            s = self.ctx(entry)
            dt = time.perf_counter() - t0
        self.driver.calls.append((entry.origin, s, dt))
        return s


class BankLookup:
    """Routed tuning jobs, one client, closed loop: back-to-back
    ``PromptTunerService.submit``, each running the Prompt Bank's
    two-layer Eqn-1 lookup on the chip. The window holds whole lookups:
    it ends with the first lookup to finish after ``seconds``."""

    spans = ("submit", "score")

    def __init__(self, run: Run):
        self.run = run
        self.calls = []

    def setup(self):
        r, mix, cfg = self.run, self.run.mix, self.run.cfg
        P, d = mix["prompt_len"], cfg["d_model"]
        sources = {f"src{i:03d}": generator.prompt(r.seed, "bank", i, P, d)
                   for i in range(mix["bank_sources"])}
        pre = PretrainResult(r.model, r.params, sources, [])
        t0 = time.perf_counter()
        self.bank = build_bank_from_pretrain(
            pre, variants_per_prompt=mix["variants_per_source"],
            noise_scales=tuple(mix["noise_scales"]),
            num_clusters=mix["num_clusters"],
            capacity=mix["bank_sources"] * mix["variants_per_source"],
            seed=r.seed)
        sizes = sorted(len(c) for c in self.bank.clusters)
        log(f"bank: {len(self.bank.entries)} candidates in "
            f"{time.perf_counter() - t0:.2f}s; cluster sizes {sizes[0]}.."
            f"{sizes[-1]}")
        self.index = {e.origin: i for i, e in enumerate(self.bank.entries)}
        self.tune_cfg = TuneConfig(prompt_len=P,
                                   eval_samples=mix["eval_samples"])
        self.service = PromptTunerService(
            SimConfig(max_gpus=8), bank=self.bank,
            score_fn_factory=self._score_fn)
        warm = int(generator.rng(r.seed, "warm").integers(mix["tasks"]))
        self._submit(warm)

    def _score_fn(self, req):
        task = int(req.task_id.split(":")[1])
        r = self.run
        ctx = ScoreContext(PromptTuner(r.model, self.tune_cfg), r.params,
                           generator.eval_rows(r.seed, task, r.mix,
                                               r.cfg["vocab_size"]))
        return Recorder(ctx, self)

    def _submit(self, task: int):
        mix = self.run.mix
        self.calls = []
        with jax.profiler.TraceAnnotation("submit"):
            h = self.service.submit(SubmitRequest(
                task_id=f"task:{task}", llm=self.run.cfg["program_arch"],
                slo=mix["slo_s"], iters_manual=mix["iters_manual"],
                iters_bank=mix["iters_bank"]))
        t1 = time.perf_counter()
        ok = (h.routed_through_bank and h.initial_prompt is not None
              and bool(np.isfinite(h.bank_score)))
        return dict(task=task, calls=self.calls, picked=h.bank_origin,
                    ok=ok, t1=t1)

    def window(self, seconds: float):
        tasks = generator.lookup_tasks(self.run.seed, self.run.mix, 100000)
        self.lookups = []
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(reduce_trace.WINDOW):
            while True:
                lk = self._submit(tasks[len(self.lookups)])
                self.lookups.append(lk)
                if lk["t1"] - start >= seconds:
                    break
        return start, self.lookups[-1]["t1"]

    def results(self, window_s: float):
        lks, mix, cfg = self.lookups, self.run.mix, self.run.cfg
        calls = sum(len(lk["calls"]) for lk in lks)
        per_call = flops.forward(cfg, mix["eval_samples"], mix["prompt_len"],
                                 generator.seq_len(mix))
        return {
            "attempted": len(lks),
            "failed": sum(not lk["ok"] for lk in lks),
            "end_to_end": {"lookup_s": window_s / len(lks)},
            "counters": {"lookups": len(lks), "score_calls": calls,
                         "score_call_s": sum(c[2] for lk in lks
                                             for c in lk["calls"])},
            "required_flops": calls * per_call,
        }

    def take_for_check(self):
        """Host copies of what the check reads."""
        r, mix = self.run, self.run.mix
        idx = self.index
        lookups = [dict(task=lk["task"], picked=idx.get(lk["picked"]),
                        calls=[(idx[o], s) for o, s, _ in lk["calls"]])
                   for lk in self.lookups]
        rng = generator.rng(r.seed, "check")
        chosen = rng.choice(len(lookups), min(mix["check_lookups"],
                                              len(lookups)), replace=False)
        sample = []
        for li in sorted(chosen):
            lk = lookups[li]
            scored = dict(lk["calls"])
            others = [i for i in scored if i != lk["picked"]]
            k = min(mix["check_calls_per_lookup"] - 1, len(others))
            for i in [lk["picked"], *rng.choice(others, k, replace=False)]:
                sample.append(dict(task=lk["task"], prompt=self.bank.entries[
                    int(i)].prompt, score=scored[int(i)]))
        skipped = [i for i, e in enumerate(self.bank.entries)
                   if e.origin == "<evicted>"]
        return dict(lookups=lookups, sample=sample,
                    medoids=list(self.bank.medoid_ids),
                    clusters=[list(c) for c in self.bank.clusters],
                    skipped=skipped)

    @staticmethod
    def numbers(run_seed, cfg, mix, family, taken):
        """The compared numbers, from the program's records and the
        float32 reference."""
        ref = Reference(family, cfg, generator.key_words(run_seed))
        batches = [generator.eval_rows(run_seed, s["task"], mix,
                                       cfg["vocab_size"])
                   for s in taken["sample"]]
        ref_scores = ref.scores([s["prompt"] for s in taken["sample"]],
                                batches)
        return {
            "pick_errors": check.lookup_pick_errors(
                taken["lookups"], taken["medoids"], taken["clusters"],
                taken["skipped"]),
            "score_gap": check.widest_gap(
                [s["score"] for s in taken["sample"]], ref_scores),
        }


class TuneJobs:
    """Prompt-tuning jobs back to back through ``PromptTuner.tune``, each
    with its own tuner and loader (as ``bank_builder.measure_ita`` builds
    a job), from a random manual prompt: the Prompt Bank is bypassed.
    The window ends at the first step to complete after ``seconds``; the
    first job's first steps are recorded for the check."""

    spans = ("job_setup", "tune")
    RECORDED_STEPS = 3

    def __init__(self, run: Run):
        self.run = run
        self.steps = self.evals = self.failed = self.jobs = 0
        self.recorded = []

    def setup(self):
        r, mix = self.run, self.run.mix
        self.tune_cfg = TuneConfig(
            prompt_len=mix["prompt_len"], batch_size=mix["batch_size"],
            eval_every=mix["eval_every"], eval_samples=mix["eval_samples"],
            lr=mix["lr"], max_iters=mix["iters_max"])
        warm = int(generator.rng(r.seed, "warm").integers(mix["tasks"]))
        self._job(dict(job=-1, task=warm, iters=mix["eval_every"]),
                  record=False, deadline=None)

    def _job(self, job, *, record: bool, deadline):
        r, mix = self.run, self.run.mix
        with jax.profiler.TraceAnnotation("job_setup"):
            tuner = PromptTuner(r.model, self.tune_cfg)
            loader = generator.Loader(r.seed, job["task"], job["job"], mix,
                                      r.cfg["vocab_size"])
            p0 = {"soft_prompt": jnp.asarray(generator.prompt(
                r.seed, "job", job["job"], mix["prompt_len"],
                r.cfg["d_model"]))}
            step, score = tuner.step, tuner.score

            def counted_step(*args):
                out = step(*args)
                self.steps += 1
                if record and len(self.recorded) < self.RECORDED_STEPS:
                    pp, opt, loss = out
                    self.recorded.append(dict(
                        prompt=np.asarray(pp["soft_prompt"]),
                        mu=np.asarray(opt.mu["soft_prompt"]),
                        loss=float(loss)))
                if (deadline is not None and time.perf_counter() >= deadline
                        and len(self.recorded) >= self.RECORDED_STEPS):
                    jax.block_until_ready(out)
                    raise WindowEnd
                return out

            def counted_score(*args):
                self.evals += 1
                return score(*args)

            tuner.step, tuner.score = counted_step, counted_score
        with jax.profiler.TraceAnnotation("tune"):
            res = tuner.tune(r.params, loader, p0, max_iters=job["iters"],
                             eval_every=mix["eval_every"])
        return all(np.isfinite(ev) for _, _, ev in res["history"])

    def window(self, seconds: float):
        self.steps = self.evals = self.failed = self.jobs = 0
        self.recorded = []
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(reduce_trace.WINDOW):
            try:
                for job in generator.job_plan(self.run.seed, self.run.mix):
                    self.jobs += 1
                    ok = self._job(job, record=job["job"] == 0,
                                   deadline=start + seconds)
                    self.failed += not ok
            except WindowEnd:
                pass
        return start, time.perf_counter()

    def results(self, window_s: float):
        mix, cfg = self.run.mix, self.run.cfg
        S = generator.seq_len(mix)
        P, B = mix["prompt_len"], mix["batch_size"]
        return {
            "attempted": self.steps,
            "failed": self.failed,
            "end_to_end": {"tune_tokens_per_s": self.steps * B * S / window_s},
            "counters": {"steps": self.steps, "evals": self.evals,
                         "jobs": self.jobs},
            "required_flops": (
                self.steps * flops.tune_step(cfg, B, P, S)
                + self.evals * flops.forward(cfg, mix["eval_samples"], P, S)),
        }

    def take_for_check(self):
        job0 = generator.job_plan(self.run.seed, self.run.mix)[0]
        rec = self.recorded
        return dict(task=job0["task"], losses=[s["loss"] for s in rec],
                    first_grad=rec[0]["mu"] / 0.1,   # Adam: mu_1 = (1 - b1) g
                    prompt=rec[-1]["prompt"])

    @staticmethod
    def numbers(run_seed, cfg, mix, family, taken):
        ref = Reference(family, cfg, generator.key_words(run_seed))
        p0 = generator.prompt(run_seed, "job", 0, mix["prompt_len"],
                              cfg["d_model"])
        batches = [generator.train_batch(run_seed, taken["task"], 0, k, mix,
                                         cfg["vocab_size"])
                   for k in range(TuneJobs.RECORDED_STEPS)]
        losses, g1, p3 = adam_steps(ref, p0, batches, mix["lr"])
        return check.tune_numbers(p0, taken, dict(
            losses=losses, first_grad=np.asarray(g1), prompt=np.asarray(p3)))


DRIVERS = {"bank_lookup": BankLookup, "tune_jobs": TuneJobs}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def program_config(cfg_file: dict):
    """The program's ModelConfig as the configuration file states it.

    A top-level key that names a field sets it; others describe the
    configuration and are ignored. A dict under a field whose value is a
    dataclass (``moe``, ``mla``, ``ssm``, ``hybrid``, ...) replaces those
    sub-fields of the registry's value (or of the dataclass's defaults,
    where the registry has none); a sub-key that is not a field raises.
    A section the registry's architecture has must be in the file, since
    the benchmark's own arithmetic (``flops.py``) reads it from there."""
    base = get_config(cfg_file["program_arch"])
    hints = typing.get_type_hints(type(base))
    over = {}
    for f in dataclasses.fields(base):
        if f.name in NOT_MODEL_FIELDS:
            continue
        if f.name not in cfg_file:
            if f.name in SECTIONS and getattr(base, f.name) is not None:
                raise KeyError(f"configuration {cfg_file.get('name')!r} has "
                               f"no {f.name!r} section, which "
                               f"{cfg_file['program_arch']!r} has")
            continue
        value = cfg_file[f.name]
        if isinstance(value, dict):
            value = _section(f.name, getattr(base, f.name), hints[f.name],
                             value)
        over[f.name] = value
    return base.with_overrides(**over)


def _section(key: str, current, hint, values: dict):
    cls = type(current) if current is not None else next(
        (a for a in typing.get_args(hint) if dataclasses.is_dataclass(a)),
        None)
    if not dataclasses.is_dataclass(cls):
        raise TypeError(f"configuration key {key!r} is not a section")
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(values) - known)
    if unknown:
        raise KeyError(f"configuration section {key!r} has no field "
                       f"{unknown}; its fields are {sorted(known)}")
    return dataclasses.replace(cls() if current is None else current,
                               **values)


def stacked_groups(model):
    """Paths of the program's groups that stack layers, in layer order."""
    groups = tuple(s.name for s in model.segments)
    return groups + (("encoder/blocks",) if model.cfg.encdec else ())


def reference_family(cfg_file: dict):
    spec = importlib.util.spec_from_file_location(
        f"reference.{cfg_file['reference']}",
        os.path.join(BENCH, "reference", f"{cfg_file['reference']}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def peak_of(device_kind: str) -> dict:
    peaks = load_json(BENCH, "peaks.json")["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peak for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return peaks[device_kind]


def run(bench: dict, workload: str, seed: int, seconds: float,
        trace: bool) -> dict:
    """One run of one cell; returns the result object. ``setup_s``
    counts from the start of this module's import."""
    cell = next(c for c in bench["workloads"] if c["name"] == workload)
    cfg = load_json(BENCH, "configs", f"{cell['config']}.json")
    mix = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    limits = check.load_limits(workload)
    devices = jax.devices()
    dev = devices[0]
    peak = peak_of(dev.device_kind) if dev.platform == "tpu" else None

    # Every program in the cache, in a fixed directory of the checkout;
    # no size limit, so no eviction bookkeeping
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {enable_compile_cache()}")
    comp = CompileLog().install()

    model = build_model(program_config(cfg))
    words = generator.key_words(seed)
    params = weights.program_params(
        words, model.abstract_params(), stacked_groups(model),
        weights.expert_offset(cfg))
    jax.block_until_ready(params)
    driver = DRIVERS[mix["kind"]](Run(seed, cfg, mix, model, params))
    driver.setup()
    setup_s = time.perf_counter() - T0
    c0 = comp.snapshot()
    log(f"set-up {setup_s:.3f}s: {c0[0]} backend compiles or cache loads "
        f"({c0[2]:.3f}s), {c0[1]} cache hits")

    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(tmp)
    start, end = driver.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    window_s = end - start
    c1 = comp.snapshot()
    log(f"window {window_s:.3f}s; in it {c1[0] - c0[0]} backend compiles or "
        f"cache loads ({c1[2] - c0[2]:.3f}s), {c1[1] - c0[1]} cache hits")
    res = driver.results(window_s)
    log(f"counters {res['counters']}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(int((d.memory_stats() or {}).get(
                  "peak_bytes_in_use", 0)) for d in devices)}

    breakdown = None
    if trace:
        ops, spans = reduce_trace.load(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
        red = reduce_trace.reduce(ops, spans,
                                  (reduce_trace.WINDOW, *driver.spans))
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        ctx = {"cfg": cfg, "mix": mix,
               "counters": res["counters"], "window_s": window_s,
               "busy_s": red["busy_s"], "trace_window_s": red["window_s"],
               "required_flops": res["required_flops"],
               "peak_flops": peak["bf16_flops_per_s"] if peak else None}
        metrics = {}
        for m in bench["per_layer"]:
            if applies(m, workload):
                v = metric_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(res["end_to_end"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"] if applies(m, workload)}

    taken = driver.take_for_check()
    family = reference_family(cfg)
    del driver, params, model
    gc.collect()
    t1 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        numbers = DRIVERS[mix["kind"]].numbers(seed, cfg, mix, family, taken)
    log(f"reference check {time.perf_counter() - t1:.3f}s")
    correct, checks = check.verdict(numbers, limits)
    result = {"correct": correct, "attempted": res["attempted"],
              "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks          # last: the numbers and their limits
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((c for c in bench["workloads"]
                 if c["name"] == args.workload), None)
    if cell is None:
        print(f"bench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    devices = jax.devices()
    log(f"device platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)}; "
        f"jax {jax.__version__}")
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = run(bench, args.workload, args.seed, args.seconds,
                 bool(args.trace))
    for line in check.lines(result["checks"]):
        log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
