"""Smoke run of the tuning service's device path on a TPU.

    python chip_smoke.py              # one chip: the service's main path
    python chip_smoke.py --chips 4    # four chips: data-parallel tuning step

One process drives the chip(s). The model is GPT2-Large at its published
widths (``configs/gpt2_large.py``: 36 layers, d_model 1280, vocab 50257,
bf16) with random weights from a fixed seed; nothing is loaded from disk.

One chip, through the entry points a user of the service calls:

1. activation features of the Prompt Bank candidates
   (``build_bank_from_pretrain``);
2. ``PromptTunerService.submit``: latency-budget routing and the bank's
   two-layer lookup, which scores candidates with Eqn 1 on the chip;
3. ``PromptTuner.tune`` from the looked-up prompt;
4. a second ``submit`` carrying the tuned prompt, which
   ``run_until_idle`` inserts into the bank (N -> N+1);
5. check 1: the looked-up candidate's Eqn-1 score recomputed on the host
   CPU in float32 at the highest matmul precision;
6. check 2: the tuner's full-logits loss against the chunked-CE prefill
   step (``launch.steps.make_prefill_step``) on the same batch.

``--chips 4`` runs only the data-parallel tuning step
(``launch.steps.make_train_step`` over a 4-way ``data`` mesh) and the same
step on one device, in float32, from the same state at every step, and
compares loss, gradient moment and prompt.

Compile seconds, step time and peak device memory are printed for
information; they are readings of one run, not a benchmark. The last line
of standard output is one JSON object naming the device. Any failed phase
raises, and the process exits non-zero without that line. The script
refuses to run when JAX's default device is not a TPU.
"""
from __future__ import annotations

import argparse
import collections
import importlib.metadata
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

from repro.api import PromptTunerService, SubmitRequest
from repro.cluster import SimConfig
from repro.config import InputShape, ModelConfig, TuneConfig
from repro.configs import get_config
from repro.core.bank_builder import build_bank_from_pretrain, make_score_fn
from repro.data import LoaderConfig, TaskLoader, batch_to_jnp, make_tasks
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import data_axes, make_debug_mesh
from repro.launch.steps import (
    input_specs,
    make_prefill_step,
    make_train_step,
    step_shardings,
)
from repro.models import build_model
from repro.train.objectives import lpt_loss
from repro.train.pretrain import PretrainResult
from repro.tuning import PromptTuner, activation_features

LLM = "gpt2-large"
SEED = 0

# Check 1: chip (bf16 weights and activations, f32 logits and CE) vs the
# host CPU (the same weights cast to f32, highest matmul precision). bf16
# rounds at 2^-9 = 2e-3 relative. On the CPU at GPT2-Large widths with
# 2 and 6 layers, the bf16 mean loss sat 1.3e-3 and 7e-6 from the f32
# one (per example up to 4.2e-3); 36 layers add rounding steps, so the
# bound is 5x the 6-layer per-example worst. A wrong mask, shift or vocab
# tail moves the loss by more than this.
CPU_REF_RTOL = 2e-2
# Check 2: the same bf16 backbone on the same device; the two losses
# differ only in how the f32 cross-entropy is summed (one full-vocab
# logsumexp vs sequence chunks) and in the compiler's fusion of the last
# layers. f32 summation order is good to ~1e-6, so 1e-3 leaves ample room
# and still catches a chunk that is dropped or padded into the mean.
CHUNKED_RTOL = 1e-3
# Data-parallel (4 devices) vs one device, both float32 at the highest
# matmul precision: the runs differ only in the order of f32 reductions
# (a cross-device sum vs a microbatch scan), ~1e-6 relative.
DP_LOSS_RTOL = 1e-4
# Adam's first moment is (1 - b1) * gradient, so it compares gradients
# scale-sensitively: a sum-vs-mean slip or a lost shard moves it by O(1).
DP_GRAD_RTOL = 1e-3
# The prompt bound is on ||p_dp - p_1|| / ||p_1 - p_in||, the gap
# relative to the distance the step travelled. Adam's early steps are
# sign-like: an element whose gradient is within rounding of zero steps
# the other way and moves 2*lr, adding 0.014 to this ratio per element of
# the 20480; 5e-2 admits three such flips, a wrong shard gives O(1).
DP_PROMPT_RTOL = 5e-2


class SmokeError(RuntimeError):
    """A phase produced a wrong or missing result."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def _log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def _rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


class CompileLog:
    """Collects backend compile seconds per jitted function from JAX's
    monitoring events (a persistent-cache hit reports its load time)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = collections.defaultdict(float)
        self.cache_hits = 0

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.seconds[kw.get("fun_name", "?")] += duration

    def on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def install(self) -> "CompileLog":
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def report(self, top: int = 8) -> str:
        items = sorted(self.seconds.items(), key=lambda kv: -kv[1])
        head = ", ".join(f"{k}={v:.2f}s" for k, v in items[:top])
        return (f"compile seconds: total={sum(self.seconds.values()):.2f}s "
                f"over {len(items)} functions; {head}; "
                f"persistent-cache hits={self.cache_hits}")


# ---------------------------------------------------------------------------
# Phases (each runs at any width; tests drive them at smoke widths on CPU)
# ---------------------------------------------------------------------------


def make_fixture(cfg: ModelConfig, *, partitions: int, prompt_len: int,
                 input_len: int = 128, target_len: int = 128
                 ) -> PretrainResult:
    """Random weights from ``SEED`` through ``Model.init``, seeded tasks
    and seeded random per-task prompts, wrapped as a PretrainResult."""
    model = build_model(cfg)
    params = model.init(jax.random.key(SEED))
    tasks = make_tasks(vocab=32, partitions=partitions, input_len=input_len,
                       target_len=target_len)
    rng = np.random.default_rng(SEED)
    scale = 0.5 / np.sqrt(cfg.d_model)
    prompts = {t.task_id: rng.normal(0, scale, (prompt_len, cfg.d_model))
               .astype(np.float32) for t in tasks}
    return PretrainResult(model, params, prompts, tasks)


def phase_bank(pre: PretrainResult, *, variants: int):
    """Prompt Bank with activation features extracted on the device."""
    t0 = time.perf_counter()
    bank = build_bank_from_pretrain(pre, variants_per_prompt=variants,
                                    seed=SEED)
    secs = time.perf_counter() - t0
    feats = np.stack([e.feature for e in bank.entries])
    _require(feats.shape[0] == len(pre.tasks) * variants,
             f"bank holds {feats.shape[0]} candidates")
    _require(bool(np.isfinite(feats).all()), "non-finite activation feature")
    return bank, secs


def phase_submit(pre: PretrainResult, bank, tune_cfg: TuneConfig, task):
    """Service front door: routing + two-layer lookup (Eqn-1 scoring)."""
    by_id = {t.task_id: t for t in pre.tasks}
    score_fns = {}

    def score_fn_factory(req):
        if req.task_id not in score_fns:
            score_fns[req.task_id] = make_score_fn(pre, by_id[req.task_id],
                                                   tune_cfg)
        return score_fns[req.task_id]

    service = PromptTunerService(SimConfig(max_gpus=8), bank=bank,
                                 score_fn_factory=score_fn_factory)
    t0 = time.perf_counter()
    handle = service.submit(SubmitRequest(
        task_id=task.task_id, llm=LLM, slo=120.0, iters_manual=400,
        iters_bank=120))
    secs = time.perf_counter() - t0
    _require(handle.routed_through_bank, "request was not routed via bank")
    _require(handle.initial_prompt is not None, "lookup returned no prompt")
    _require(np.isfinite(handle.bank_score), "non-finite Eqn-1 score")
    return service, handle, score_fns[task.task_id], secs


def phase_tune(pre: PretrainResult, tuner: PromptTuner, task, init_prompt,
               *, steps: int, timed_steps: int):
    """``PromptTuner.tune`` from the looked-up prompt, then a timed loop
    of single steps, each ended by ``block_until_ready``."""
    loader = TaskLoader(task, LoaderConfig(
        batch_size=tuner.tune_cfg.batch_size, seed=SEED))
    p0 = {"soft_prompt": jnp.asarray(init_prompt)}
    res = tuner.tune(pre.params, loader, p0, max_iters=steps,
                     eval_every=max(steps // 2, 1))
    tuned = np.asarray(res["prompt"]["soft_prompt"])
    _require(res["iters"] == steps, f"tune ran {res['iters']} steps")
    _require(bool(np.isfinite(tuned).all()), "non-finite tuned prompt")
    _require(not np.array_equal(tuned, np.asarray(init_prompt)),
             "tuning left the prompt unchanged")
    for it, loss, ev in res["history"]:
        _require(np.isfinite(loss) and np.isfinite(ev),
                 f"non-finite loss at step {it}")
    pp, opt = res["prompt"], tuner.init_opt(res["prompt"])
    times = []
    for _ in range(timed_steps):
        batch = next(loader)
        t0 = time.perf_counter()
        pp, opt, loss = tuner.step(pp, opt, pre.params, batch)
        jax.block_until_ready((pp, loss))
        times.append(time.perf_counter() - t0)
    return tuned, res["history"], times


def phase_insert(service, pre: PretrainResult, task, tuned) -> tuple:
    """Second submit carrying the tuned prompt and its feature; the
    finished job inserts it into the bank."""
    feat = np.asarray(activation_features(pre.model, pre.params,
                                          jnp.asarray(tuned)))
    n0 = len(service.bank)
    service.submit(SubmitRequest(
        task_id=task.task_id, llm=LLM, slo=120.0, iters_manual=400,
        iters_bank=60, prompt=tuned, feature=feat))
    results = service.run_until_idle()
    n1 = len(service.bank)
    _require(len(results) == 2 and all(r.completed for r in results),
             f"jobs not all completed: {results}")
    _require(sum(r.inserted_to_bank for r in results) == 1,
             "tuned prompt was not inserted")
    _require(n1 == n0 + 1, f"bank went {n0} -> {n1}")
    return n0, n1


def check_cpu_reference(pre: PretrainResult, score_ctx, prompt,
                        chip_score: float, rtol: float = CPU_REF_RTOL):
    """Check 1: Eqn-1 score on the host CPU in float32, highest precision."""
    cpu = jax.devices("cpu")[0]
    cfg32 = pre.model.cfg.with_overrides(dtype="float32",
                                         param_dtype="float32")
    model32 = build_model(cfg32)
    P = int(np.asarray(prompt).shape[0])

    def reference_score(params, prompt, batch):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        return lpt_loss(model32, params, prompt, batch, P)[1][0]

    args = jax.device_put(
        (pre.params, jnp.asarray(prompt, jnp.float32),
         batch_to_jnp(score_ctx.eval_batch)), cpu)
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(reference_score)(*args))
    diff = _rel(chip_score, ref)
    _require(diff <= rtol, f"check 1: device {chip_score!r} vs CPU f32 "
             f"{ref!r}: rel {diff:.3e} > {rtol}")
    return ref, diff


def check_chunked_prefill(pre: PretrainResult, score_ctx, prompt, *,
                          ce_chunk: int = 64, rtol: float = CHUNKED_RTOL):
    """Check 2: the tuner's ``lpt_loss`` score vs ``make_prefill_step``
    (``lpt_loss_chunked``) on the same batch, both on the device."""
    pp = {"soft_prompt": jnp.asarray(prompt)}
    full = score_ctx.tuner.score(pp, pre.params, score_ctx.eval_batch)
    batch = batch_to_jnp(score_ctx.eval_batch)
    prefill = jax.jit(make_prefill_step(pre.model, ce_chunk=ce_chunk))
    per_ex = prefill(pre.params, pp, batch)
    ntok = batch["mask"].sum(axis=-1)
    chunked = float((per_ex * ntok).sum() / ntok.sum())
    diff = _rel(full, chunked)
    _require(diff <= rtol, f"check 2: lpt_loss {full!r} vs chunked "
             f"{chunked!r}: rel {diff:.3e} > {rtol}")
    return full, chunked, diff


def phase_data_parallel(cfg: ModelConfig, *, n_data: int, global_batch: int,
                        steps: int, tune_cfg: TuneConfig,
                        input_len: int = 128, target_len: int = 128):
    """``make_train_step`` on a ``data``-only mesh vs the same step on
    one device, which accumulates the gradient over ``n_data``
    microbatches, one per data shard. Every step starts both from the
    same prompt and optimizer state (the one-device trajectory's), with
    the same batch. Both run in float32 at the highest matmul precision,
    so they differ only in the order of f32 reductions. (The prompt
    gradient of this random-weight model amplifies a 1e-7 change of the
    prompt ~1e4-fold, so two trajectories left to run apart compare that
    amplification, not the sharding.)"""
    cfg = cfg.with_overrides(dtype="float32", param_dtype="float32")
    mesh = make_debug_mesh(data=n_data)
    task = make_tasks(vocab=32, partitions=1, input_len=input_len,
                      target_len=target_len)[1]
    loader = TaskLoader(task, LoaderConfig(batch_size=global_batch,
                                           seed=SEED))
    batches = [batch_to_jnp(next(loader)) for _ in range(steps)]
    seq = int(batches[0]["tokens"].shape[1])
    shape = InputShape("dp_smoke", seq, global_batch, "train")

    model = build_model(cfg, model_axis=1, data_axis=n_data, mesh=mesh)
    specs = input_specs(model, shape, tune_cfg)
    sh = step_shardings(model, shape, mesh, specs)
    # both steps take arrays placed as ``sh`` says (or on one device)
    dp_step, opt = make_train_step(model, tune_cfg,
                                   batch_axes=data_axes(mesh))
    one_model = build_model(cfg)
    one_step, _ = make_train_step(one_model, tune_cfg, microbatches=n_data)

    params = one_model.init(jax.random.key(SEED))
    dp_params = jax.device_put(params, sh["params"])
    rng = np.random.default_rng(SEED + 1)
    pp = {"soft_prompt": jnp.asarray(rng.normal(
        0, 0.5 / np.sqrt(cfg.d_model), (tune_cfg.prompt_len, cfg.d_model)),
        jnp.float32)}
    st = opt.init(pp)
    out = dict(dp=dict(losses=[], times=[]), one=dict(losses=[], times=[]),
               per_step=[], seq=seq)
    with jax.default_matmul_precision("highest"):
        for b in batches:
            start = np.asarray(pp["soft_prompt"])
            res = {}
            for name, step, args in [
                    ("dp", dp_step, (dp_params, *jax.device_put(
                        (pp, st, b), (sh["prompt_params"], sh["opt_state"],
                                      sh["batch"])))),
                    ("one", one_step, (params, pp, st, b))]:
                t0 = time.perf_counter()
                new_pp, new_st, loss = step(*args)
                res[name] = (float(loss), np.asarray(new_pp["soft_prompt"]),
                             np.asarray(new_st.mu["soft_prompt"]))
                out[name]["times"].append(time.perf_counter() - t0)
                out[name]["losses"].append(res[name][0])
                if name == "one":
                    pp, st = new_pp, new_st
            (l_dp, p_dp, mu_dp), (l_one, p_one, mu_one) = res["dp"], res["one"]
            out["per_step"].append(dict(
                loss=_rel(l_dp, l_one), grad=_rel_l2(mu_dp, mu_one),
                prompt=float(np.linalg.norm(p_dp - p_one)
                             / max(np.linalg.norm(p_one - start), 1e-30))))
    return out


def check_data_parallel(out) -> None:
    """Every step of the data-parallel run within tolerance of one device."""
    losses = out["dp"]["losses"] + out["one"]["losses"]
    _require(bool(np.isfinite(losses).all()), "non-finite data-parallel loss")
    for i, d in enumerate(out["per_step"], 1):
        _require(d["loss"] <= DP_LOSS_RTOL,
                 f"step {i}: dp loss rel {d['loss']:.3e} > {DP_LOSS_RTOL}")
        _require(d["grad"] <= DP_GRAD_RTOL,
                 f"step {i}: dp gradient moment rel L2 {d['grad']:.3e} > "
                 f"{DP_GRAD_RTOL}")
        _require(d["prompt"] <= DP_PROMPT_RTOL,
                 f"step {i}: dp prompt gap/distance {d['prompt']:.3e} > "
                 f"{DP_PROMPT_RTOL}")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


def _run_one_chip(cfg: ModelConfig) -> None:
    tune_cfg = TuneConfig(prompt_len=16, batch_size=16, eval_samples=16)
    pre = make_fixture(cfg, partitions=2,
                       prompt_len=tune_cfg.prompt_len)
    task = pre.tasks[5]
    _log(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
         f"{cfg.d_model}, vocab {cfg.vocab_size}, {cfg.dtype}; "
         f"{len(pre.tasks)} tasks, tokens per sequence "
         f"{task.input_len + task.target_len + 1}")

    bank, secs = phase_bank(pre, variants=4)
    _log(f"bank: {len(bank)} candidates, {len(bank.medoid_ids)} clusters, "
         f"features extracted in {secs:.2f}s")

    service, handle, score_ctx, secs = phase_submit(pre, bank, tune_cfg, task)
    _log(f"submit: routed_through_bank={handle.routed_through_bank}, picked "
         f"{handle.bank_origin} Eqn-1 score={handle.bank_score!r} "
         f"(lookup {secs:.2f}s)")

    tuned, history, times = phase_tune(pre, score_ctx.tuner, task,
                                       handle.initial_prompt, steps=20,
                                       timed_steps=10)
    _log(f"tune: 20 steps at batch {tune_cfg.batch_size}; (step, loss, "
         f"eval) = {history}")
    _log(f"tuning step median {statistics.median(times):.4f}s over "
         f"{len(times)} steps (smoke reading, not a benchmark)")

    n0, n1 = phase_insert(service, pre, task, tuned)
    _log(f"online insertion: bank {n0} -> {n1} entries")

    ref, diff = check_cpu_reference(pre, score_ctx, handle.initial_prompt,
                                    handle.bank_score)
    _log(f"check 1 (Eqn-1 score, device vs CPU f32 highest): device="
         f"{handle.bank_score!r} cpu={ref!r} rel={diff:.3e} "
         f"tol={CPU_REF_RTOL} PASS")
    full, chunked, diff = check_chunked_prefill(pre, score_ctx, tuned)
    _log(f"check 2 (lpt_loss vs chunked prefill, same batch): lpt_loss="
         f"{full!r} chunked={chunked!r} rel={diff:.3e} tol={CHUNKED_RTOL} "
         f"PASS")


def _run_data_parallel(cfg: ModelConfig, n: int) -> None:
    tune_cfg = TuneConfig(prompt_len=16)
    out = phase_data_parallel(cfg, n_data=n, global_batch=64, steps=3,
                              tune_cfg=tune_cfg)
    _log(f"data-parallel step on {n} devices vs one device ({n} "
         f"microbatches), global batch 64 x {out['seq']} tokens, prompt "
         f"{tune_cfg.prompt_len}, float32 at highest matmul precision")
    for name in ("dp", "one"):
        r = out[name]
        _log(f"{name}: losses={r['losses']!r} step seconds="
             f"{[round(t, 4) for t in r['times']]} (first includes compile)")
    for i, d in enumerate(out["per_step"], 1):
        _log(f"step {i}: loss rel={d['loss']:.3e} (tol {DP_LOSS_RTOL}), "
             f"gradient moment rel L2={d['grad']:.3e} (tol {DP_GRAD_RTOL}), "
             f"prompt gap/distance={d['prompt']:.3e} (tol {DP_PROMPT_RTOL})")
    check_data_parallel(out)
    _log("data-parallel vs one device: PASS")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    _log(f"device platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)}; jax {jax.__version__}, jaxlib "
         f"{jaxlib.__version__}, libtpu {libtpu}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    _log(f"compile cache: {enable_compile_cache()}")
    log = CompileLog().install()
    cfg = get_config(LLM)
    t0 = time.perf_counter()
    if args.chips == 1:
        _run_one_chip(cfg)
    else:
        _run_data_parallel(cfg, args.chips)
    _log(log.report())
    _log(f"peak_bytes_in_use={_peak_bytes(dev)}; wall "
         f"{time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
