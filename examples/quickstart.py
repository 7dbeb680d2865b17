"""Quickstart: LPT requests through the PromptTunerService front door.

    PYTHONPATH=src python examples/quickstart.py

The service ties the paper's pieces into one API (§4):

1. Load the pretrained testbed LLM (trains + caches on first run) and
   build the Prompt Bank (two-layer K-medoid over activation features).
2. Stand up ``PromptTunerService`` — bank + Eqn-1 scorer + scheduling
   policy behind a single ``submit`` / ``run_until_idle`` surface.
3. ``submit`` an LPT request: the §4.4.3 latency budget routes it
   through the bank, whose two-layer lookup picks the initial prompt.
4. Tune for real from the looked-up prompt vs. a manual (random) one;
   compare ITA — the paper's headline win.
5. Submit a follow-up request carrying the freshly tuned prompt: when
   its job finishes, the service inserts it into the bank (Fig 5b's
   online loop), so later similar requests start from it.
6. ``telemetry=True`` wires the fleet telemetry plane into the same
   front door: per-job lifecycle spans (``handle.timeline()``) and the
   SLO-attainment time-series report.
"""
import sys
import time

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro.api import PromptTunerService, SubmitRequest
from repro.cluster import SimConfig
from repro.config import TuneConfig
from repro.core.bank_builder import (
    build_bank_from_pretrain,
    make_score_fn,
    select_manual,
)
from repro.core.prompt_bank import PromptBank
from repro.data import LoaderConfig, TaskLoader
from repro.launch.compile_cache import enable_compile_cache
from repro.train.pretrain import pretrain
from repro.tuning import PromptTuner, activation_features


def main():
    enable_compile_cache()
    print("== 1. testbed LLM + Prompt Bank")
    pre = pretrain("gpt2-base", cache=True)
    t0 = time.time()
    bank = build_bank_from_pretrain(pre, variants_per_prompt=4)
    print(f"   {len(pre.tasks)} tasks, d_model={pre.model.cfg.d_model}; "
          f"bank: {len(bank)} candidates, {len(bank.medoid_ids)} clusters, "
          f"built in {time.time() - t0:.1f}s")

    task = pre.tasks[17]
    tune_cfg = TuneConfig(lr=0.5, batch_size=16, eval_every=5)
    # hold out the task's own optimized prompts: the bank must TRANSFER
    # prompts from similar tasks (the paper's premise)
    holdout = PromptBank(capacity=bank.capacity,
                         num_clusters=bank.num_clusters)
    holdout.add_candidates([e for e in bank.entries
                            if not e.origin.startswith(task.task_id + "/")])
    holdout.build()

    print("== 2. PromptTunerService front door")
    tasks_by_id = {t.task_id: t for t in pre.tasks}

    def score_factory(req):
        """Eqn-1 bound to the request's task eval set."""
        return make_score_fn(pre, tasks_by_id[req.task_id], tune_cfg)

    service = PromptTunerService(SimConfig(max_gpus=8), bank=holdout,
                                 score_fn_factory=score_factory,
                                 telemetry=True)

    print("== 3. submit: latency budget -> two-layer lookup (Eqn-1)")
    t0 = time.time()
    handle = service.submit(SubmitRequest(
        task_id=task.task_id, llm="gpt2-base", slo=60.0,
        iters_manual=400, iters_bank=120))
    print(f"   task={task.task_id}, SLO=60s, routed={handle.routed_through_bank}")
    print(f"   picked {handle.bank_origin} score={handle.bank_score:.3f} "
          f"({time.time() - t0:.1f}s; flat search would score "
          f"all {len(holdout)})")

    print("== 4. prompt tuning to target (bank init vs manual init)")
    loader = TaskLoader(task, LoaderConfig(batch_size=16))
    tuner = PromptTuner(pre.model, tune_cfg)
    own = tuner.score({"soft_prompt": jnp.asarray(
        pre.task_prompts[task.task_id])}, pre.params,
        loader.eval_batch(16))
    target = own * 1.5 + 0.05

    t0 = time.time()
    res_bank = tuner.tune(pre.params, loader,
                          {"soft_prompt": jnp.asarray(handle.initial_prompt)},
                          target_loss=target, max_iters=400)
    t_bank = time.time() - t0
    t0 = time.time()
    res_manual = tuner.tune(
        pre.params, loader,
        {"soft_prompt": jnp.asarray(select_manual(pre, seed=7))},
        target_loss=target, max_iters=400)
    t_manual = time.time() - t0
    print(f"   bank   init: ITA={res_bank['iters']:4d} "
          f"(reached={res_bank['reached']}, {t_bank:.0f}s)")
    print(f"   manual init: ITA={res_manual['iters']:4d} "
          f"(reached={res_manual['reached']}, {t_manual:.0f}s)")
    print(f"   ITA speedup from prompt reusing: "
          f"{res_manual['iters'] / max(res_bank['iters'], 1):.2f}x")

    print("== 5. online insertion (Fig 5b): tuned prompt -> bank")
    tuned = np.asarray(res_bank["prompt"]["soft_prompt"])
    feat = np.asarray(activation_features(
        pre.model, pre.params, jnp.asarray(tuned)))
    size0 = len(holdout)
    service.submit(SubmitRequest(
        task_id=task.task_id, llm="gpt2-base", slo=120.0,
        iters_manual=res_manual["iters"], iters_bank=res_bank["iters"],
        prompt=tuned, feature=feat))
    results = service.run_until_idle()
    done = [r for r in results if r.inserted_to_bank]
    print(f"   {len(results)} jobs scheduled+finished "
          f"(SLO violations: {sum(r.violated for r in results)}); "
          f"bank {size0} -> {len(holdout)} entries "
          f"({len(done)} fresh prompt inserted online)")
    print(f"   service summary: {service.summary()}")

    print("== 6. telemetry: per-job spans + SLO-attainment report")
    tl = handle.timeline()
    phases = ", ".join(f"{s.phase}={s.duration:.1f}s" for s in tl.spans
                       if s.end is not None)
    print(f"   job {tl.job_id} on shard {tl.shard}: {phases}")
    print(service.report(title="SLO attainment over time (quickstart)"))


if __name__ == "__main__":
    main()
