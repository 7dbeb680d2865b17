"""Assemble a Prompt Bank from real artifacts and provide the initial-prompt
selection strategies compared in the paper (§6.1, Fig 9):

  * ``score``     — the Prompt Bank's two-layer lookup with Eqn 1.
  * ``ideal``     — shortlist by score, then pick best by *measured ITA*
                    (paper: computationally infeasible online; upper bound).
  * ``induction`` — automatic prompt generation by the LLM itself [88].
                    Our testbed analog: the model's own embedding of a
                    generic instruction (mean of related task prompts +
                    heavy noise, scaled by model capability) — it works
                    for simple tasks, degrades for weak models, mirroring
                    the paper's observation.
  * ``manual``    — a user-provided random prompt (current practice).
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import TuneConfig
from repro.core.prompt_bank import PromptBank, PromptEntry
from repro.data import LoaderConfig, TaskLoader, TaskSpec, batch_to_jnp
from repro.models import Model
from repro.obs.device import span
from repro.train.pretrain import PretrainResult
from repro.tuning import PromptTuner, activation_features


def build_bank_from_pretrain(
    pre: PretrainResult,
    *,
    variants_per_prompt: int = 8,
    noise_scales: Sequence[float] = (0.0, 0.05, 0.15, 0.3),
    num_clusters: int = 0,
    capacity: int = 3000,
    seed: int = 0,
) -> PromptBank:
    """Candidates = per-task optimized prompts + jittered variants (the
    public-prompt corpus analog: many prompts of varying quality/tasks).
    Features are REAL model activations."""
    rng = np.random.default_rng(seed)
    entries: List[PromptEntry] = []
    feats_batch: List[np.ndarray] = []
    prompts: List[np.ndarray] = []
    origins: List[str] = []
    for task_id, prompt in pre.task_prompts.items():
        for v in range(variants_per_prompt):
            scale = noise_scales[v % len(noise_scales)]
            noise = rng.normal(0, scale * (np.abs(prompt).mean() + 1e-6),
                               size=prompt.shape)
            prompts.append((prompt + noise).astype(np.float32))
            origins.append(f"{task_id}/v{v}")
    # batch feature extraction (one forward for all candidates)
    stacked = jnp.asarray(np.stack(prompts))
    feats = activation_features(pre.model, pre.params, stacked)
    feats = np.atleast_2d(np.asarray(feats))
    for p, o, f in zip(prompts, origins, feats):
        entries.append(PromptEntry(prompt=p, feature=f, origin=o))
    # cluster count ~ distinct task groups beats sqrt(C) here
    # (Fig 10b sweep: see bench_bank); paper uses K=50 at C~3000
    k = num_clusters or max(2, min(48, len(entries) // 4))
    bank = PromptBank(capacity=capacity, num_clusters=k, seed=seed)
    bank.add_candidates(entries)
    bank.build()
    return bank


@dataclass
class ScoreContext:
    """Binds Eqn-1 scoring to (model, task eval set)."""
    tuner: PromptTuner
    params: Dict
    eval_batch: Dict

    def __call__(self, entry: PromptEntry) -> float:
        with span("tuner.upload"):
            pp = {"soft_prompt": jnp.asarray(entry.prompt)}
        return self.tuner.score(pp, self.params, self.eval_batch)


def make_score_fn(pre: PretrainResult, task: TaskSpec, tune_cfg: TuneConfig,
                  loader: Optional[TaskLoader] = None) -> ScoreContext:
    loader = loader or TaskLoader(task, LoaderConfig(batch_size=tune_cfg.batch_size))
    tuner = PromptTuner(pre.model, tune_cfg)
    return ScoreContext(tuner, pre.params, loader.eval_batch(tune_cfg.eval_samples))


# ---------------------------------------------------------------------------
# Selection strategies
# ---------------------------------------------------------------------------


def select_score(bank: PromptBank, score_ctx: ScoreContext):
    """The Prompt Bank two-layer lookup."""
    return bank.lookup(score_ctx)


def select_ideal(
    bank: PromptBank,
    score_ctx: ScoreContext,
    measure_ita,
    shortlist: int = 20,
):
    """Paper's Ideal baseline: score-shortlist ``shortlist`` prompts then
    pick the one with best measured ITA (infeasible online)."""
    scored = []
    for e in bank.entries:
        if e.origin == "<evicted>":
            continue
        scored.append((score_ctx(e), e))
    scored.sort(key=lambda t: t[0])
    best_entry, best_ita = None, float("inf")
    for s, e in scored[:shortlist]:
        ita = measure_ita(e.prompt)
        if ita < best_ita:
            best_ita, best_entry = ita, e
    return best_entry, best_ita


def select_induction(
    pre: PretrainResult, task: TaskSpec, *, capability: float = 0.5, seed: int = 0
) -> np.ndarray:
    """Induction initialization [88]: the LLM generates its own initial
    prompt from demonstrations. Testbed analog: an imperfect recall of the
    family's optimized prompts — fidelity scales with model capability
    (bigger testbed LLM => better generated prompt), reproducing the
    paper's finding that induction relies on strong LLMs."""
    rng = np.random.default_rng(seed)
    related = [p for tid, p in pre.task_prompts.items()
               if tid.split(":")[0] == task.family]
    base = np.mean(related, axis=0) if related else list(pre.task_prompts.values())[0]
    noise_scale = (1.0 - capability) * 2.0 * (np.abs(base).mean() + 1e-6)
    return (base * capability + rng.normal(0, noise_scale, base.shape)).astype(
        np.float32
    )


def select_manual(pre: PretrainResult, seed: int = 0) -> np.ndarray:
    """Manual initialization: a generic, uninformed prompt."""
    rng = np.random.default_rng(seed)
    d = pre.model.cfg.d_model
    P = next(iter(pre.task_prompts.values())).shape[0]
    return (rng.normal(0, 0.5 / np.sqrt(d), (P, d))).astype(np.float32)


def measure_ita(
    pre: PretrainResult,
    task: TaskSpec,
    prompt: np.ndarray,
    tune_cfg: TuneConfig,
    *,
    target_loss: float,
    max_iters: int = 400,
) -> Tuple[int, bool]:
    """Iterations-To-Accuracy: REAL tuning run until eval loss target."""
    loader = TaskLoader(task, LoaderConfig(batch_size=tune_cfg.batch_size))
    tuner = PromptTuner(pre.model, tune_cfg)
    res = tuner.tune(
        pre.params, loader, {"soft_prompt": jnp.asarray(prompt)},
        target_loss=target_loss, max_iters=max_iters,
    )
    return res["iters"], res["reached"]
