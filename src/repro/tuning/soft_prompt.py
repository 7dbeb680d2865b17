"""Soft prompt tuning [Lester et al. '21] / prefix-style reparameterized
variant [Li & Liang '21] — the LPT algorithms the paper schedules.

The tunable object is a continuous prompt ``(P, d_model)`` prepended to
the embedded input. Model weights stay FROZEN: gradients are taken w.r.t.
the prompt parameters only, which is why LPT's cross-GPU gradient payload
is tiny (paper §2.2: 0.4-0.5% comm overhead).

``PromptTuner`` also implements Eqn 1's ``score`` (mean eval loss of a
candidate prompt WITHOUT tuning) used by the Prompt Bank, and the
``activation_features`` extractor used for bank clustering.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, TuneConfig
from repro.data import TaskLoader, batch_to_jnp
from repro.models import Model
from repro.obs.device import span
from repro.train import apply_updates, lpt_loss, make_optimizer
from repro.train.remat import GradProgram


def init_prompt_from_tokens(model: Model, params, token_ids: jax.Array):
    """Initialize the soft prompt from token embeddings (the 'initial
    prompt' a user provides as text; Fig 1 step 1)."""
    emb = jnp.take(params["embedding"], token_ids, axis=0)
    return {"soft_prompt": emb.astype(jnp.float32)}


def init_prompt_random(model: Model, prompt_len: int, key: jax.Array):
    d = model.cfg.d_model
    scale = 0.5 / np.sqrt(d)
    return {
        "soft_prompt": jax.random.normal(key, (prompt_len, d), jnp.float32) * scale
    }


@dataclass
class PromptTuner:
    model: Model
    tune_cfg: TuneConfig

    def __post_init__(self):
        self.optimizer = make_optimizer(
            self.tune_cfg.optimizer, self.tune_cfg.lr, self.tune_cfg.weight_decay
        )
        self._score = jax.jit(self._loss_fn(self.model))
        # the step keeps what the chip can hold for its backward pass
        self._step = GradProgram(self._step_fn, self.model,
                                 key=("PromptTuner.step", self.tune_cfg))

    def _loss_fn(self, model: Model):
        P = self.tune_cfg.prompt_len

        def loss_fn(prompt_params, params, batch):
            prompt = self._materialize_prompt(prompt_params, params)
            return lpt_loss(model, params, prompt, batch, P)

        return loss_fn

    def _step_fn(self, model: Model):
        grad = jax.value_and_grad(self._loss_fn(model), has_aux=True)

        def step(prompt_params, opt_state, params, batch):
            (tot, (loss, _)), grads = grad(prompt_params, params, batch)
            updates, opt_state = self.optimizer.update(grads, opt_state, prompt_params)
            prompt_params = apply_updates(prompt_params, updates)
            return prompt_params, opt_state, loss

        return step

    # prefix variant: reparameterize the prompt through a small MLP
    def _materialize_prompt(self, prompt_params, params):
        sp = prompt_params["soft_prompt"]
        if self.tune_cfg.algorithm == "prefix" and "reparam_w" in prompt_params:
            h = jnp.tanh(sp @ prompt_params["reparam_w"])
            sp = sp + h @ prompt_params["reparam_v"]
        return sp

    def init_prompt(self, params, key: jax.Array, token_ids=None):
        if token_ids is not None:
            pp = init_prompt_from_tokens(self.model, params, token_ids)
        else:
            pp = init_prompt_random(self.model, self.tune_cfg.prompt_len, key)
        if self.tune_cfg.algorithm == "prefix":
            d = self.model.cfg.d_model
            k1, k2 = jax.random.split(key)
            r = max(d // 4, 8)
            pp["reparam_w"] = jax.random.normal(k1, (d, r), jnp.float32) * 0.02
            pp["reparam_v"] = jax.random.normal(k2, (r, d), jnp.float32) * 0.02
        return pp

    def init_opt(self, prompt_params):
        return self.optimizer.init(prompt_params)

    def step(self, prompt_params, opt_state, params, batch):
        with span("tuner.upload"):
            batch = batch_to_jnp(batch)
        with span("tuner.dispatch"):
            return self._step(prompt_params, opt_state, params, batch)

    def score(self, prompt_params, params, eval_batch) -> float:
        """Eqn 1: mean loss on D_eval, no tuning. Smaller is better."""
        with span("tuner.upload"):
            eval_batch = batch_to_jnp(eval_batch)
        with span("tuner.dispatch"):
            tot, (loss, _) = self._score(prompt_params, params, eval_batch)
        with span("tuner.sync"):
            return float(loss)

    def evaluate(self, prompt_params, params, eval_batch) -> float:
        return self.score(prompt_params, params, eval_batch)

    # ------------------------------------------------------------------
    def tune(
        self,
        params,
        loader: TaskLoader,
        prompt_params,
        *,
        target_loss: Optional[float] = None,
        max_iters: Optional[int] = None,
        eval_every: Optional[int] = None,
    ) -> Dict[str, Any]:
        """Run LPT until the termination condition (Table 3): accuracy
        target (here: eval-loss target) or max iterations.

        Returns {prompt, iters, reached, history}."""
        max_iters = max_iters or self.tune_cfg.max_iters
        eval_every = eval_every or self.tune_cfg.eval_every
        with span("tune.job"):
            eval_batch = loader.eval_batch(self.tune_cfg.eval_samples)
            opt_state = self.init_opt(prompt_params)
            history = []
            reached = False
            it = 0
            # the initial prompt may already meet the target (ITA = 0) —
            # the whole point of prompt reusing
            if target_loss is not None:
                with span("tune.eval"):
                    ev0 = self.score(prompt_params, params, eval_batch)
                history.append((0, float("nan"), ev0))
                if ev0 <= target_loss:
                    return {"prompt": prompt_params, "iters": 0,
                            "reached": True, "history": history}
            for it in range(1, max_iters + 1):
                with span("tune.batch"):
                    batch = next(loader)
                with span("tune.step"):
                    prompt_params, opt_state, loss = self.step(
                        prompt_params, opt_state, params, batch
                    )
                if it % eval_every == 0:
                    with span("tune.eval"):
                        ev = self.score(prompt_params, params, eval_batch)
                    with span("tuner.sync"):
                        loss = float(loss)
                    history.append((it, loss, ev))
                    if target_loss is not None and ev <= target_loss:
                        reached = True
                        break
        return {
            "prompt": prompt_params,
            "iters": it,
            "reached": reached,
            "history": history,
        }


def _probe_tokens(model: Model, n_probe: int, length: int) -> jax.Array:
    """Fixed probe inputs shared by all feature extractions (so features
    of different prompts are comparable)."""
    key = jax.random.key(20240517)
    lo, hi = 3, model.cfg.vocab_size // 2 + 3
    return jax.random.randint(key, (n_probe, length), lo, hi)


def activation_features(
    model: Model, params, prompt: jax.Array, *, n_probe: int = 4,
    probe_len: int = 9,
) -> np.ndarray:
    """Prompt Bank clustering feature (§4.3.1 'activation features').

    The LLM runs on ``[prompt, probe tokens]`` for a handful of FIXED
    probe inputs; the feature is the concatenated final-position hidden
    state per probe — i.e. the model's prediction state under this
    prompt, which directly encodes the behaviour the prompt induces.
    (Pooling over a dummy input alone clusters by prompt norm, not by
    task — measured: family-mixed clusters and 20x worse two-layer
    lookups.)"""
    if prompt.ndim == 2:
        prompt = prompt[None]
    B, P, d = prompt.shape
    probes = _probe_tokens(model, n_probe, probe_len)     # (n, L)
    n, L = probes.shape
    tokens = jnp.broadcast_to(probes[None], (B, n, L)).reshape(B * n, L)
    prompt_rep = jnp.repeat(prompt, n, axis=0)            # (B*n, P, d)
    frontend = None
    if model.cfg.frontend.kind != "none":
        frontend = jnp.zeros(
            (B * n, model.cfg.frontend.num_embeddings,
             model.cfg.frontend.embed_dim),
            jnp.float32,
        )
    hidden, _ = model.backbone(params, tokens, prompt=prompt_rep,
                               frontend=frontend)
    feat = hidden[:, -1].astype(jnp.float32)              # prediction state
    feat = feat.reshape(B, n * feat.shape[-1])
    feat = feat / (jnp.linalg.norm(feat, axis=-1, keepdims=True) + 1e-8)
    return np.asarray(feat[0] if B == 1 else feat)
