"""End-to-end driver: prompt-tune a ~100M-parameter qwen2-family model for
a few hundred steps on CPU, with checkpointing — the full training path a
production job runs (model def -> data -> LPT step -> eval -> ckpt).

    PYTHONPATH=src python examples/train_lpt_e2e.py [--steps 300]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import TuneConfig
from repro.configs import get_config
from repro.data import LoaderConfig, TaskLoader, TaskSpec, batch_to_jnp
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.train.checkpoint import save_checkpoint


def hundred_m_config():
    """qwen2-family scaled to ~100M params (assigned arch reduced in
    width/depth, same structure: GQA + QKV bias + SwiGLU)."""
    return get_config("qwen2-7b").with_overrides(
        num_layers=12, d_model=640, num_heads=10, num_kv_heads=2,
        head_dim=64, d_ff=2560, vocab_size=16384, max_seq_len=512,
        dtype="float32", param_dtype="float32", remat=False,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default="artifacts/e2e_prompt.npz")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = hundred_m_config()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(params))
    print(f"model: qwen2-family {n_params / 1e6:.0f}M params, "
          f"{cfg.num_layers}L d{cfg.d_model}")

    task = TaskSpec("shift", 3, vocab=256, input_len=12, target_len=12)
    loader = TaskLoader(task, LoaderConfig(batch_size=args.batch))
    tune_cfg = TuneConfig(prompt_len=16, lr=0.3, batch_size=args.batch)
    step, opt = make_train_step(model, tune_cfg)

    key = jax.random.key(1)
    prompt = {"soft_prompt": jax.random.normal(
        key, (tune_cfg.prompt_len, cfg.d_model)) * 0.02}
    opt_state = opt.init(prompt)

    eval_b = batch_to_jnp(loader.eval_batch(16))
    t0 = time.time()
    for it in range(1, args.steps + 1):
        batch = batch_to_jnp(next(loader))
        prompt, opt_state, loss = step(params, prompt, opt_state, batch)
        if it % 25 == 0 or it == 1:
            rate = it / (time.time() - t0)
            print(f"step {it:4d}  loss {float(loss):.4f}  "
                  f"({rate:.2f} steps/s)")
    save_checkpoint(args.ckpt, prompt, step=args.steps,
                    meta={"task": task.task_id, "arch": "qwen2-100m"})
    print(f"prompt checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
