"""The one traffic generator: every input a cell feeds the system, drawn
from ``--seed`` and the parameters of the cell's traffic file
(``bench/traffic/<name>.json``).

Nothing here imports the program. The same seed gives the same rows,
prompts and job plans, so the reference can draw them again on its own.
A "task" is a seeded token-level transform; its rows follow the
program's LPT layout::

    tokens = [BOS, x_1..x_I, SEP, y_1..y_{T-1}]     (I + T + 1 positions)
    labels = tokens shifted left by one; mask = 1 on the target region
"""
from __future__ import annotations

import statistics
import zlib
from typing import Dict, List

import numpy as np

BOS, SEP, FIRST_ID = 2, 1, 3


def _word(part) -> int:
    if isinstance(part, str):
        return zlib.crc32(part.encode())
    return int(part) % 2**64


def rng(seed: int, *stream) -> np.random.Generator:
    """Independent generator for one named stream of one seed."""
    return np.random.default_rng(
        np.random.SeedSequence([_word(seed), *(_word(s) for s in stream)]))


def key_words(seed: int) -> np.ndarray:
    """Two 32-bit words for a threefry key, from the full seed."""
    return np.random.SeedSequence(_word(seed)).generate_state(2).astype(
        np.uint32)


def task_rows(seed: int, task: int, stream, n: int, mix: Dict,
              vocab: int) -> Dict[str, np.ndarray]:
    """``n`` rows of task ``task``: inputs uniform over the vocabulary,
    targets a per-task affine map of the inputs."""
    I, T = mix["input_len"], mix["target_len"]
    span = vocab - FIRST_ID
    a, b = rng(seed, "task", task).integers(1, span, 2)
    r = rng(seed, "rows", task, *stream)
    x = r.integers(0, span, (n, I))
    y = (x[:, np.arange(T) % I] * a + b) % span
    seq = np.concatenate([np.full((n, 1), BOS), x + FIRST_ID,
                          np.full((n, 1), SEP), y + FIRST_ID], axis=1)
    mask = np.zeros((n, seq_len(mix)), np.float32)
    mask[:, I + 1:] = 1.0
    return {"tokens": seq[:, :-1].astype(np.int32),
            "labels": seq[:, 1:].astype(np.int32), "mask": mask}


def seq_len(mix: Dict) -> int:
    """Task positions per row (prompt positions excluded)."""
    return mix["input_len"] + mix["target_len"] + 1


def eval_rows(seed: int, task: int, mix: Dict, vocab: int):
    """The task's fixed Eqn-1 evaluation set."""
    return task_rows(seed, task, ("eval",), mix["eval_samples"], mix, vocab)


class Loader:
    """Batches of one job, in the duck type ``PromptTuner.tune`` reads
    (``next(loader)`` and ``loader.eval_batch(n)``)."""

    def __init__(self, seed: int, task: int, job: int, mix: Dict,
                 vocab: int):
        self.seed, self.task, self.job = seed, task, job
        self.mix, self.vocab = mix, vocab
        self.drawn = 0

    def __iter__(self):
        return self

    def __next__(self):
        b = train_batch(self.seed, self.task, self.job, self.drawn,
                        self.mix, self.vocab)
        self.drawn += 1
        return b

    def eval_batch(self, n: int):
        return task_rows(self.seed, self.task, ("eval",), n, self.mix,
                         self.vocab)


def train_batch(seed, task, job, k, mix, vocab):
    """Batch ``k`` (from 0) of job ``job``."""
    return task_rows(seed, task, ("train", job, k), mix["batch_size"], mix,
                     vocab)


def prompt(seed: int, what: str, index: int, prompt_len: int,
           d_model: int) -> np.ndarray:
    """A random soft prompt at the program's manual-prompt scale."""
    return rng(seed, "prompt", what, index).normal(
        0.0, 0.5 / np.sqrt(d_model), (prompt_len, d_model)).astype(np.float32)


def job_plan(seed: int, mix: Dict) -> List[Dict]:
    """Tuning jobs: one fixed set of iteration counts (lognormal
    quantiles), in an order drawn from the seed, each on a seeded task."""
    n = mix["jobs"]
    nd = statistics.NormalDist()
    iters = [int(np.clip(round(mix["iters_median"] * np.exp(
        mix["iters_sigma"] * nd.inv_cdf((i + 0.5) / n))),
        mix["iters_min"], mix["iters_max"])) for i in range(n)]
    order = rng(seed, "jobs").permutation(n)
    tasks = rng(seed, "job_tasks").integers(0, mix["tasks"], n)
    return [{"job": j, "task": int(tasks[j]), "iters": iters[order[j]]}
            for j in range(n)]


def lookup_tasks(seed: int, mix: Dict, n: int) -> List[int]:
    """Tasks of the routed jobs, in submission order."""
    return [int(t) for t in rng(seed, "lookups").integers(0, mix["tasks"], n)]
