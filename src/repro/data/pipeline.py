"""Host-side data pipeline: deterministic shard-aware batching.

For multi-host SPMD the loader yields per-host shards of the global batch
(host h takes rows [h*B/H, (h+1)*B/H)); on this single-process testbed the
host count is 1 and the loader degrades to simple batching. Prefetch is a
simple double-buffer (thread-free: CPU-bound synthetic generation)."""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Dict, Iterator, Optional

import numpy as np

from repro.data.synthetic import TaskSpec, sample_batch


@dataclass
class LoaderConfig:
    batch_size: int = 8
    seed: int = 0
    host_id: int = 0
    num_hosts: int = 1


def _task_key(spec: TaskSpec) -> int:
    # zlib.crc32, not hash(): str hashing is salted per process, which
    # made every process draw different batches from the same seed
    return zlib.crc32(spec.task_id.encode())


class TaskLoader:
    """Infinite iterator of batches for one LPT task."""

    def __init__(self, spec: TaskSpec, cfg: LoaderConfig):
        assert cfg.batch_size % cfg.num_hosts == 0
        self.spec = spec
        self.cfg = cfg
        self._rng = np.random.default_rng(
            np.random.SeedSequence([cfg.seed, _task_key(spec)])
        )

    def __iter__(self) -> Iterator[Dict]:
        return self

    def __next__(self) -> Dict:
        global_b = self.cfg.batch_size
        batch = sample_batch(self.spec, self._rng, global_b)
        per = global_b // self.cfg.num_hosts
        lo = self.cfg.host_id * per
        return {k: v[lo : lo + per] for k, v in batch.items()}

    def eval_batch(self, n: int, seed: int = 1234) -> Dict:
        """Fixed evaluation set (the Eqn-1 D_eval, e.g. 16 samples)."""
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, _task_key(self.spec)])
        )
        return sample_batch(self.spec, rng, n)
