"""Synthetic task families + loader: layout, determinism, invariants."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.data import (
    FAMILIES,
    LoaderConfig,
    TaskLoader,
    TaskSpec,
    make_tasks,
    sample_batch,
    task_similarity,
)
from repro.data.synthetic import BOS, N_SPECIAL, SEP, _apply_family


def test_twelve_families_ten_partitions():
    tasks = make_tasks(partitions=10)
    assert len(tasks) == 120              # the paper's 12 datasets x 10
    assert len({t.family for t in tasks}) == 12


@pytest.mark.parametrize("family", FAMILIES)
def test_families_are_deterministic_per_token_maps(family):
    rng = np.random.default_rng(0)
    x = rng.integers(0, 32, size=(4, 8))
    y1 = _apply_family(family, 2, x, 32)
    y2 = _apply_family(family, 2, x, 32)
    assert (y1 == y2).all()
    assert y1.shape == x.shape
    assert ((y1 >= 0) & (y1 < 32)).all()


@pytest.mark.parametrize("family", FAMILIES)
def test_families_differ_across_params(family):
    rng = np.random.default_rng(1)
    x = rng.integers(0, 32, size=(8, 8))
    y0 = _apply_family(family, 0, x, 32)
    y1 = _apply_family(family, 1, x, 32)
    assert (y0 != y1).any(), f"{family}: params 0 and 1 give identical tasks"


def test_batch_layout():
    spec = TaskSpec("shift", 1, 32, input_len=8, target_len=8)
    b = sample_batch(spec, np.random.default_rng(0), 4)
    T = 1 + 8 + 1 + 8 - 1                # BOS x SEP y, minus last shift
    assert b["tokens"].shape == (4, T)
    assert b["tokens"][0, 0] == BOS
    assert b["tokens"][0, 9] == SEP
    # mask covers exactly the target region
    assert b["mask"].sum() == 4 * 8
    assert (b["mask"][:, :9] == 0).all()
    # labels are tokens shifted by one
    assert (b["labels"][:, :-1] == b["tokens"][:, 1:]).all()
    # data tokens sit above the specials
    assert (b["tokens"][:, 1:9] >= N_SPECIAL).all()


def test_loader_determinism_and_eval_fixture():
    spec = TaskSpec("xor", 3, 32)
    l1 = TaskLoader(spec, LoaderConfig(batch_size=4, seed=7))
    l2 = TaskLoader(spec, LoaderConfig(batch_size=4, seed=7))
    b1, b2 = next(l1), next(l2)
    assert (b1["tokens"] == b2["tokens"]).all()
    e1 = l1.eval_batch(16)
    e2 = l2.eval_batch(16)
    assert (e1["tokens"] == e2["tokens"]).all()   # fixed D_eval


def test_host_sharded_loader_partitions_batch():
    spec = TaskSpec("shift", 1, 32)
    full = TaskLoader(spec, LoaderConfig(batch_size=8, seed=3))
    h0 = TaskLoader(spec, LoaderConfig(batch_size=8, seed=3, host_id=0,
                                       num_hosts=2))
    h1 = TaskLoader(spec, LoaderConfig(batch_size=8, seed=3, host_id=1,
                                       num_hosts=2))
    bf, b0, b1 = next(full), next(h0), next(h1)
    assert (np.concatenate([b0["tokens"], b1["tokens"]]) ==
            bf["tokens"]).all()


def test_task_similarity_structure():
    a = TaskSpec("shift", 1, 32)
    b = TaskSpec("shift", 2, 32)
    c = TaskSpec("xor", 1, 32)
    assert task_similarity(a, a) == 1.0
    assert 0 < task_similarity(a, b) < 1
    assert task_similarity(a, c) == 0.0


@settings(max_examples=20, deadline=None)
@given(family=st.sampled_from(FAMILIES), param=st.integers(0, 9),
       seed=st.integers(0, 999))
def test_family_property_bounded_alphabet(family, param, seed):
    x = np.random.default_rng(seed).integers(0, 32, size=(3, 8))
    y = _apply_family(family, param, x, 32)
    assert ((y >= 0) & (y < 32)).all()


def test_task_loader_batches_do_not_depend_on_the_process():
    """Same seed, same task -> same batches in another interpreter, whose
    str hashing is salted differently."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("from repro.data import LoaderConfig, TaskLoader, make_tasks;"
            "t = make_tasks(partitions=2)[7];"
            "print(next(TaskLoader(t, LoaderConfig(batch_size=2)))"
            "['tokens'].tolist())")
    env = {**os.environ, "PYTHONPATH": os.path.join(root, "src"),
           "JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "12345"}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    here = next(TaskLoader(make_tasks(partitions=2)[7],
                           LoaderConfig(batch_size=2)))["tokens"].tolist()
    assert r.stdout.strip() == str(here)
