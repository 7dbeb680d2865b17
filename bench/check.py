"""The numbers that decide ``correct``, each against its limit.

The limits of a cell are in ``bench/limits/<workload>.json``, with the
readings each was set from. A number passes when it is finite and at
most its limit.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, List, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_limits(workload: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "limits", f"{workload}.json")) as f:
        spec = json.load(f)
    return {name: float(v["limit"]) for name, v in spec["numbers"].items()}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}) over the cell's limits."""
    missing = set(limits) - set(numbers)
    if missing:
        raise KeyError(f"no reading for {sorted(missing)}")
    checks = {k: {"value": float(numbers[k]), "limit": limits[k]}
              for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


# -- Prompt Bank lookup ------------------------------------------------------


def lookup_pick_errors(lookups: Sequence[Dict], medoids: Sequence[int],
                       clusters: Sequence[Sequence[int]],
                       skipped: Sequence[int] = ()) -> int:
    """Lookups that scored another sequence of candidates, or picked
    another one, than the two-layer lookup does with the scores they
    got: every medoid in order; then, in the cluster of the first
    lowest-scoring medoid, every member but the medoid and the
    ``skipped`` (evicted) ones; the pick is the first lowest score of
    the medoid and those members."""
    errors = 0
    for lk in lookups:
        calls = lk["calls"]              # [(candidate index, score), ...]
        head = [s for _, s in calls[:len(medoids)]]
        best = int(np.argmin(head)) if head else 0
        members = [i for i in clusters[best]
                   if i != medoids[best] and i not in skipped]
        expected = list(medoids) + members
        pick, low = medoids[best], head[best] if head else math.inf
        for i, s in calls[len(medoids):]:
            if s < low:
                pick, low = i, s
        if [i for i, _ in calls] != expected or pick != lk["picked"]:
            errors += 1
    return errors


def widest_gap(program: Sequence[float], reference: Sequence[float]):
    return max(abs(a - b) for a, b in zip(program, reference))


# -- prompt tuning -------------------------------------------------------------


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-30)


def tune_numbers(p0, program: Dict, reference: Dict) -> Dict[str, float]:
    """Program against reference over a job's first steps, each side
    given as ``losses`` (per step), ``first_grad`` (the first gradient)
    and ``prompt`` (after the last step):

    - loss_gap: the widest relative gap of a step's loss;
    - grad_norm_gap: relative gap of the first gradient's norm;
    - update_norm_gap: relative gap of the norm of the prompt's change.
    """
    p0 = np.asarray(p0, np.float64)
    norm = lambda x: float(np.linalg.norm(np.asarray(x, np.float64)))
    return {
        "loss_gap": max(_rel(a, b) for a, b in
                        zip(program["losses"], reference["losses"])),
        "grad_norm_gap": _rel(norm(program["first_grad"]),
                              norm(reference["first_grad"])),
        "update_norm_gap": _rel(norm(np.asarray(program["prompt"]) - p0),
                                norm(np.asarray(reference["prompt"]) - p0)),
    }


def lines(checks: Dict) -> List[str]:
    return [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
            for k, v in checks.items()]
