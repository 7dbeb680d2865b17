"""Reduction of a JAX profiler trace to the device's busy time, its idle
share, and a breakdown: the device operations that took most time, and
the idle gaps by the benchmark's host span they fall in.

The window is the host span named ``WINDOW`` in the same trace, so host
and device intervals are read on one clock. Busy time is the union of
the intervals of the device's operations ("XLA Ops" lines of each
``/device:`` plane) that fall inside the window, averaged over the
chips. The per-op breakdown counts only innermost ops, so that a loop
and its body are not counted twice.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, List, Tuple

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[8,273]{...} fusion(...)`` -> ``fusion.12
    bf16[8,273]``: the op and the type of what it produces."""
    head, _, rest = hlo.partition(" = ")
    return f"{head.lstrip('%')} {rest.split('{')[0].split(' ')[0]}".strip()


def leaves(evs):
    """Events that contain no other event of the same line (a loop's op
    spans its body's ops)."""
    evs = sorted(evs, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or not (nxt[1] < e[2] and nxt[2] <= e[2])]


def load(path: str):
    """(device ops per chip {plane: [(name, start_s, end_s)]}, host spans
    [(name, start_s, end_s)]) from an ``.xplane.pb`` file or from the
    directory a trace was written to."""
    import jax
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{len(found)} .xplane.pb under {path}")
        path = found[0]
    data = jax.profiler.ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            evs = [(short_name(e.name), e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if evs:
                ops[plane.name] = evs
        elif plane.name.startswith("/host:CPU"):
            spans.extend((e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                         for line in plane.lines for e in line.events)
    return ops, spans


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def window_of(spans) -> Interval:
    found = [(a, b) for name, a, b in spans if name == WINDOW]
    if len(found) != 1:
        raise ValueError(f"{len(found)} host spans named {WINDOW!r}")
    return found[0]


def reduce(ops: Dict, spans, span_names, top: int = 10) -> Dict:
    """busy_s, window_s and the breakdown of one traced window."""
    lo, hi = window_of(spans)
    busy, per_op, gaps = [], collections.Counter(), collections.Counter()
    label = _labeller([(n, a, b) for n, a, b in spans if n in span_names])
    for evs in ops.values():
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in evs
                  if min(b, hi) > max(a, lo)]
        for n, a, b in leaves(inside):
            per_op[n] += b - a
        merged = union([(a, b) for _, a, b in inside])
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[label((a + b) / 2)] += b - a
    n = max(len(ops), 1)
    return {
        "busy_s": sum(busy) / n,
        "window_s": hi - lo,
        "device_ops": [[k, v / n] for k, v in per_op.most_common(top)],
        "idle_gaps": [[k, v / n] for k, v in gaps.most_common(top)],
    }


def _labeller(marks):
    """Function from a time to the name of the innermost marked host
    span around it (spans nest: the shortest one that covers it)."""
    cuts = sorted({t for _, a, b in marks for t in (a, b)})
    names = []
    for lo, hi in zip(cuts, cuts[1:]):
        mid = (lo + hi) / 2
        inner = min(((b - a, n) for n, a, b in marks if a <= mid <= b),
                    default=None)
        names.append(inner[1] if inner else "outside spans")

    def label(t):
        i = bisect.bisect_right(cuts, t) - 1
        return names[i] if 0 <= i < len(names) else "outside spans"
    return label
