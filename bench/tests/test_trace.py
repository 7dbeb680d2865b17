"""Trace reduction: busy time as the union of device-op intervals, idle
gaps attributed to the innermost marked host span, on synthetic
intervals and on a small trace recorded on a v5e chip
(``data/small.xplane.pb``, made by ``record_trace.py``)."""
import os

import pytest

import reduce_trace as rt

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


def test_union_merges_overlaps():
    assert rt.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                              (3, 4)]


def test_reduce_on_synthetic_intervals():
    ops = {"/device:TPU:0": [("a", 1.0, 2.0), ("b", 1.5, 3.0),
                             ("a", 5.0, 6.0), ("c", 9.0, 12.0)]}
    spans = [(rt.WINDOW, 0.0, 10.0), ("submit", 0.01, 10.0),
             ("score", 0.6, 3.5), ("score", 3.8, 7.0)]
    red = rt.reduce(ops, spans, (rt.WINDOW, "submit", "score"))
    assert red["window_s"] == 10.0
    assert red["busy_s"] == pytest.approx(2.0 + 1.0 + 1.0)
    assert dict(red["device_ops"]) == pytest.approx({"a": 2.0, "b": 1.5,
                                                     "c": 1.0})
    # gaps: 0-1 (mid 0.5, before the first score -> submit), 3-5 (mid 4
    # -> score), 6-9 (mid 7.5 -> submit)
    assert dict(red["idle_gaps"]) == pytest.approx({"submit": 4.0,
                                                    "score": 2.0})


def test_reduce_on_recorded_chip_trace():
    ops, spans = rt.load(DATA)
    assert ops and all(p.startswith("/device:TPU") for p in ops)
    red = rt.reduce(ops, spans, (rt.WINDOW, "submit", "score"))
    assert 0 < red["busy_s"] < red["window_s"]
    gaps = dict(red["idle_gaps"])
    # the recorder sleeps 20 ms inside each of three spans
    assert gaps.get("score", 0) + gaps.get("submit", 0) >= 0.055
    assert sum(v for _, v in red["device_ops"]) >= red["busy_s"] * 0.99
    assert all(len(name) < 80 for name, _ in red["device_ops"])


def test_nested_ops_counted_once():
    ops = {"/device:TPU:0": [("while.1", 0.0, 4.0), ("fusion.1", 0.5, 1.5),
                             ("fusion.2", 2.0, 3.5)]}
    red = rt.reduce(ops, [(rt.WINDOW, 0.0, 5.0)], (rt.WINDOW,))
    assert red["busy_s"] == pytest.approx(4.0)
    assert dict(red["device_ops"]) == pytest.approx({"fusion.1": 1.0,
                                                     "fusion.2": 1.5})


def test_short_names():
    assert rt.short_name("%fusion.174 = bf16[16,273,1280]{2,1,0:T(8,128)}"
                         " fusion(bf16[16,273,1280]{2,1,0})") == \
        "fusion.174 bf16[16,273,1280]"
    assert rt.short_name("custom-call") == "custom-call"
