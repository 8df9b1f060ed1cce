"""Per-layer metric readers on synthetic records, and the trace reduction
on a synthetic trace and on a small trace recorded on the chip."""
from __future__ import annotations

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
import tracereduce  # noqa: E402

MS = 1_000_000  # ns


def _ctx(**over):
    ctx = dict(trace=dict(window_s=2.0, busy_s=1.5, chips=1,
                          window_program=dict(count=4, seconds=1.2),
                          window_gaps_s=[0.01, 0.03], device_ops=[],
                          idle_gaps=[]),
               compiles_in_window=0, window_ticks=1500,
               timelines=[dict(ticks=3000, servers=8, admit_s=0.1,
                               run_s=0.9),
                          dict(ticks=3000, servers=8, admit_s=0.3,
                               run_s=0.9)])
    ctx.update(over)
    return ctx


def test_tick_device_us():
    assert run._reader("tick_device_us")(_ctx()) == pytest.approx(200.0)
    # 1.2 s of window programs over 4 windows of 1,500 fleet ticks
    empty = _ctx(trace=dict(_ctx()["trace"],
                            window_program=dict(count=0, seconds=0.0)))
    assert run._reader("tick_device_us")(empty) is None


def test_device_idle_pct():
    assert run._reader("device_idle_pct")(_ctx()) == pytest.approx(25.0)
    idle = _ctx(trace=dict(_ctx()["trace"], busy_s=0.0))
    assert run._reader("device_idle_pct")(idle) is None


def test_host_gap_ms():
    assert run._reader("host_gap_ms")(_ctx()) == pytest.approx(20.0)
    none = _ctx(trace=dict(_ctx()["trace"], window_gaps_s=[]))
    assert run._reader("host_gap_ms")(none) is None


def test_admit_ms():
    assert run._reader("admit_ms")(_ctx()) == pytest.approx(200.0)
    none = _ctx(timelines=[dict(ticks=6000, servers=8, admit_s=0.0,
                                run_s=17.0)])
    assert run._reader("admit_ms")(none) is None


def test_compiles_in_window():
    assert run._reader("compiles_in_window")(_ctx()) == 0.0
    assert run._reader("compiles_in_window")(
        _ctx(compiles_in_window=3)) == 3.0


def test_every_per_layer_metric_has_a_reader():
    for m in run.benchmark()["per_layer"]:
        assert callable(run._reader(m["name"]))


def test_reduce_synthetic():
    """A traced span of three window programs with a small device program
    between two of them; the engine call of each window is a host span."""
    wp = "jit__run_core"
    raw = dict(
        devices={"/device:TPU:0": dict(
            modules=[[wp, 10 * MS, 40 * MS], [wp, 60 * MS, 30 * MS],
                     ["jit_convert", 100 * MS, 5 * MS],
                     [wp, 120 * MS, 40 * MS],
                     [wp, 198 * MS, 1 * MS]])},   # cut by the span's end
        host=[["bench.span", 0.0, 200 * MS],
              ["bench.engine_call", 5 * MS, 6 * MS],
              ["bench.engine_call", 55 * MS, 6 * MS],
              ["bench.engine_call", 115 * MS, 6 * MS],
              ["bench.closing_call", 194 * MS, 6 * MS]])
    red = tracereduce.reduce(raw)
    assert red["window_s"] == pytest.approx(0.2)
    assert red["busy_s"] == pytest.approx(0.116)  # the cut program's 1 ms
    assert red["window_program"]["count"] == 3
    assert red["window_program"]["seconds"] == pytest.approx(0.11)
    # program end to the next engine call's return: 50 -> 61, 90 -> 121,
    # 160 -> 200 (the closing call)
    assert red["window_gaps_s"] == pytest.approx([0.011, 0.031, 0.040])
    labels = {lbl for lbl, _s in red["idle_gaps"]}
    assert tracereduce.SPAN_LABELS["bench.span"] in labels
    assert tracereduce.SPAN_LABELS["bench.engine_call"] in labels
    assert red["idle_gaps"][0][1] >= red["idle_gaps"][-1][1]
    assert sum(s for _l, s in red["idle_gaps"]) == pytest.approx(0.084)
    assert tracereduce.SPAN_LABELS["bench.closing_call"] in labels


def test_reduce_recorded_chip_trace():
    """A trace recorded on the chip (``testdata``): three 500-tick windows
    of an eight-server fleet under churn, cut to one timeline's events.
    The reduction reads any cell's trace alike."""
    with gzip.open(os.path.join(HERE, "testdata", "mica8_churn_trace.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    red = tracereduce.reduce(rec["raw"])
    for k, v in rec["expect"].items():
        assert red[k] == pytest.approx(v), k
    assert red["window_program"]["count"] == rec["expect_windows"]
    assert 0.0 < red["busy_s"] <= red["window_s"]
