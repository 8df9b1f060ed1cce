"""The program's spans read back from a host trace, and the split of the
host gap and of the tick on synthetic traces and on a trace recorded on
the chip."""
from __future__ import annotations

import bisect
import collections
import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import spansplit  # noqa: E402
import tracereduce  # noqa: E402

MS = 1_000_000  # ns


def _spec(fid, slo_gbps):
    from repro.core.flow import SLO, FlowSpec, Path, TrafficPattern
    return FlowSpec(fid, fid, Path.FUNCTION_CALL, 0,
                    TrafficPattern(1024, load=0.3, process="poisson"),
                    SLO.gbps(slo_gbps))


def test_program_spans_read_back(tmp_path, monkeypatch):
    """A tiny fleet run (2 servers, 3 windows, one arrival) under the
    profiler: every ``arcus.*`` span appears as often as its layer ran;
    prepare and dispatch nest in each engine call, and each poll follows
    its dispatch."""
    import jax
    from repro.core import engine
    from repro.core.accelerator import CATALOG
    from repro.core.controller import FleetController, TenantEvent
    from repro.core.profiler import ProfileTable, profiling_stats
    from repro.core.runtime import ArcusRuntime

    profile = ProfileTable(n_ticks=2_000)
    ctrl = FleetController([ArcusRuntime([CATALOG["synthetic50"]],
                                         profile_table=profile)
                            for _ in range(2)])
    inner = engine.run_window_batch

    def engine_call(*a, **kw):
        with jax.profiler.TraceAnnotation("bench.engine_call"):
            return inner(*a, **kw)
    monkeypatch.setattr(engine, "run_window_batch", engine_call)
    stats0 = profiling_stats()
    jax.profiler.start_trace(str(tmp_path))
    try:
        ctrl.admit_fleet([[_spec(0, 2.0)], [_spec(1, 2.0)]])
        _res, reports = ctrl.run(
            total_ticks=600, window_ticks=200, seeds=[1, 2],
            load_ref_gbps={0: 32.0},
            events=[TenantEvent.arrive(1, _spec(100, 2.0),
                                       accel_name="synthetic50")])
    finally:
        jax.profiler.stop_trace()
    raw = spansplit.read_spans(tracereduce.find_xplane(str(tmp_path)))
    host = sorted(raw["host"], key=lambda e: e[1])
    count = collections.Counter(n for n, _s, _d in host)
    arrived = [e for e in ctrl.last_events if e["kind"] == "arrive"]
    assert arrived and arrived[0]["server"] is not None
    stats = profiling_stats()
    # every engine call: the three windows and admission's and the
    # arrival's profiling batches
    n_calls = 3 + stats["sim_batches"] - stats0["sim_batches"]
    # lane tables are built for window 0, for the window the arrival
    # touched, and for window 2 if a server re-planned after window 1
    repacks = 2 + any(rep[1].reconfigured or rep[1].path_changes
                      for rep in reports)
    assert {k: v for k, v in count.items() if k.startswith("arcus.")} == {
        "arcus.fleet.admit": 1,
        "arcus.profile.contexts": stats["calls"] - stats0["calls"],
        "arcus.fleet.event": 1,
        "arcus.fleet.lanes": repacks,
        "arcus.engine.prepare": n_calls, "arcus.engine.dispatch": n_calls,
        "arcus.fleet.poll": 3, "arcus.fleet.pass": 3,
        "arcus.fleet.control": 2, "arcus.fleet.collect": 1}
    assert count["bench.engine_call"] == n_calls

    def of(name):
        return [(s, s + d) for n, s, d in host if n == name]
    calls, preps, disps, polls = (of("bench.engine_call"),
                                  of("arcus.engine.prepare"),
                                  of("arcus.engine.dispatch"),
                                  of("arcus.fleet.poll"))
    for (c0, c1), (p0, p1), (d0, d1) in zip(calls, preps, disps):
        assert c0 <= p0 < p1 <= d0 < d1 <= c1
    # each poll follows a dispatch of its own
    ends = [d1 for _d0, d1 in disps]
    seen = -1
    for q0, _q1 in polls:
        k = bisect.bisect_right(ends, q0) - 1
        assert k > seen
        seen = k


def _raw(**over):
    """A traced span of two window programs (10-40 and 60-90 ms) and the
    closing call's program, with the program's spans between them."""
    wp = "jit__run_core"
    raw = dict(
        devices={"/device:TPU:0": dict(
            modules=[[wp, 10 * MS, 30 * MS], [wp, 60 * MS, 30 * MS],
                     [wp, 110 * MS, 30 * MS]],
            ops=[["while", 10 * MS, 4 * MS, "other"],
                 ["fusion.1", 10 * MS, 1 * MS, "intake"],
                 ["fusion.2", 11 * MS, 1 * MS, "grant"],
                 ["fusion.3", 12 * MS, 1 * MS, "service"],
                 ["fusion.4", 13 * MS, 0.5 * MS, "egress"]])},
        host=[["bench.span", 0.0, 100 * MS],
              ["bench.engine_call", 5 * MS, 6 * MS],
              ["arcus.engine.prepare", 5 * MS, 4 * MS],
              ["arcus.engine.dispatch", 9 * MS, 1 * MS],
              ["arcus.fleet.poll", 11 * MS, 31 * MS],      # 2 ms past 40
              ["arcus.fleet.pass", 42 * MS, 3 * MS],
              ["arcus.fleet.control", 45 * MS, 1 * MS],
              ["bench.engine_call", 50 * MS, 12 * MS],
              ["arcus.engine.prepare", 50 * MS, 10 * MS],
              ["arcus.engine.dispatch", 60 * MS, 1 * MS],
              ["arcus.fleet.poll", 62 * MS, 29 * MS],      # 1 ms past 90
              ["arcus.fleet.pass", 91 * MS, 2 * MS],
              ["arcus.fleet.event", 93 * MS, 2 * MS],
              ["arcus.profile.contexts", 93.5 * MS, 1 * MS],
              ["bench.closing_call", 96 * MS, 4 * MS],
              ["arcus.engine.prepare", 96 * MS, 3 * MS],
              ["arcus.engine.dispatch", 99 * MS, 0.5 * MS],
              ["arcus.fleet.pass", 150 * MS, 1 * MS]])      # outside
    raw.update(over)
    return raw


def test_split_synthetic():
    out = spansplit.split(_raw(), window_ticks=1_000)
    assert out["spans"]["arcus.engine.prepare"] == dict(
        count=3, mean_ms=pytest.approx(17 / 3))
    assert out["spans"]["arcus.fleet.pass"]["count"] == 2
    assert out["poll_ms"] == pytest.approx(1.5)
    # gaps 40 -> 62 and 90 -> 100 (the engine calls' returns)
    g = out["gaps"]
    assert g["count"] == 2
    assert g["gap_ms"] == pytest.approx(16.0)
    # the first gap: poll 2 + pass 3 + control 1 + prepare 10 + dispatch 1;
    # the second: poll 1 + pass 2 + event 2 (its profiling nests in it) +
    # prepare 3 + dispatch 0.5; each over two gaps
    assert g["parts_ms"] == pytest.approx({
        "arcus.fleet.poll": 1.5, "arcus.fleet.pass": 2.5,
        "arcus.fleet.control": 0.5, "arcus.fleet.event": 1.0,
        "arcus.engine.prepare": 6.5, "arcus.engine.dispatch": 0.75})
    assert g["unattributed_ms"] == pytest.approx(16.0 - 12.75)
    # self time: the loop (4 ms) less the stages' 3.5 ms it contains
    st = out["stages"]
    assert st["seconds"] == pytest.approx(dict(
        intake=1e-3, grant=1e-3, service=1e-3, egress=5e-4, other=5e-4))
    assert st["tick_device_us"] == pytest.approx(30.0)
    assert st["us"]["grant"] == pytest.approx(7.5)
    assert st["us"]["other"] == pytest.approx(3.75)
    assert sum(st["us"].values()) == pytest.approx(30.0)


def test_split_without_program_spans_or_scopes():
    """A trace of a program without spans or scopes: nothing of the split
    is reported."""
    raw = _raw()
    raw["host"] = [h for h in raw["host"] if h[0].startswith("bench.")]
    for op in raw["devices"]["/device:TPU:0"]["ops"]:
        op[3] = "other"
    assert spansplit.split(raw, window_ticks=1_000) == {}
    assert spansplit.split(dict(devices={}, host=[]), 1_000) == {}


def test_scopes_from_names_and_hlo():
    assert spansplit.scope_of(
        "jit(_run_core)/vmap(while)/body/closed_call/grant/cond/add") \
        == "grant"
    assert spansplit.scope_of("jit(_run_core)/while/body/add") == "other"
    assert spansplit.scope_of("jit(f)/granted/x") == "other"
    hlo = "\n".join([
        '  %add.7 = s32[] add(%p0, %p1), metadata={op_name='
        '"jit(_run_core)/while/body/add"}',
        '  ROOT %sin.2 = f32[8]{0} sine(%a), metadata={op_name='
        '"jit(_run_core)/while/body/closed_call/egress/sin" '
        'stack_frame_id=5}',
        '  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(_run_core)/while/body/intake/mul"}',
        '  %copy.1 = s32[] copy(%x)'])
    assert spansplit.hlo_scopes(hlo) == {
        "add.7": "other", "sin.2": "egress", "fusion.3": "intake"}


def test_reduce_recorded_trace_keeps_its_values():
    """The recorded trace of ``tracereduce``'s tests reduces as before
    beside the new split: ``tracereduce`` reads only ``bench.*`` spans."""
    with gzip.open(os.path.join(HERE, "testdata", "mica8_churn_trace.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    red = tracereduce.reduce(rec["raw"])
    for k, v in rec["expect"].items():
        assert red[k] == pytest.approx(v), k
    assert spansplit.split(rec["raw"], window_ticks=500) == {}


def test_split_recorded_chip_trace():
    """A trace recorded on the chip (``testdata``): windows 1 and 2 of a
    ``mica8.fig11a`` timeline with the program's spans, and one tick's
    operations of the first window program with their stages.  The spans
    account for the host gap ``tracereduce`` measures, and the stages for
    the tick's device time."""
    with gzip.open(os.path.join(HERE, "testdata", "mica8_fig11a_spans.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    out = spansplit.split(rec["raw"], rec["window_ticks"])
    exp = rec["expect"]
    gaps = tracereduce.reduce(rec["raw"])["window_gaps_s"]
    assert gaps == pytest.approx(exp["window_gaps_s"])
    g = out["gaps"]
    assert g["gap_ms"] == pytest.approx(1e3 * sum(gaps) / len(gaps))
    assert g["parts_ms"] == pytest.approx(exp["gaps"]["parts_ms"])
    assert g["unattributed_ms"] == pytest.approx(exp["gaps"]["unattributed_ms"])
    assert g["unattributed_ms"] < 0.1 * g["gap_ms"]
    assert max(g["parts_ms"], key=g["parts_ms"].get) == "arcus.engine.prepare"
    assert out["poll_ms"] == pytest.approx(exp["poll_ms"])
    for name, s in exp["spans"].items():
        assert out["spans"][name]["count"] == s["count"]
        assert out["spans"][name]["mean_ms"] == pytest.approx(s["mean_ms"])
    st = out["stages"]
    assert st["us"] == pytest.approx(exp["stages_us"])
    assert (sum(st["us"][k] for k in spansplit.STAGES)
            >= 0.75 * st["tick_device_us"])
