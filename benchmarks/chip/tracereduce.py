"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``read_xplane`` turns one ``.xplane.pb`` into a small JSON-able record:
per device plane, the program executions (the ``XLA Modules`` line), and
the benchmark's own host spans (``bench.*`` ``TraceAnnotation`` names).
``reduce`` turns that record into busy and span seconds, the engine
window program's device time, the idle gaps between consecutive window
programs, and the breakdown's top programs and longest idle gaps.  ``testdata/`` holds a small
recorded trace that the tests reduce.
"""
from __future__ import annotations

import glob
import os

#: the engine's jitted window program (``engine.run_window_batch`` jits
#: ``_run_core``); XLA names its module after the jitted function
WINDOW_PROGRAM = "_run_core"

#: host spans the benchmark opens inside a traced span: the span itself
#: (``bench.span``, inside ``FleetController.run``), each engine call, and
#: the engine call after which the span closes
SPAN_LABELS = {
    "bench.engine_call": "engine call (run_window_batch: tables, carry, "
                         "dispatch)",
    "bench.closing_call": "engine call (run_window_batch: tables, carry, "
                          "dispatch)",
    "bench.span": "controller between engine calls (poll, fleet pass, "
                  "control, events, re-pack)",
}


def _merge(intervals):
    """Union of [start, end) intervals, sorted and merged."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def read_xplane(path: str) -> dict:
    """Program executions and benchmark spans of one profiler trace (ns).

    Only the ``XLA Modules`` line is read: the ``XLA Ops`` line holds
    every operation of every tick, millions of events a second of device
    time.  Busy time is then the union of the programs that ran."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    devices[plane.name] = dict(modules=[
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return dict(devices=devices, host=host)


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def _label(t: float, host: list) -> str:
    """What the benchmark was doing at device-idle instant ``t``: the
    innermost of its host spans that covers it."""
    best = None
    for name, s, d in host:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return SPAN_LABELS.get(best[0], best[0]) if best else "outside the span"


def reduce(raw: dict, pattern: str = WINDOW_PROGRAM) -> dict:
    """Busy, window-program and gap seconds of the traced span (the
    ``bench.span`` host span; without one, the span of all device
    events), averaged over device planes.  The span lies inside one
    timeline and ends once the engine window after its last one has been
    dispatched, so each window program in it is followed by one host gap
    between engine windows."""
    host = raw["host"]
    win = [(s, s + d) for n, s, d in host if n == "bench.span"]
    devs = raw["devices"]
    if not devs:
        return dict(window_s=0.0, busy_s=0.0, chips=0,
                    window_program=dict(count=0, seconds=0.0),
                    window_gaps_s=[], device_ops=[], idle_gaps=[])
    if win:
        lo, hi = win[0]
    else:
        ev = [(s, s + du) for d in devs.values() for _n, s, du in d["modules"]]
        lo, hi = min(s for s, _ in ev), max(e for _, e in ev)
    calls = sorted((s, s + d) for n, s, d in host
                   if n in ("bench.engine_call", "bench.closing_call"))
    # the program that the closing call dispatched runs past the span
    cut = min([s for n, s, d in host if n == "bench.closing_call"],
              default=hi)
    busy, wp_n, wp_s, gaps, idle, ops = 0.0, 0, 0.0, [], [], {}
    for d in devs.values():
        mods = [m for m in d["modules"] if lo <= m[1] < hi]
        union = _clip(_merge([[s, s + du] for _n, s, du in mods]), lo, hi)
        busy += sum(e - s for s, e in union)
        wins = sorted((s, s + du) for n, s, du in mods
                      if pattern in n and s < cut)
        wp_n += len(wins)
        wp_s += sum(e - s for s, e in wins)
        # a host gap: from the end of a window program to the return of
        # the next engine call, which dispatches the next window program
        for s0, e0 in wins:
            nxt = [e for s, e in calls if s > s0]
            if nxt and nxt[0] > e0:
                gaps.append((nxt[0] - e0) * 1e-9)
        prev = lo
        for s, e in union + [[hi, hi]]:
            if s > prev:
                idle.append([_label((prev + s) / 2, host), (s - prev) * 1e-9])
            prev = max(prev, e)
        for name, _s, du in mods:
            key = name.split("(")[0]
            ops[key] = ops.get(key, 0.0) + du
    n = len(devs)
    return dict(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n, chips=n,
        window_program=dict(count=wp_n // n, seconds=wp_s * 1e-9 / n),
        window_gaps_s=gaps,
        device_ops=[[k, v * 1e-9 / n] for k, v in
                    sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        idle_gaps=sorted(idle, key=lambda g: -g[1])[:10])
