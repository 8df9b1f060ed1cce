#!/usr/bin/env python3
"""Split of the host gap and of the tick by the program's own spans and
named scopes, read from one JAX profiler trace.

The program marks its layers with ``arcus.*`` host spans
(``FleetController.run``'s poll, fleet pass, control, events and lane
tables; ``engine.run_window_batch``'s prepare and dispatch; admission and
profiling) and the stages of the engine's tick with named scopes
(``STAGES``).  ``read_spans`` keeps those spans and the benchmark's
``bench.*`` spans, the program executions, and a bounded slice of the first
window program's operations; ``split`` turns them into

- ``spans``: count and mean milliseconds of each ``arcus.*`` span in the
  traced span;
- ``gaps``: each host gap between window programs (as ``tracereduce``
  measures it: from a window program's end to the return of the next
  engine call) cut into the outermost program spans that overlap it, and
  what no span covers;
- ``poll_ms``: from a window program's end on the device to the end of
  the counter poll that waits for it;
- ``stages``: operation seconds per named scope in the slice (self time:
  an operation that contains others counts only its own), ``other`` for
  unscoped operations such as loop control, and each scope's share of
  ``tick_device_us``.

Run on the chip, it records one traced timeline of a cell (the span the
cell's mix names, as a ``--trace 1`` run of ``run.py`` records it), keeps
the trace, and prints the split as its last line:

    python3 benchmarks/chip/spansplit.py --workload mica8.fig11a \\
        --seed 7 --out bench_out/spans
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import json
import os
import re
import sys
import time

import tracereduce

#: named scopes of the engine's tick (``engine._tick``)
STAGES = ("intake", "grant", "service", "egress")
#: how much of the first window program's operations is read (ns): the
#: operation line holds every operation of every tick
SLICE_NS = 20e6

_SCOPE = re.compile(r"(?:^|/)(" + "|".join(STAGES) + r")(?:/|$)")
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The tick stage an operation's name path lies in, or ``other``."""
    m = _SCOPE.search(op_name or "")
    return m.group(1) if m else "other"


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> tick stage, from the ``op_name`` metadata of a
    compiled program's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line)
        if m:
            out[m.group(1)] = scope_of(m.group(2))
    return out


def read_spans(path: str, *, scopes: dict[str, str] | None = None,
               slice_ns: float = SLICE_NS) -> dict:
    """Spans, program executions and the operation slice of one trace (ns).

    An operation is kept by its instruction name, with its interval cut
    at the slice's end and the stage that ``scopes`` (``hlo_scopes`` of the
    window program) gives it, else ``other``: the trace's operation events
    carry no op-name metadata."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("arcus.", "bench.")):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
        elif plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Modules" not in lines:
                continue
            mods = [[e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in lines["XLA Modules"].events]
            ops = []
            wins = [m for m in mods if tracereduce.WINDOW_PROGRAM in m[0]]
            if wins and "XLA Ops" in lines:
                lo = wins[0][1]
                hi = lo + slice_ns
                for e in lines["XLA Ops"].events:
                    if e.start_ns < lo:
                        continue
                    if e.start_ns >= hi:
                        break
                    # "%fusion.12 = s32[8,3]{...} fusion(...)" on a TPU
                    name = e.name.split(" = ", 1)[0].lstrip("%")
                    ops.append([name, float(e.start_ns),
                                min(e.start_ns + e.duration_ns, hi)
                                - e.start_ns,
                                (scopes or {}).get(name, "other")])
            devices[plane.name] = dict(modules=mods, ops=ops)
    return dict(devices=devices, host=host)


def _outermost(spans):
    """The spans no other span contains (spans nest or follow)."""
    out = []
    for name, s, d in sorted(spans, key=lambda x: (x[1], -x[2])):
        if out and s + d <= out[-1][1] + out[-1][2]:
            continue
        out.append((name, s, d))
    return out


def _self_time(ops):
    """Per-operation self time: duration less that of the operations it
    contains."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = {i: ops[i][2] for i in order}
    stack = []
    for i in order:
        s, e = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return own


def split(raw: dict, window_ticks: int) -> dict:
    """The host gaps and the tick split by the program's spans and scopes
    (see the module docstring).  Keys whose spans or scopes the trace does
    not hold are left out."""
    host = raw["host"]
    win = [(s, s + d) for n, s, d in host if n == "bench.span"]
    lo, hi = win[0] if win else (float("-inf"), float("inf"))
    prog = [(n, s, d) for n, s, d in host
            if n.startswith("arcus.") and lo <= s < hi]
    out: dict = {}
    if prog:
        spans = {}
        for n, _s, d in prog:
            c, t = spans.get(n, (0, 0.0))
            spans[n] = (c + 1, t + d)
        out["spans"] = {n: dict(count=c, mean_ms=t * 1e-6 / c)
                        for n, (c, t) in sorted(spans.items())}
    calls = sorted((s, s + d) for n, s, d in host
                   if n in ("bench.engine_call", "bench.closing_call"))
    cut = min([s for n, s, d in host if n == "bench.closing_call"],
              default=hi)
    polls = sorted(s + d for n, s, d in prog if n == "arcus.fleet.poll")
    top = _outermost(prog)
    gaps, parts, waits, wp = [], {}, [], []
    for d in raw["devices"].values():
        wins = sorted((s, s + du) for n, s, du in d["modules"]
                      if tracereduce.WINDOW_PROGRAM in n
                      and lo <= s < min(hi, cut))
        wp += wins
        for s0, e0 in wins:
            k = bisect.bisect_left(polls, e0)
            if k < len(polls):
                waits.append(polls[k] - e0)
            nxt = [e for s, e in calls if s > s0]
            if not nxt or nxt[0] <= e0:
                continue
            g0, g1 = e0, nxt[0]
            gaps.append(g1 - g0)
            for n, s, du in top:
                ov = min(g1, s + du) - max(g0, s)
                if ov > 0:
                    parts[n] = parts.get(n, 0.0) + ov
    if gaps and prog:
        n = len(gaps)
        attributed = sum(parts.values())
        out["gaps"] = dict(
            count=n, gap_ms=sum(gaps) * 1e-6 / n,
            parts_ms={k: v * 1e-6 / n for k, v in
                      sorted(parts.items(), key=lambda kv: -kv[1])},
            unattributed_ms=(sum(gaps) - attributed) * 1e-6 / n)
    if waits:
        out["poll_ms"] = sum(waits) * 1e-6 / len(waits)
    ops = [o for d in raw["devices"].values() for o in d.get("ops", ())]
    if wp and any(o[3] in STAGES for o in ops):
        own = _self_time(ops)
        secs = dict.fromkeys(STAGES + ("other",), 0.0)
        for i, o in enumerate(ops):
            secs[o[3]] += own[i] * 1e-9
        total = sum(secs.values())
        tick_us = (sum(e - s for s, e in wp) * 1e-3
                   / (len(wp) * window_ticks))
        out["stages"] = dict(
            seconds=secs, ops=len(ops), tick_device_us=tick_us,
            us={k: tick_us * v / total for k, v in secs.items()})
    return out


def _window_hlo(engine, jax):
    """Keep the compiled window program and its argument shapes at its
    first call (then step aside), so that its HLO text can be read after
    the traced span."""
    seen = {}
    inner = engine._get_run

    def get_run(key, builder):
        fn = inner(key, builder)
        if key[0] != "batch":
            return fn

        def call(carry, args):
            engine._get_run = inner
            seen["fn"] = fn
            seen["shapes"] = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                (carry, args))
            return fn(carry, args)
        return call

    engine._get_run = get_run
    return lambda: seen["fn"].lower(*seen["shapes"]).compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default=os.path.join("bench_out", "spans"))
    a = ap.parse_args(argv)
    import run
    # the compile cache as ``run.py`` keeps it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    try:
        r = run.setup(a.workload, a.seed)
    except run.NoChip as e:
        print(f"spansplit.py: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.core import engine
    cell = r["cell"]
    rec = cell.recorder
    raw_dir = os.path.join(a.out, f"{a.workload}.seed{a.seed}", "raw")
    plan = cell.mix["trace"]
    rec.trace_plan = (int(plan["first_window"]), int(plan["windows"]),
                      lambda: jax.profiler.start_trace(
                          raw_dir,
                          profiler_options=run._profile_options(jax)),
                      jax.profiler.stop_trace)
    hlo = _window_hlo(engine, jax)
    t0 = time.perf_counter()
    cell.timeline()
    rec.stop_span(jax.profiler.stop_trace)
    timeline_s = time.perf_counter() - t0
    text = hlo()
    t0 = time.perf_counter()
    raw = read_spans(tracereduce.find_xplane(raw_dir),
                     scopes=hlo_scopes(text))
    read_s = time.perf_counter() - t0
    out = os.path.dirname(raw_dir)
    with gzip.open(os.path.join(out, "window_hlo.txt.gz"), "wt") as f:
        f.write(text)
    with gzip.open(os.path.join(out, "spans.json.gz"), "wt") as f:
        json.dump(raw, f)
    res = split(raw, cell.window_ticks)
    res.update(workload=a.workload, seed=a.seed, timeline_s=timeline_s,
               read_s=read_s, reduced=tracereduce.reduce(raw))
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
