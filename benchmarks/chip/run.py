#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload mica8.fig11a --seed 7 \\
        --seconds 10 --trace 0

The cell (``workloads`` in ``BENCHMARK.json``) names a deployment
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``).
One process: configure the persistent compile cache inside the checkout,
build the cell from ``--seed`` (admission profiling, one warm-up timeline of
the window's exact shapes), then run whole ``FleetController.run``
timelines back to back, starting none once ``--seconds`` have passed.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` runs the
same window under the profiler and reports the per-layer metrics, each read
by ``metrics/<name>.py``.  After the window the plain reference
(``plainref.py``) replays every server and decides ``correct`` over every
timeline of the window.  The last stdout line is the result object; the numbers
compared, each beside its limit, are also the last lines on stderr.

Without a TPU (or with fewer chips than the cell asks for) it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: fixed, inside the checkout: the path is part of the cache key
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    pass


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "bench_out", "chip"),
                    help="directory for a traced run's spans and reduced "
                         "trace (default: bench_out/chip in the checkout)")
    return ap.parse_args(argv)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name}", os.path.join(HERE, "metrics",
                                                 name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


#: what the profiler records on the TPU (libtpu's ``tpu_trace_mode``)
TPU_TRACE_MODE = "TRACE_ONLY_XLA"


def _profile_options(jax):
    """Device programs and the benchmark's host spans; no Python tracer
    (it would slow the host loop that the trace measures)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.advanced_configuration = {"tpu_trace_mode": TPU_TRACE_MODE}
    return opts


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _log(rec: dict) -> None:
    print(json.dumps(rec), file=sys.stderr, flush=True)


def _device(jax, chips: int) -> dict:
    dev = jax.devices()[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in dev)
    return dict(platform=dev[0].platform, kind=dev[0].device_kind,
                count=len(dev), memory_peak_bytes=int(peak))


def setup(workload: str, seed: int, *, require_chip: bool = True,
          overrides=None) -> dict:
    """Build and warm one cell: everything before the measured window.

    ``require_chip=False`` and ``overrides`` are for the tests, which drive
    the rest of a run at a tiny size on the host."""
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    wl = cells[workload]
    import jax
    if require_chip:
        if jax.default_backend() != "tpu":
            raise NoChip(f"needs a TPU; JAX's backend is "
                         f"{jax.default_backend()!r}")
        if len(jax.devices()) < int(wl["chips"]):
            raise NoChip(f"cell {workload} needs {wl['chips']} chips, JAX "
                         f"sees {len(jax.devices())}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from compileclock import CompileClock
    from fleetcell import Cell
    from repro import compile_cache
    cache_dir = compile_cache.configure() if require_chip else None
    clock = CompileClock()
    t_init = time.perf_counter() - T_START
    c0 = clock.snap()
    cell = Cell(wl, seed, overrides)
    parts = cell.setup()
    setup_s = time.perf_counter() - T_START
    _log(dict(phase="setup", workload=workload, seed=seed,
              cache_dir=cache_dir, setup_s=setup_s, init_s=t_init,
              trace_gen_and_build_s=setup_s - t_init - parts["admit_s"]
              - parts["warm_s"], **parts, **clock.since(c0)))
    return dict(bench=bench, wl=wl, cell=cell, clock=clock, seed=seed,
                setup_s=setup_s)


def measure(run: dict, seconds: float, trace: bool, *,
            out_dir: str | None = None) -> dict:
    """The measured window, the metrics and the check of a set-up cell;
    returns the result object."""
    import jax
    import plainref
    import tracereduce
    bench, wl, cell, clock = run["bench"], run["wl"], run["cell"], run["clock"]
    workload, seed = wl["name"], run["seed"]
    trace_dir = None
    rec = cell.recorder
    if trace:
        # the profiler records a span of the first timeline (the mix's
        # ``trace``: first engine window and how many), not the whole
        # window: every tick's operations are traced, hundreds of MB a
        # second of device time
        trace_dir = os.path.join(out_dir or os.path.join(ROOT, "bench_out",
                                                         "chip"),
                                 f"{workload}.seed{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        plan = cell.mix["trace"]
        raw_dir = os.path.join(trace_dir, "raw")
        rec.trace_plan = (int(plan["first_window"]), int(plan["windows"]),
                          lambda: jax.profiler.start_trace(
                              raw_dir, profiler_options=_profile_options(jax)),
                          jax.profiler.stop_trace)
    c1 = clock.snap()
    timelines = []
    t0 = time.perf_counter()
    while True:
        timelines.append(cell.timeline())
        if trace:
            rec.stop_span(jax.profiler.stop_trace)
            rec.trace_plan = None
        if time.perf_counter() - t0 >= seconds:
            break
    t1 = time.perf_counter()
    in_window = clock.since(c1)
    device = _device(jax, int(wl["chips"]))
    window_s = t1 - t0
    work = sum(tl.ticks * tl.servers for tl in timelines)
    _log(dict(phase="window", window_s=window_s, timelines=len(timelines),
              server_ticks=work, run_s=[tl.run_s for tl in timelines],
              admit_s=[tl.admit_s for tl in timelines], **in_window))

    result = dict(correct=False, attempted=len(timelines), failed=0,
                  metrics={}, device=device)
    if trace:
        raw = tracereduce.read_xplane(
            tracereduce.find_xplane(os.path.join(trace_dir, "raw")))
        red = tracereduce.reduce(raw)
        with gzip.open(os.path.join(trace_dir, "trace.json.gz"), "wt") as f:
            json.dump(raw, f)
        with open(os.path.join(trace_dir, "reduced.json"), "w") as f:
            json.dump(red, f)
        shutil.rmtree(raw_dir)
        ctx = dict(trace=red, compiles_in_window=in_window["compiles"],
                   window_ticks=cell.window_ticks,
                   timelines=[dict(ticks=tl.ticks, servers=tl.servers,
                                   admit_s=tl.admit_s, run_s=tl.run_s)
                              for tl in timelines])
        units = {m["name"]: m["unit"] for m in bench["per_layer"]}
        for m in bench["per_layer"]:
            if not _applies(m, workload):
                continue
            v = _reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = dict(value=v,
                                                    unit=units[m["name"]])
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = dict(device_ops=red["device_ops"],
                                   idle_gaps=red["idle_gaps"])
    else:
        e2e = dict(sim_rate=work / window_s, setup_s=run["setup_s"])
        for m in bench["end_to_end"]:
            if _applies(m, workload) and m["name"] in e2e:
                result["metrics"][m["name"]] = dict(value=e2e[m["name"]],
                                                     unit=m["unit"])

    # correctness: after the window, with the peak memory already read
    t_check = time.perf_counter()
    verdict = plainref.check(cell, timelines)
    result["correct"] = verdict["correct"]
    result["failed"] = verdict["failed"]
    result["checked"] = verdict["numbers"]
    _log(dict(phase="check", check_s=time.perf_counter() - t_check,
              timelines=len(timelines), servers=cell.B))
    for name, rec in verdict["numbers"].items():
        print(f"check {name}: {rec['value']!r} limit {rec['limit']!r}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    a = _parse(argv)
    # the compile cache lives at a fixed path inside the checkout, and
    # every program is cached however fast it compiled (steady set-up)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    sys.path.insert(0, HERE)
    try:
        run = setup(a.workload, a.seed)
        result = measure(run, a.seconds, bool(a.trace), out_dir=a.out)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
