"""One benchmark cell: a deployment (``configs/``) under a traffic mix
(``traffic/``), built from the data files and driven through the program's
``FleetController``.

A cell is built once per process (``setup``: admission profiling, then one
warm-up timeline of exactly the shapes the window runs) and then runs
whole timelines back to back (``timeline``), each on a controller admitted
afresh from the warmed ProfileTable, as a user admits a deployment and then
runs it.  Every timeline replays the same traces, drawn once from
``--seed`` by the benchmark's own generator (``tracegen.py``) and handed to
the program as explicit ``arrivals=``, so every timeline does the same
work.

While a timeline runs, a ``Recorder`` keeps what the program handed its
engine at each window (lane tables, masks, the token-bucket registers it
wrote), so that the check (``plainref.py``) can hold them against the
reference's own after the measured window has closed.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np

from tracegen import Pattern, gen_traces, pattern_for

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Window:
    """What the program handed its engine for one window of a timeline."""

    t0: int
    n_ticks: int
    lanes: list            # per server: accel, path, flow id of each lane
    masks: list            # per server: [width] bool
    writes: list | None    # per server: register arrays, or None (kept)


@dataclasses.dataclass
class Timeline:
    """Everything one run of ``FleetController.run`` produced."""

    windows: list = dataclasses.field(default_factory=list)
    results: list | None = None
    reports: list | None = None
    admit_s: float = 0.0
    run_s: float = 0.0
    ticks: int = 0
    servers: int = 0


class Recorder:
    """Wraps ``engine.run_window_batch`` to keep its per-window inputs.

    Only references are kept (the lane specs and register arrays the
    controller already built); nothing is copied off the device until the
    reference runs after the window."""

    def __init__(self, engine_mod):
        self.engine = engine_mod
        self.inner = engine_mod.run_window_batch
        self.current: Timeline | None = None
        #: set by a traced run: (first window, windows, start, stop) of
        #: the span of the next timeline that the profiler records
        self.trace_plan = None
        self._span = None
        engine_mod.run_window_batch = self

    def close(self) -> None:
        self.engine.run_window_batch = self.inner

    def start_span(self, start) -> None:
        import jax
        start()
        self._span = jax.profiler.TraceAnnotation("bench.span")
        self._span.__enter__()

    def stop_span(self, stop) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            stop()

    def __call__(self, flows, accels, link, cfg, tb_states, arr_t, arr_sz,
                 stall_mask=None, *, t0_ticks=0, carry=None, fl_masks=None):
        # the controller's windows pass per-server lane masks; profiling
        # calls do not, and are not recorded
        if self.current is None or fl_masks is None:
            return self.inner(flows, accels, link, cfg, tb_states, arr_t,
                              arr_sz, stall_mask, t0_ticks=t0_ticks,
                              carry=carry, fl_masks=fl_masks)
        w = len(self.current.windows)
        stop = None
        if self.trace_plan is not None:
            first, n, start, stop_fn = self.trace_plan
            if w == first:
                self.start_span(start)
            elif w == first + n:
                # the span ends once the next engine window is dispatched,
                # so the host gap before it is inside the span
                stop = stop_fn
                self.trace_plan = None
        self.current.windows.append(Window(
            t0=int(t0_ticks), n_ticks=int(cfg.n_ticks),
            lanes=[[dict(accel=int(fs.accel_id[i]), path=int(fs.path[i]),
                         flow_id=int(fs.specs[i].flow_id))
                    for i in range(fs.n)] for fs in flows],
            masks=[np.asarray(m, bool) for m in fl_masks],
            writes=None if tb_states is None else list(tb_states)))
        if self._span is None:
            return self.inner(flows, accels, link, cfg, tb_states, arr_t,
                              arr_sz, stall_mask, t0_ticks=t0_ticks,
                              carry=carry, fl_masks=fl_masks)
        import jax
        # the call that closes the span dispatches a program the span cuts
        name = "bench.engine_call" if stop is None else "bench.closing_call"
        with jax.profiler.TraceAnnotation(name):
            out = self.inner(flows, accels, link, cfg, tb_states, arr_t,
                             arr_sz, stall_mask, t0_ticks=t0_ticks,
                             carry=carry, fl_masks=fl_masks)
        if stop is not None:
            self.stop_span(stop)
        return out


class Cell:
    """A workload of ``BENCHMARK.json``: its deployment and traffic mix.

    ``overrides`` (tests only) replaces top-level keys of the deployment
    (``servers``, ``profile_ticks``) or of the mix (``window_ticks``,
    ``windows``)."""

    def __init__(self, workload: dict, seed: int, overrides=None):
        over = dict(overrides or {})
        self.workload = workload
        self.seed = int(seed)
        self.config = load_json("configs", workload["config"] + ".json")
        self.mix = load_json("traffic", workload["traffic"] + ".json")
        for k, v in over.items():
            (self.config if k in self.config else self.mix)[k] = v
        self.B = int(self.config["servers"])
        self.window_ticks = int(self.mix["window_ticks"])
        self.n_windows = int(self.mix["windows"])
        self.total_ticks = self.window_ticks * self.n_windows
        rng = np.random.default_rng(self.seed)
        self.server_seeds = [int(s) for s in rng.integers(0, 2**31 - 1,
                                                          self.B)]
        self.specs = [self.server_tenants(b) for b in range(self.B)]
        self.traces = [gen_traces([p for _t, p in self.specs[b]],
                                  self.total_ticks,
                                  int(self.config["tick_cycles"]),
                                  float(self.config["clock_hz"]),
                                  self.server_seeds[b])
                       for b in range(self.B)]
        self.ctrl = None
        self.profile = None
        self.recorder = None

    # -- the deployment as data ------------------------------------------
    def complement(self, b: int) -> list[str]:
        comps = self.config["complements"]
        return comps[b % len(comps)]

    def server_tenants(self, b: int) -> list[tuple[dict, Pattern]]:
        """Server b's incumbent tenants (config order = flow-id order,
        which is the controller's lane order) with their patterns."""
        n_acc = len(self.complement(b))
        ts = sorted((t for t in self.config["tenants"] if t["accel"] < n_acc),
                    key=lambda t: t["flow_id"])
        return [(t, pattern_for(t, self.mix)) for t in ts]

    # -- program objects -------------------------------------------------
    def _program_objects(self):
        from repro.core.accelerator import AcceleratorSpec
        from repro.core.flow import SLO, FlowSpec, Path, TrafficPattern
        from repro.core.interconnect import LinkSpec
        cfg = self.config
        accels = {n: AcceleratorSpec(**a)
                  for n, a in cfg["accelerators"].items()}
        link = LinkSpec(clock_hz=float(cfg["clock_hz"]), **cfg["link"])

        def flow_spec(t: dict, p: Pattern) -> FlowSpec:
            return FlowSpec(
                int(t["flow_id"]), int(t["flow_id"]), Path[t["path"]],
                int(t["accel"]),
                TrafficPattern(p.msg_bytes, load=p.load, process=p.process,
                               burst_len=p.burst_len, duty=p.duty,
                               params=p.params),
                SLO.gbps(float(t["slo_gbps"])), priority=int(t["priority"]),
                weight=float(t["weight"]))
        return accels, link, flow_spec

    def _fleet(self):
        """Runtimes sharing the cell's ProfileTable, admitted."""
        from repro.core.controller import FleetController
        from repro.core.runtime import ArcusRuntime
        accels, link, flow_spec = self._program_objects()
        rts = [ArcusRuntime([accels[n] for n in self.complement(b)],
                            link=link, profile_table=self.profile,
                            clock_hz=float(self.config["clock_hz"]),
                            slo_tol=float(self.config["slo_tol"]))
               for b in range(self.B)]
        # no policy on top: Algorithm 1's own re-planning (ReAdjustPattern)
        ctrl = FleetController(rts)
        admitted = ctrl.admit_fleet([[flow_spec(t, p) for t, p in self.specs[b]]
                                     for b in range(self.B)])
        if not all(all(a) for a in admitted):
            raise RuntimeError(f"admission rejected a tenant: {admitted}")
        return ctrl

    # -- lifecycle -------------------------------------------------------
    def setup(self, *, profile=None, warm: bool = True) -> dict:
        """Admission (profiling on the chip), then one warm-up timeline.
        Returns the seconds of each part.  ``profile`` shares an already
        warmed ProfileTable (``calibrate.py`` reads many seeds in one
        process)."""
        import repro.workloads.generators  # noqa: F401  (registers mmpp)
        from repro.core import engine
        from repro.core.profiler import ProfileTable
        self.recorder = Recorder(engine)
        self.profile = profile or ProfileTable(
            n_ticks=int(self.config["profile_ticks"]),
            tick_cycles=int(self.config["tick_cycles"]),
            clock_hz=float(self.config["clock_hz"]))
        t = time.perf_counter()
        self.ctrl = self._fleet()
        admit_s = time.perf_counter() - t
        t = time.perf_counter()
        if warm:
            self.timeline()
        return dict(admit_s=admit_s, warm_s=time.perf_counter() - t)

    def timeline(self) -> Timeline:
        """Admission from the warmed ProfileTable (it profiles nothing),
        then one whole ``FleetController.run`` over the cell's horizon.
        A fresh controller starts every timeline from the admitted
        registers: the control rule's headroom lives in the runtimes."""
        tl = Timeline(ticks=self.total_ticks, servers=self.B)
        t = time.perf_counter()
        self.ctrl = self._fleet()
        tl.admit_s = time.perf_counter() - t
        self.recorder.current = tl
        try:
            t = time.perf_counter()
            results, reports = self.ctrl.run(
                total_ticks=self.total_ticks,
                window_ticks=self.window_ticks,
                tick_cycles=int(self.config["tick_cycles"]),
                arrivals=self.traces,
                sim_kwargs=dict(self.config["dataplane"],
                                clock_hz=float(self.config["clock_hz"])))
            tl.run_s = time.perf_counter() - t   # ends in a device_get
        finally:
            self.recorder.current = None
        tl.results, tl.reports = results, reports
        return tl
