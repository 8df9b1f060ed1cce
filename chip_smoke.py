#!/usr/bin/env python3
"""Bring-up smoke run of both halves of the main path on one TPU chip.

    python chip_smoke.py

Everything runs in this one process (a chip belongs to one process).
Without a TPU the script exits non-zero before doing any work.  Phases:

1. fleet — a B=128 heterogeneous fleet admitted through
   ``FleetController.admit_fleet`` runs ``FleetController.run`` for 8
   windows of 3,000 ticks under ``GlobalRetarget(SlackAIMD())`` with
   ARRIVE and DEPART events, as ONE compiled engine entry.  Checks:
   (a) batched == serial ``ArcusRuntime.run_managed`` for 4 servers,
   bitwise; (b) the vectorized grant/service/egress stages == the
   sequential ones on the whole timeline, bitwise; (c) a B=8 timeline on
   the chip against the same timeline on the host CPU: same admission
   decisions and SLO verdicts, completed bytes within 0.1%.
2. serving — gemma3-12b at full width cut to 6 layers, bf16 parameters
   from a seed: prefill + one cached decode step against the full forward
   pass (float32 compute), then a dozen requests of three tenants served
   by ``ArcusScheduler`` twice, with the jnp token buckets and with the
   compiled Pallas token-bucket kernel, which must agree.

Each phase prints one JSON line (set-up and compile seconds, counters,
engine entries, wall times taken after the device finished).  Any failed
check raises.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

FLEET_B = 128
WINDOW_TICKS = 3_000
N_WINDOWS = 8
PROFILE_TICKS = 20_000
SERIAL_B = 4          # check (a)
CROSS_B = 8           # check (c)
CNT_KEYS = ("c_adm_msgs", "c_done_msgs", "c_drops", "c_adm_bytes",
            "c_done_bytes")

SERVE_ARCH = "gemma3-12b"
SERVE_LAYERS = 6      # one 5-local + 1-global period
PROMPT_LENS = (16, 48, 96)
NEW_TOKENS = (8, 16, 24)
#: max |decode - forward| over max |forward| at the last position of a
#: 16-token prompt, float32 compute at HIGHEST matmul precision: about
#: 2e-7 at full width on a TPU v5e.  A decode one cache slot off lands near
#: 2e-4 there (random weights attend almost uniformly, so a wrong slot moves
#: the logits little); the check also requires that to exceed ten times the
#: tolerance.
CACHE_TOL = 1e-5


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


class CompileClock:
    """Seconds JAX spent compiling (or loading compiled programs from the
    persistent cache), from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.s = 0.0
        self.n = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.s += duration
            self.n += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snap(self) -> tuple[float, int, int]:
        return self.s, self.n, self.cache_hits

    def since(self, snap) -> dict:
        return dict(compile_s=self.s - snap[0], compiles=self.n - snap[1],
                    cache_hits=self.cache_hits - snap[2])


# ---------------------------------------------------------------------------
# Fleet phase
# ---------------------------------------------------------------------------


def _runtime(b: int, profile):
    """Server b of the fleet_slo benchmark's heterogeneous fleet."""
    from benchmarks.fleet_slo import COMPLEMENTS
    from repro.core.accelerator import CATALOG
    from repro.core.runtime import ArcusRuntime
    names = COMPLEMENTS[b % len(COMPLEMENTS)]
    return ArcusRuntime([CATALOG[n] for n in names], profile_table=profile)


def _fleet(n_servers: int, profile, control=None):
    from benchmarks.fleet_slo import fleet_specs
    from repro.core.controller import FleetController
    ctrl = FleetController([_runtime(b, profile) for b in range(n_servers)],
                           control=control)
    admitted = ctrl.admit_fleet([fleet_specs(b) for b in range(n_servers)])
    check(all(all(a) for a in admitted), "fleet admission rejected a flow")
    return ctrl, admitted


def _adaptive():
    from repro.core import control
    return control.GlobalRetarget(control.SlackAIMD())


def _events():
    """Two tenants arrive (windows 2 and 3) and the first departs
    (window 5); the controller places them fleet-wide."""
    from repro.core.controller import TenantEvent
    from repro.core.flow import SLO, FlowSpec, Path, TrafficPattern

    def tenant(fid):
        return FlowSpec(fid, fid, Path.FUNCTION_CALL, 0,
                        TrafficPattern(1024, load=0.3, process="poisson"),
                        SLO.gbps(4.0))
    return [TenantEvent.arrive(2, tenant(5000), accel_name="synthetic50"),
            TenantEvent.arrive(3, tenant(5001), accel_name="synthetic50"),
            TenantEvent.depart(5, tenant_id=5000)]


def _run_kwargs(ctrl, window: int, n_windows: int, events) -> dict:
    from benchmarks.fleet_slo import fleet_refs
    B = len(ctrl.runtimes)
    return dict(total_ticks=window * n_windows, window_ticks=window,
                seeds=list(range(B)), load_ref_gbps=fleet_refs(ctrl.runtimes),
                events=events)


def _warm_events(ctrl, events) -> None:
    """Profile every context the timeline's arrivals will be scored
    against, on a throwaway controller sharing the ProfileTable: the timed
    run's placements are then pure cache hits and the engine's only
    compiled entry is the timeline's own."""
    for ev in events:
        if ev.kind == "arrive":
            ctrl.place([ev.spec], accel_names=[ev.accel_name])


def _timeline(clock: CompileClock, B: int, profile, window: int,
              n_windows: int, sim_kwargs=None) -> dict:
    """Admit a B-server fleet and run the adaptive event timeline once."""
    from repro.core import engine
    from repro.core.profiler import profiling_stats
    t = time.perf_counter()
    events = _events()
    warm, _ = _fleet(B, profile, _adaptive())
    _warm_events(warm, events)
    ctrl, admitted = _fleet(B, profile, _adaptive())
    engine.cache_clear()
    p0 = profiling_stats()["contexts"]
    t_run, c_run = time.perf_counter(), clock.snap()
    results, reports = ctrl.run(**_run_kwargs(ctrl, window, n_windows,
                                              events),
                                sim_kwargs=sim_kwargs)
    run_s = time.perf_counter() - t_run  # ends in a device_get: synchronous
    return dict(ctrl=ctrl, admitted=admitted, results=results,
                reports=reports, setup_s=t_run - t, run_s=run_s,
                run_compile_s=clock.since(c_run)["compile_s"],
                cache=engine.cache_info(),
                new_contexts=profiling_stats()["contexts"] - p0)


def _counters(results) -> list[dict]:
    import numpy as np
    return [{k: np.asarray(r.counters[k]) for k in CNT_KEYS}
            for r in results]


def _same_counters(a, b) -> bool:
    import numpy as np
    return all(np.array_equal(x[k], y[k]) for x, y in zip(a, b)
               for k in CNT_KEYS)


def _verdicts(reports) -> list:
    return [[sorted(w.violated) for w in rep] for rep in reports]


def _measured(reports) -> list:
    return [[w.measured for w in rep] for rep in reports]


def _decisions(ctrl) -> list:
    return [(e["window"], e["kind"], e["tenant"], e["server"])
            for e in ctrl.last_events]


def _check_serial(profile, window: int, n_windows: int) -> dict:
    """(a) the batched controller == serial run_managed, bitwise."""
    from benchmarks.fleet_slo import fleet_refs, fleet_specs
    ctrl, _ = _fleet(SERIAL_B, profile)
    kw = dict(total_ticks=window * n_windows, window_ticks=window)
    refs = fleet_refs(ctrl.runtimes)
    results, reports = ctrl.run(seeds=list(range(SERIAL_B)),
                                load_ref_gbps=refs, **kw)
    serial, serial_reports = [], []
    for b in range(SERIAL_B):
        rt = _runtime(b, profile)
        check(all(rt.register(s) for s in fleet_specs(b)),
              "serial admission rejected a flow")
        res, rep = rt.run_managed(seed=b, load_ref_gbps=refs[b], **kw)
        serial.append(res)
        serial_reports.append(rep)
    counters = _same_counters(_counters(results), _counters(serial))
    reps = (_measured(reports) == _measured(serial_reports)
            and _verdicts(reports) == _verdicts(serial_reports))
    check(counters and reps,
          f"(a) batched != serial run_managed (counters equal: {counters},"
          f" reports equal: {reps})")
    return dict(servers=SERIAL_B, counters_bitwise=counters,
                reports_equal=reps)


def _check_sequential(clock: CompileClock, profile, fast: dict,
                      window: int, n_windows: int) -> dict:
    """(b) the vectorized stages == the sequential reference, bitwise."""
    B = len(fast["results"])
    seq = _timeline(clock, B, profile, window, n_windows,
                    sim_kwargs={"grant_fast": False, "stage_fast": False})
    counters = _same_counters(_counters(fast["results"]),
                              _counters(seq["results"]))
    reps = (_measured(fast["reports"]) == _measured(seq["reports"])
            and _verdicts(fast["reports"]) == _verdicts(seq["reports"])
            and _decisions(fast["ctrl"]) == _decisions(seq["ctrl"]))
    check(counters and reps,
          f"(b) fast stages != sequential stages (counters equal: "
          f"{counters}, reports and decisions equal: {reps})")
    return dict(servers=B, counters_bitwise=counters, reports_equal=reps,
                sequential_run_s=seq["run_s"])


def _check_cross_device(clock: CompileClock, window: int, n_windows: int,
                        profile_ticks: int) -> dict:
    """(c) a B=8 timeline on the default device against the host CPU."""
    import jax
    import numpy as np
    from repro.core.profiler import ProfileTable

    def one():
        out = _timeline(clock, CROSS_B, ProfileTable(n_ticks=profile_ticks),
                        window, n_windows)
        out["counters"] = _counters(out["results"])
        return out

    chip = one()
    with jax.default_device(jax.devices("cpu")[0]):
        host = one()
    admit_same = (chip["admitted"] == host["admitted"]
                  and _decisions(chip["ctrl"]) == _decisions(host["ctrl"]))
    verdicts_same = _verdicts(chip["reports"]) == _verdicts(host["reports"])
    rel = 0.0
    for a, b in zip(chip["counters"], host["counters"]):
        x = a["c_done_bytes"].astype(np.float64)
        y = b["c_done_bytes"].astype(np.float64)
        rel = max(rel, float(np.max(np.abs(x - y) / np.maximum(y, 1.0))))
    bitwise = _same_counters(chip["counters"], host["counters"])
    differ = sorted({k for a, b in zip(chip["counters"], host["counters"])
                     for k in CNT_KEYS if not np.array_equal(a[k], b[k])})
    measured_differ = [w for w in range(n_windows)
                       if any(rep[w] != hrep[w] for rep, hrep in
                              zip(_measured(chip["reports"]),
                                  _measured(host["reports"])))]
    rec = dict(servers=CROSS_B, admission_same=admit_same,
               verdicts_same=verdicts_same, done_bytes_max_rel=rel,
               counters_bitwise=bitwise, counters_differ=differ,
               windows_measured_differ=measured_differ,
               chip_run_s=chip["run_s"], cpu_run_s=host["run_s"])
    check(admit_same and verdicts_same and rel <= 1e-3,
          f"(c) chip vs CPU: {rec}")
    return rec


def fleet_phase(clock: CompileClock, *, B: int = FLEET_B,
                window: int = WINDOW_TICKS, n_windows: int = N_WINDOWS,
                profile_ticks: int = PROFILE_TICKS) -> dict:
    import numpy as np
    from repro.core.profiler import ProfileTable
    c0 = clock.snap()
    profile = ProfileTable(n_ticks=profile_ticks)
    fast = _timeline(clock, B, profile, window, n_windows)
    check(fast["cache"] == {"entries": 1, "traces": 1},
          f"the timeline is not one compiled engine entry: {fast['cache']}")
    check(fast["new_contexts"] == 0,
          f"{fast['new_contexts']} admission contexts were profiled "
          "inside the timed run")
    landed = [e for e in fast["ctrl"].last_events if e["kind"] == "arrive"]
    check(len(landed) == 2 and all(e["server"] is not None for e in landed),
          f"an arriving tenant was rejected: {fast['ctrl'].last_events}")
    check(len(fast["reports"][0]) == n_windows, "missing window reports")
    cnt = _counters(fast["results"])
    done = int(sum(c["c_done_bytes"].sum() for c in cnt))
    check(done > 0, "the fleet completed nothing")
    rec = dict(phase="fleet", servers=B, windows=n_windows,
               window_ticks=window, events=_decisions(fast["ctrl"]),
               engine_cache=fast["cache"], setup_s=fast["setup_s"],
               run_s=fast["run_s"], run_compile_s=fast["run_compile_s"],
               done_bytes=done,
               adm_msgs=int(sum(c["c_adm_msgs"].sum() for c in cnt)),
               drops=int(sum(c["c_drops"].sum() for c in cnt)),
               violation_windows=int(sum(len(w.violated)
                                         for rep in fast["reports"]
                                         for w in rep)),
               ref_gbps_mean=float(np.mean(
                   [r.counters["c_done_bytes"][0] * 8 / r.seconds / 1e9
                    for r in fast["results"]])))
    rec.update(clock.since(c0))
    emit(rec)
    c1 = clock.snap()
    rec_a = dict(phase="fleet_check_a_serial",
                 **_check_serial(profile, window, min(n_windows, 4)))
    rec_a.update(clock.since(c1))
    emit(rec_a)
    c1 = clock.snap()
    rec_b = dict(phase="fleet_check_b_sequential",
                 **_check_sequential(clock, profile, fast, window,
                                     n_windows))
    rec_b.update(clock.since(c1))
    emit(rec_b)
    c1 = clock.snap()
    rec_c = dict(phase="fleet_check_c_cpu",
                 **_check_cross_device(clock, window, n_windows,
                                       profile_ticks))
    rec_c.update(clock.since(c1))
    emit(rec_c)
    return rec


# ---------------------------------------------------------------------------
# Serving phase
# ---------------------------------------------------------------------------


def _cache_vs_forward(params, cfg, seed: int, max_len: int) -> dict:
    """Prefill S tokens, decode token S through the cache, compare with the
    full forward pass at position S — float32 compute, HIGHEST matmuls."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T
    from repro.serving import engine as E
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    S = PROMPT_LENS[0]   # the shortest: a wrong slot weighs the most
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab, (1, S + 1)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        full, _ = jax.jit(T.forward, static_argnums=(1,))(params, cfg32, toks)
        ref = np.asarray(full[0, -1], np.float32)
        cache = T.init_cache(cfg32, 1, max_len, jnp.float32)
        _, cache, lengths = E._prefill(params, cfg32, toks[:, :S], cache,
                                       None)
        dec, _ = E._decode(params, cfg32, toks[:, S:], lengths, cache)
        off, _ = E._decode(params, cfg32, toks[:, S:], lengths + 1, cache)
    scale = float(np.max(np.abs(ref)))
    err = float(np.max(np.abs(np.asarray(dec[0], np.float32) - ref))) / scale
    off_err = float(np.max(np.abs(np.asarray(off[0], np.float32) - ref))) \
        / scale
    check(np.isfinite(ref).all() and scale > 0, "forward logits not finite")
    check(err <= CACHE_TOL,
          f"cached decode differs from forward: {err:.3g} > {CACHE_TOL}")
    check(off_err > 10 * CACHE_TOL,
          f"an off-by-one cache slot stays within tolerance ({off_err:.3g})"
          " — the check cannot see a wrong slot")
    return dict(prompt=S, rel_err=err, off_by_one_rel_err=off_err,
                tol=CACHE_TOL)


def _requests(cfg, seed: int):
    """Twelve requests: four per tenant, each tenant cycling through the
    prompt lengths and new-token counts."""
    import numpy as np
    from repro.serving.request import Request
    rng = np.random.default_rng(seed)
    reqs = []
    for k in range(12):
        tid, j = k % 3, k // 3
        prompt = rng.integers(0, cfg.vocab, PROMPT_LENS[(j + tid) % 3])
        reqs.append(Request(k, tid, [int(x) for x in prompt],
                            NEW_TOKENS[(j + 2 * tid) % 3],
                            arrive_s=0.002 * k))
    return reqs


def _serve(params, cfg, seed: int, *, use_kernel: bool, max_batch: int,
           max_len: int) -> dict:
    import jax
    import numpy as np
    from repro.configs.registry import get_config
    from repro.core.flow import SLO
    from repro.serving.costmodel import HardwareSpec, StepCostModel
    from repro.serving.engine import ServingEngine
    from repro.serving.request import Tenant
    from repro.serving.scheduler import ArcusScheduler
    eng = ServingEngine(cfg, params, max_batch=max_batch, max_len=max_len)
    tenants = [Tenant(0, SLO.iops(1200.0), "reserved"),
               Tenant(1, SLO.iops(800.0), "reserved"),
               Tenant(2, SLO.iops(1e9), "opportunistic")]
    cost = StepCostModel(get_config(SERVE_ARCH), HardwareSpec(chips=1))
    sched = ArcusScheduler(eng, tenants, cost, use_kernel=use_kernel)
    reqs = _requests(cfg, seed)
    for r in reqs:
        sched.submit(r)
    t = time.perf_counter()
    rounds = 0
    while not all(r.done for r in reqs):
        sched.step()
        rounds += 1
        check(rounds < 5_000, "serving did not finish its requests")
    jax.block_until_ready(sched.buckets)
    wall = time.perf_counter() - t
    for r in reqs:
        check(len(r.generated) == r.max_new_tokens,
              f"request {r.req_id}: {len(r.generated)} tokens, wanted "
              f"{r.max_new_tokens}")
        check(all(0 <= x < cfg.vocab for x in r.generated),
              f"request {r.req_id} produced a token outside the vocabulary")
    return dict(requests=reqs, rounds=rounds, wall_s=wall,
                buckets={k: np.asarray(v) for k, v in
                         sched.buckets._asdict().items()},
                tokens=int(sum(len(r.generated) for r in reqs)),
                finished={tid: st.finished
                          for tid, st in sched.stats.items()})


def serving_phase(clock: CompileClock, *, cfg=None, seed: int = 0,
                  max_batch: int = 4, max_len: int = 128) -> dict:
    import jax
    import numpy as np
    from repro.configs.registry import get_config
    from repro.kernels.token_bucket import ops as tb_ops
    from repro.models import transformer as T
    c0 = clock.snap()
    if cfg is None:
        cfg = dataclasses.replace(get_config(SERVE_ARCH),
                                  n_layers=SERVE_LAYERS)
    t = time.perf_counter()
    params = jax.block_until_ready(T.init_model_params_only(seed, cfg))
    init_s = time.perf_counter() - t
    n_params = sum(x.size for x in jax.tree.leaves(params))
    check(all(x.dtype == jax.numpy.bfloat16
              for x in jax.tree.leaves(params)), "parameters are not bf16")
    cache_check = _cache_vs_forward(params, cfg, seed, max_len)
    plain = _serve(params, cfg, seed, use_kernel=False, max_batch=max_batch,
                   max_len=max_len)
    check(not tb_ops.resolved_interpret(),
          "the token-bucket kernel would run in interpret mode")
    kern = _serve(params, cfg, seed, use_kernel=True, max_batch=max_batch,
                  max_len=max_len)
    same_buckets = all(np.array_equal(plain["buckets"][k], kern["buckets"][k])
                       for k in plain["buckets"])
    check(same_buckets, "kernel bucket states differ from the jnp path")
    same_tokens = all(a.generated == b.generated
                      for a, b in zip(plain["requests"], kern["requests"]))
    check(same_tokens, "kernel-shaped serving produced different tokens")
    stats = jax.devices()[0].memory_stats() or {}
    rec = dict(phase="serving", arch=cfg.name, n_layers=cfg.n_layers,
               d_model=cfg.d_model, vocab=cfg.vocab, params=int(n_params),
               init_s=init_s, cache_check=cache_check,
               requests=len(plain["requests"]), tokens=plain["tokens"],
               finished=plain["finished"], rounds=plain["rounds"],
               serve_s=plain["wall_s"], serve_kernel_s=kern["wall_s"],
               kernel_interpret=bool(tb_ops.resolved_interpret()),
               buckets_equal=same_buckets, tokens_equal=same_tokens,
               peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    rec.update(clock.since(c0))
    emit(rec)
    return rec


# ---------------------------------------------------------------------------


def main() -> None:
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        # check (c) runs a timeline on the host CPU beside the chip
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX's backend is {backend!r}")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro import compile_cache
    t0 = time.perf_counter()
    cache_dir = compile_cache.configure()
    clock = CompileClock()
    dev = jax.devices()[0]
    emit(dict(phase="start", cache_dir=cache_dir,
              cache_warm=os.path.isdir(cache_dir) and bool(
                  os.listdir(cache_dir)), jax=jax.__version__))
    fleet_phase(clock)
    serving_phase(clock)
    emit(dict(phase="end", wall_s=time.perf_counter() - t0,
              compile_s=clock.s, compiles=clock.n,
              cache_hits=clock.cache_hits))
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
