"""Fleet-scale SLO management — the paper's scalability claim at fleet
granularity.

Arcus argues one shaping architecture can serve many client servers with
<1% throughput variance ("SLO Management for Accelerators in the Cloud");
this benchmark drives B managed client servers — heterogeneous flow counts
AND accelerator complements — as ONE compiled control plane
(`runtime.run_managed_batch`) and checks both halves of the claim:

  fleet_slo/B{N}        — batched managed fleet of N servers: wall clock,
                          us per (server x tick), cross-server throughput
                          deviation of the common reference flow vs the
                          paper's <1% target, worst per-server p99 latency,
                          and the engine-cache proof that the whole
                          heterogeneous fleet is ONE compiled entry
  fleet_slo/batch_vs_serial8 — the same 8-server fleet run as 8 serial
                          `run_managed` loops (each a compile-bound
                          distinct signature) vs the single batched
                          program; asserts counters bitwise-equal and
                          >= 3x wall-clock on CPU
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import Row, Timer, save_json, us_per_tick
from repro.core import engine
from repro.core.accelerator import CATALOG
from repro.core.flow import SLO, FlowSpec, Path, TrafficPattern
from repro.core.profiler import ProfileTable
from repro.core.runtime import ArcusRuntime, register_fleet, run_managed_batch

#: every server carries this reference flow on its first accelerator; its
#: achieved rate is what the cross-server variance check compares
REF_SLO_GBPS = 8.0
REF_MSG = 1024

#: heterogeneous accelerator complements, cycled across the fleet (the
#: first accel is shared so the reference flow is comparable server-to-
#: server; the rest make the accel tables ragged)
COMPLEMENTS = (
    ["synthetic50"],
    ["synthetic50", "aes256"],
    ["synthetic50", "aes256", "ipsec32"],
)


def fleet_specs(b: int) -> list[FlowSpec]:
    """Server b's flows: the shared reference flow plus 0-2 extra flows on
    the server's extra accelerators (ragged flow counts)."""
    names = COMPLEMENTS[b % len(COMPLEMENTS)]
    specs = [FlowSpec(0, 0, Path.FUNCTION_CALL, 0,
                      TrafficPattern(REF_MSG, load=0.4, process="poisson"),
                      SLO.gbps(REF_SLO_GBPS))]
    for i, _name in enumerate(names[1:], start=1):
        specs.append(FlowSpec(i, i, Path.FUNCTION_CALL, i,
                              TrafficPattern(512 << (i % 2), load=0.3,
                                             process="poisson"),
                              SLO.gbps(3.0 + i)))
    return specs


def _build_fleet(n_servers: int, profile: ProfileTable
                 ) -> list[ArcusRuntime]:
    rts = [ArcusRuntime([CATALOG[n]
                         for n in COMPLEMENTS[b % len(COMPLEMENTS)]],
                        profile_table=profile)
           for b in range(n_servers)]
    specs = [fleet_specs(b) for b in range(n_servers)]
    accepted = register_fleet(rts, specs)
    assert all(all(a) for a in accepted), "fleet admission rejected a flow"
    return rts


def fleet_refs(rts) -> list[dict[int, float]]:
    return [{i: 32.0 for i in range(len(rt.table))} for rt in rts]


def _ref_flow_gbps(res) -> float:
    return float(res.counters["c_done_bytes"][0] * 8 / res.seconds / 1e9)


def _p99_lat_us(res) -> float:
    lat = res.comp_lat_s[res.comp_flow == 0]
    if len(lat) == 0:
        return float("nan")
    return float(np.percentile(lat, 99) * 1e6)


def run(quick: bool = False) -> list[Row]:
    sweep = (1, 8, 32) if quick else (1, 8, 32, 128)
    window = 1_500 if quick else 3_000
    n_windows = 4 if quick else 5
    total = window * n_windows          # divisible: one engine entry
    rows, payload = [], {}

    profile = ProfileTable(n_ticks=6_000 if quick else 20_000)
    for B in sweep:
        rts = _build_fleet(B, profile)
        seeds = list(range(B))
        engine.cache_clear()
        with Timer() as t:
            results, reports = run_managed_batch(
                rts, total_ticks=total, window_ticks=window,
                seeds=seeds, load_ref_gbps=fleet_refs(rts))
        info = engine.cache_info()
        # the whole heterogeneous fleet (mixed flow counts, mixed accel
        # counts, per-server registers) is ONE compiled engine entry
        assert info == {"entries": 1, "traces": 1}, info
        ref = np.array([_ref_flow_gbps(r) for r in results])
        dev_pct = (np.max(np.abs(ref - ref.mean()) / ref.mean()) * 100
                   if B > 1 else 0.0)
        viol = sum(len(w.violated) for rep in reports for w in rep)
        d = dict(wall_s=t.s, servers=B, windows=len(reports[0]),
                 ref_gbps_mean=float(ref.mean()),
                 ref_dev_max_pct=float(dev_pct),
                 var_under_1pct=bool(dev_pct < 1.0),
                 p99_lat_us_worst=max(_p99_lat_us(r) for r in results),
                 slo_violations=viol,
                 entries=info["entries"], traces=info["traces"])
        rows.append(Row(f"fleet_slo/B{B}", us_per_tick(t.s, B * total), d))
        payload[f"B{B}"] = d

    # -- batched fleet vs B serial run_managed loops at B=8 --------------
    # serial pays one compile per server (every server's trace shape and
    # flow/accel signature differs); the batch compiles once.  Fresh
    # runtimes per side: run_managed mutates control state.
    B = 8
    seeds = list(range(B))
    rts_serial = _build_fleet(B, profile)
    engine.cache_clear()
    with Timer() as t_ser:
        serial = [rt.run_managed(total_ticks=total, window_ticks=window,
                                 seed=seeds[b],
                                 load_ref_gbps=fleet_refs(rts_serial)[b])
                  for b, rt in enumerate(rts_serial)]
    rts_batch = _build_fleet(B, profile)
    engine.cache_clear()
    with Timer() as t_bat:
        results, reports = run_managed_batch(
            rts_batch, total_ticks=total, window_ticks=window,
            seeds=seeds, load_ref_gbps=fleet_refs(rts_batch))
    match = all(
        np.array_equal(np.asarray(s.counters[k]), np.asarray(r.counters[k]))
        for (s, _), r in zip(serial, results)
        for k in ("c_adm_msgs", "c_done_msgs", "c_drops", "c_adm_bytes",
                  "c_done_bytes"))
    reports_match = all(
        ws.measured == wb.measured and ws.violated == wb.violated
        for (_, rep_s), rep_b in zip(serial, reports)
        for ws, wb in zip(rep_s, rep_b))
    speedup = t_ser.s / max(t_bat.s, 1e-9)
    assert match and reports_match, \
        "batched fleet diverged from serial run_managed"
    assert speedup >= 3.0, f"fleet batching speedup {speedup:.2f}x < 3x"
    d = dict(wall_s=t_bat.s, serial_wall_s=t_ser.s,
             speedup_vs_serial_x=speedup,
             counters_match_serial=bool(match),
             reports_match_serial=bool(reports_match))
    rows.append(Row("fleet_slo/batch_vs_serial8",
                    us_per_tick(t_bat.s, B * total), d))
    payload["batch_vs_serial8"] = d
    save_json("fleet_slo", payload)
    return rows
