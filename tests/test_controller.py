"""Tenant-lifecycle controller tests: static parity, departure inertness,
admit→depart→readmit determinism, churn timelines on one compiled engine,
rebalancing onto freed capacity, the stateful score cache, and the
clock-threading satellite.

The legacy fleet entry points (`register_fleet` / `place_fleet` /
`run_managed_batch`) are deprecation shims over `FleetController`, so the
existing `tests/test_fleet.py` + `tests/test_placement.py` suites pin the
shim side of the parity contract (bitwise-equal to serial `run_managed`);
this file exercises what only the controller can do."""
import dataclasses

import numpy as np
import pytest

from repro.core import engine, placement, token_bucket as tb
from repro.core.accelerator import CATALOG
from repro.core.controller import FleetController, TenantEvent
from repro.core.flow import SLO, FlowSpec, Path, TrafficPattern
from repro.core.interconnect import LinkSpec
from repro.core.profiler import ProfileTable, profiling_stats
from repro.core.runtime import ArcusRuntime

_PROFILE_TICKS = 6_000

_CNT_KEYS = ("c_adm_msgs", "c_done_msgs", "c_drops", "c_adm_bytes",
             "c_done_bytes")


def _spec(fid, slo_gbps, accel_id=0, msg=1024, load=0.5, rate_mps=None):
    return FlowSpec(fid, fid, Path.FUNCTION_CALL, accel_id,
                    TrafficPattern(msg, load=load, rate_mps=rate_mps,
                                   process="poisson" if rate_mps is None
                                   else "cbr"),
                    SLO.gbps(slo_gbps))


def _mk_fleet(complements, profile=None):
    profile = profile or ProfileTable(n_ticks=_PROFILE_TICKS)
    return [ArcusRuntime([CATALOG[n] for n in names],
                         profile_table=profile)
            for names in complements]


# ---------------------------------------------------------------------------
# Static parity: controller.run == serial run_managed, bitwise
# ---------------------------------------------------------------------------


def test_controller_static_run_matches_serial_bitwise():
    """A FleetController driven directly (no shim) over a static tenant
    set produces counters, WindowReports and control state bitwise-equal
    to serial per-server run_managed — the deprecation-shim parity
    contract, anchored on the serial reference."""
    def mk():
        rts = _mk_fleet((["synthetic50"], ["ipsec32", "synthetic50"]))
        assert rts[0].register(_spec(0, 10.0))
        assert rts[0].register(_spec(1, 5.0, msg=2048))
        assert rts[1].register(_spec(0, 8.0, msg=1500))
        return rts

    kwargs = dict(total_ticks=12_000, window_ticks=4_000)
    refs = [{0: 32.0, 1: 32.0}, {0: 32.0}]
    rts_s = mk()
    serial = [rt.run_managed(seed=b + 1, load_ref_gbps=refs[b], **kwargs)
              for b, rt in enumerate(rts_s)]
    rts_c = mk()
    ctrl = FleetController(rts_c)
    results, reports = ctrl.run(seeds=[1, 2], load_ref_gbps=refs, **kwargs)
    for b, (res_s, rep_s) in enumerate(serial):
        for k in _CNT_KEYS:
            np.testing.assert_array_equal(res_s.counters[k],
                                          results[b].counters[k])
        np.testing.assert_array_equal(res_s.comp_flow, results[b].comp_flow)
        assert len(rep_s) == len(reports[b])
        for ws, wb in zip(rep_s, reports[b]):
            assert ws.measured == wb.measured
            assert ws.violated == wb.violated
            assert ws.reconfigured == wb.reconfigured
            # the telemetry digest agrees too (NaN-aware: frozen
            # dataclass == fails on NaN fields)
            assert set(ws.metrics) == set(wb.metrics)
            for fid in ws.metrics:
                ms, mb = ws.metrics[fid], wb.metrics[fid]
                np.testing.assert_equal(ms.lat_avg_s, mb.lat_avg_s)
                np.testing.assert_equal(ms.slack, mb.slack)
                assert (dataclasses.replace(ms, lat_avg_s=0.0, slack=0.0)
                        == dataclasses.replace(mb, lat_avg_s=0.0,
                                               slack=0.0))
        for fid in rts_s[b].table:
            assert rts_s[b].table[fid].params == rts_c[b].table[fid].params
            assert (rts_s[b].table[fid].violations
                    == rts_c[b].table[fid].violations)


# ---------------------------------------------------------------------------
# Departure: the freed lane is provably inert
# ---------------------------------------------------------------------------


def _depart_fleet(profile):
    rts = _mk_fleet((["synthetic50"], ["synthetic50"]), profile)
    assert rts[0].register(_spec(0, 5.0, load=0.4))
    assert rts[0].register(_spec(1, 5.0, load=0.4))     # the tenant
    assert rts[1].register(_spec(2, 5.0, load=0.4))
    return rts


def test_depart_event_freezes_lane_counters():
    """DEPART at a window boundary: the lane's admission/drop counters
    freeze at exactly their boundary values (bitwise-equal to a run
    truncated at the departure window), later reports drop the tenant,
    and the remaining flows keep progressing — all without a recompile
    (one engine entry for the whole churn run)."""
    profile = ProfileTable(n_ticks=_PROFILE_TICKS)
    kwargs = dict(window_ticks=3_000, seeds=[3, 4],
                  load_ref_gbps=[{0: 32.0, 1: 32.0}, {0: 32.0}])
    # truncated reference: exactly the two pre-departure windows
    trunc, _ = FleetController(_depart_fleet(profile)).run(
        total_ticks=6_000, **kwargs)
    rts = _depart_fleet(profile)
    ctrl = FleetController(rts)
    engine.cache_clear()
    res, reports = ctrl.run(total_ticks=15_000,
                            events=[TenantEvent.depart(2, tenant_id=1)],
                            **kwargs)
    assert engine.cache_info() == {"entries": 1, "traces": 1}
    # admission stopped at the boundary, bitwise; queued leftovers were
    # flushed, so no post-departure drops either
    for k in ("c_adm_msgs", "c_adm_bytes", "c_drops"):
        assert res[0].counters[k][1] == trunc[0].counters[k][1], k
    # in-flight at the boundary drained; nothing new completed after
    assert (res[0].counters["c_done_msgs"][1]
            <= trunc[0].counters["c_done_msgs"][1] + 8)
    # the tenant vanished from the control plane at its window
    assert 1 not in rts[0].table
    assert ctrl.lane_map(0) == [0, None]
    for w, rep in enumerate(reports[0]):
        assert (1 in rep.measured) == (w < 2)
    # everyone else kept running
    assert res[0].counters["c_done_msgs"][0] > trunc[0].counters[
        "c_done_msgs"][0]
    assert res[1].counters["c_done_msgs"][0] > trunc[1].counters[
        "c_done_msgs"][0]
    assert ctrl.stats["departed"] == 1


def test_departed_idle_tenant_bitwise_equal_to_never_admitted():
    """An admitted tenant that departs before its first message leaves
    the other flows' counters and reports bitwise-equal to a fleet that
    never admitted it: occupying a lane, carrying registers and being
    measured (and even reconfigured) is provably inert as long as no
    message flows."""
    profile = ProfileTable(n_ticks=_PROFILE_TICKS)
    window, total = 3_000, 15_000
    window_s = window * 8 / 250e6
    # first CBR arrival lands mid-window-2 — after the boundary-2 depart
    idle = _spec(9, 1.0, rate_mps=1.0 / (2.5 * window_s))

    def run(with_tenant):
        rts = _mk_fleet((["synthetic50"],), profile)
        assert rts[0].register(_spec(0, 8.0, load=0.5))
        if with_tenant:
            assert rts[0].register(idle)
        ctrl = FleetController(rts)
        events = [TenantEvent.depart(2, tenant_id=9)] if with_tenant else []
        res, rep = ctrl.run(total_ticks=total, window_ticks=window,
                            seeds=[7], load_ref_gbps=[{0: 32.0}],
                            events=events)
        return rts, res, rep

    rts_x, res_x, rep_x = run(True)
    rts_y, res_y, rep_y = run(False)
    for k in _CNT_KEYS:
        assert res_x[0].counters[k][0] == res_y[0].counters[k][0], k
        # the idle tenant's lane never counted anything at all
        assert res_x[0].counters[k][1] == 0, k
    for wx, wy in zip(rep_x[0], rep_y[0]):
        assert wx.measured[0] == wy.measured[0]
    assert rts_x[0].table[0].params == rts_y[0].table[0].params


# ---------------------------------------------------------------------------
# Admit → depart → readmit reproduces the original placement decision
# ---------------------------------------------------------------------------


def test_admit_depart_readmit_reproduces_placement():
    profile = ProfileTable(n_ticks=_PROFILE_TICKS)
    ctrl = FleetController(_mk_fleet(
        (["synthetic50"], ["synthetic50"], ["synthetic50"]), profile))
    names = ["synthetic50"] * 3
    first = [ctrl.admit(_spec(i, 9.0), accel_name=names[i])
             for i in range(3)]
    assert all(p.accepted for p in first)
    target = first[1]
    before = profiling_stats()
    assert ctrl.depart(1) == target.server
    again = ctrl.admit(_spec(1, 9.0), accel_name="synthetic50")
    after = profiling_stats()
    assert again.accepted
    assert (again.server, again.accel_id) == (target.server,
                                              target.accel_id)
    # the sweep reused at least one untouched server's cached score
    assert after["score_hits"] > before["score_hits"]
    # and no new profiling simulation ran — every context was known
    assert after["contexts"] == before["contexts"]


# ---------------------------------------------------------------------------
# Churn timeline: one compiled engine entry, re-pack only when touched
# ---------------------------------------------------------------------------


def _churn_fleet(profile):
    rts = _mk_fleet((["synthetic50"], ["synthetic50", "aes256"],
                     ["synthetic50"]), profile)
    specs = [[_spec(0, 4.0, load=0.3)],
             [_spec(1, 4.0, load=0.3), _spec(2, 3.0, accel_id=1, load=0.3)],
             [_spec(3, 4.0, load=0.3)]]
    return rts, specs


def test_churn_timeline_single_engine_entry_and_no_clean_repacks(
        monkeypatch):
    profile = ProfileTable(n_ticks=_PROFILE_TICKS)
    events = [
        TenantEvent.arrive(1, _spec(100, 4.0, load=0.3),
                           accel_name="synthetic50"),
        TenantEvent.depart(3, tenant_id=1),
        TenantEvent.arrive(4, _spec(101, 4.0, load=0.3),
                           accel_name="synthetic50"),
    ]
    kwargs = dict(total_ticks=18_000, window_ticks=3_000,
                  seeds=[1, 2, 3],
                  load_ref_gbps=[{0: 32.0}, {0: 32.0, 1: 32.0}, {0: 32.0}])

    # warm the admission contexts on a throwaway clone sharing the
    # ProfileTable, so the live run's placement is pure cache hits
    rts_w, specs_w = _churn_fleet(profile)
    ctrl_w = FleetController(rts_w)
    ctrl_w.admit_fleet(specs_w)
    ctrl_w.run(events=events, **kwargs)

    rts, specs = _churn_fleet(profile)
    ctrl = FleetController(rts)
    ctrl.admit_fleet(specs)
    packs = []
    real_pack = tb.pack
    monkeypatch.setattr(tb, "pack", lambda ps: packs.append(1) or
                        real_pack(ps))
    engine.cache_clear()
    results, reports = ctrl.run(events=events, **kwargs)
    # the whole churn timeline — arrivals, departure included — is ONE
    # compiled engine entry
    assert engine.cache_info() == {"entries": 1, "traces": 1}
    # re-packs: window 0 packs all 3 servers; afterwards a server packs
    # exactly when an event touched it or its previous window
    # reconfigured (one pack even when both hit); clean windows re-pack
    # nothing
    ev_servers: dict[int, set] = {}
    for e in ctrl.last_events:
        if e["server"] is not None:
            ev_servers.setdefault(e["window"], set()).add(e["server"])
    expected = 3
    for w in range(1, len(reports[0])):
        dirty = set(ev_servers.get(w, set()))
        dirty |= {b for b in range(3)
                  if reports[b][w - 1].reconfigured
                  or reports[b][w - 1].path_changes}
        expected += len(dirty)
    assert len(packs) == expected, (len(packs), expected)
    assert len(packs) < 3 * len(reports[0])     # strictly no full re-pack
    # lifecycle landed where expected
    assert ctrl.stats["admitted"] >= 6      # 4 initial + 2 arrivals
    assert ctrl.stats["departed"] == 1
    applied = {(e["kind"], e["tenant"]) for e in ctrl.last_events}
    assert applied == {("arrive", 100), ("depart", 1), ("arrive", 101)}
    # the arrivals actually produced traffic on their servers
    for e in ctrl.last_events:
        if e["kind"] == "arrive":
            b, lane = e["server"], e["lane"]
            assert results[b].counters["c_done_msgs"][lane] > 0
    # the departed tenant shows in reports only before its window
    for w, rep in enumerate(reports[1]):
        assert (1 in rep.measured) == (w < 3)


def test_reuse_lanes_recycled_lane_resets_measurement_baseline():
    """With ``reuse_lanes=True`` a mid-run arrival refills a departed
    tenant's lane — and the recycled lane's measurement baseline resets
    at the splice (device counters zeroed by ``recycle_flow_lane``, the
    host's prev-poll rows zeroed by the controller), so the newcomer's
    first-window measured rate and final per-lane counters contain only
    its own traffic, not the predecessor's cumulative totals."""
    profile = ProfileTable(n_ticks=_PROFILE_TICKS)
    events = [TenantEvent.depart(2, tenant_id=1),
              TenantEvent.arrive(3, _spec(102, 4.0, load=0.3),
                                 accel_name="synthetic50")]
    kwargs = dict(total_ticks=15_000, window_ticks=3_000, seeds=[1],
                  load_ref_gbps=[{0: 32.0, 1: 32.0}])

    def build():
        rts = _mk_fleet((["synthetic50"],), profile)
        ctrl = FleetController(rts, reuse_lanes=True)
        acc = ctrl.admit_fleet([[_spec(0, 4.0, load=0.3),
                                 _spec(1, 4.0, load=0.3)]])
        assert acc == [[True, True]]
        return ctrl

    build().run(events=events, **kwargs)         # warm the contexts
    ctrl = build()
    results, reports = ctrl.run(events=events, **kwargs)

    dep = next(e for e in ctrl.last_events if e["kind"] == "depart")
    arr = next(e for e in ctrl.last_events if e["kind"] == "arrive")
    assert arr["server"] == dep["server"] == 0
    assert arr["lane"] == dep["lane"]            # the hole was recycled
    lane = arr["lane"]

    # the newcomer's measured rate is its own traffic: ~9.6 Gbps of
    # injected load, not the predecessor's cumulative totals replayed
    # through the delta (and never negative / zero from a stale prev row)
    for w in (3, 4):
        got = reports[0][w].measured[102]
        assert 2.0 < got < 16.0, (w, got)
        m = reports[0][w].metrics[102]
        assert m.lane == lane and m.measured == got

    # final per-lane counters: tenant 0 injected for all 5 windows at the
    # same load; the recycled lane saw only the newcomer's 2 windows —
    # without the baseline reset it would also carry the predecessor's
    # 2 windows (~0.8x of tenant 0), which the bound rejects
    adm = results[0].counters["c_adm_msgs"]
    assert adm[lane] > 0
    assert adm[lane] < 0.6 * adm[0], (adm[lane], adm[0])


def test_depart_between_runs_reuses_engine_entry_then_repacks():
    """Below the fragmentation threshold a between-runs departure keeps
    the lane layout (same shapes, same compiled entry); crossing it
    compacts and pays one recompile."""
    profile = ProfileTable(n_ticks=_PROFILE_TICKS)
    rts = _mk_fleet((["synthetic50"], ["synthetic50"]), profile)
    # server 1 runs hotter so it pins the arrival-trace length M: the
    # stacked trace shape (hence the compiled signature) then survives
    # server 0's departure
    for b in range(2):
        assert rts[b].register(_spec(2 * b, 4.0, load=0.3 + 0.15 * b))
        assert rts[b].register(_spec(2 * b + 1, 4.0, load=0.3 + 0.15 * b))
    ctrl = FleetController(rts, repack_threshold=0.5)
    kwargs = dict(total_ticks=6_000, window_ticks=3_000, seeds=[1, 2],
                  load_ref_gbps=[{0: 32.0, 1: 32.0}] * 2)
    engine.cache_clear()
    ctrl.run(**kwargs)
    assert engine.cache_info()["entries"] == 1
    ctrl.depart(1)                          # 1 hole of 2 lanes: == 0.5,
    assert ctrl.lane_map(0) == [0, None]    # at the threshold — kept
    ctrl.run(**kwargs)
    assert engine.cache_info()["entries"] == 1      # same compiled entry
    ctrl.depart(0)                          # 2 holes of 2: crosses it
    assert ctrl.stats["repacks"] == 1
    assert ctrl.lane_map(0) == []
    with pytest.raises(ValueError, match="at least one registered flow"):
        ctrl.run(**kwargs)                  # server 0 is now empty


# ---------------------------------------------------------------------------
# Rebalance: migrate onto freed capacity with the stateful scorer
# ---------------------------------------------------------------------------


def test_rebalance_moves_tenant_onto_freed_capacity():
    profile = ProfileTable(n_ticks=_PROFILE_TICKS)
    rts = _mk_fleet((["synthetic50"], ["synthetic50"]), profile)
    ctrl = FleetController(rts)
    for i in range(3):                      # pile everyone onto server 0
        p = ctrl.admit(_spec(i, 9.0), server=0)
        assert p.accepted
    assert len(rts[0].table) == 3 and not rts[1].table
    moves = ctrl.rebalance()
    assert len(moves) == 1 and ctrl.stats["migrated"] == 1
    mv = moves[0]
    assert mv["src"] == 0 and mv["dst"] == 1
    assert mv["margin_after"] > mv["margin_before"]
    assert len(rts[0].table) == 2 and len(rts[1].table) == 1
    # hysteresis: the new layout is stable — and the second sweep replays
    # untouched servers' margins from the score cache
    before = profiling_stats()
    assert ctrl.rebalance() == []
    after = profiling_stats()
    assert after["score_hits"] > before["score_hits"]
    assert after["contexts"] == before["contexts"]
    # a stay-put sweep preserves control state bit-for-bit
    assert all(st.violations == 0 for st in rts[0].table.values())


def test_score_cache_standalone_in_place_fleet():
    """placement.ScoreCache is usable outside the controller: a shared
    cache across place_fleet calls reuses margins for untouched servers
    (same decisions, fewer scored contexts)."""
    profile = ProfileTable(n_ticks=_PROFILE_TICKS)
    comps = (["synthetic50"], ["synthetic50"], ["synthetic50"])
    cache = placement.ScoreCache()
    from repro.core.runtime import place_fleet
    rts = _mk_fleet(comps, profile)
    specs = [_spec(i, 9.0) for i in range(4)]
    names = ["synthetic50"] * 4
    p0 = profiling_stats()
    placed = place_fleet(rts, specs, policy=placement.SLOAware(),
                         accel_names=names, score_cache=cache)
    p1 = profiling_stats()
    # rounds after the first reuse every untouched server's score: the
    # homogeneous stream re-scores only the previous winner
    assert p1["score_hits"] > 0
    # identical decisions to an uncached fleet
    rts2 = _mk_fleet(comps, profile)
    placed2 = place_fleet(rts2, specs, policy=placement.SLOAware(),
                          accel_names=names)
    assert ([(p.server, p.accel_id, p.accepted) for p in placed]
            == [(p.server, p.accel_id, p.accepted) for p in placed2])


# ---------------------------------------------------------------------------
# Clock threading (satellite): runtime clock -> LinkSpec + profiling
# ---------------------------------------------------------------------------


def test_runtime_clock_threads_into_link_and_profiler():
    rt = ArcusRuntime([CATALOG["synthetic50"]], clock_hz=500e6)
    assert rt.link.clock_hz == 500e6
    assert rt.profile.clock_hz == 500e6
    assert rt.profile._cfg().clock_hz == 500e6
    # an explicitly passed link is the caller's override and wins
    rt2 = ArcusRuntime([CATALOG["synthetic50"]],
                       link=LinkSpec(clock_hz=125e6), clock_hz=500e6)
    assert rt2.link.clock_hz == 125e6
    assert rt2.profile.clock_hz == 125e6
    # ... as does an explicit ProfileTable clock
    pt = ProfileTable(clock_hz=777e6)
    assert pt.clock_hz == 777e6 and pt._cfg().clock_hz == 777e6


def test_profiled_capacity_clock_invariant_at_non_default_clock():
    """Profiled Gbps capacities are wall-clock quantities: with the clock
    threaded end to end, a 500 MHz runtime profiles (and admits) like a
    250 MHz one — before the fix the default 250 MHz LinkSpec under a
    500 MHz window config doubled the link's effective bandwidth."""
    ctx = [(Path.FUNCTION_CALL, 1500, 0.9)] * 2
    cap = {}
    for hz in (250e6, 500e6):
        rt = ArcusRuntime([CATALOG["ipsec32"]], clock_hz=hz,
                          profile_table=ProfileTable(
                              LinkSpec(clock_hz=hz), n_ticks=20_000))
        cap[hz] = rt.profile.profile_context(CATALOG["ipsec32"],
                                             ctx).capacity_gbps
    assert cap[500e6] == pytest.approx(cap[250e6], rel=0.05)
    # admission decisions agree across clocks
    rt5 = ArcusRuntime([CATALOG["ipsec32"]], clock_hz=500e6,
                       profile_table=ProfileTable(LinkSpec(clock_hz=500e6),
                                                  n_ticks=20_000))
    assert rt5.register(_spec(0, 10.0, msg=1500, load=0.9))
    assert rt5.register(_spec(1, 20.0, msg=1500, load=0.9))
    assert not rt5.register(_spec(2, 10.0, msg=1500, load=0.9))


def test_controller_rejects_bad_events():
    profile = ProfileTable(n_ticks=_PROFILE_TICKS)
    rts = _mk_fleet((["synthetic50"],), profile)
    assert rts[0].register(_spec(0, 5.0))
    ctrl = FleetController(rts)
    kwargs = dict(total_ticks=6_000, window_ticks=3_000,
                  load_ref_gbps=[{0: 32.0}])
    with pytest.raises(ValueError, match="outside the run"):
        ctrl.run(events=[TenantEvent.depart(7, tenant_id=0)], **kwargs)
    with pytest.raises(ValueError, match="needs a spec"):
        ctrl.run(events=[TenantEvent(0, "arrive")], **kwargs)
    with pytest.raises(ValueError, match="unknown event kind"):
        ctrl.run(events=[dataclasses.replace(
            TenantEvent.depart(0, tenant_id=0), kind="evict")], **kwargs)
    with pytest.raises(KeyError):
        ctrl.depart(42)
    with pytest.raises(ValueError, match="fleet-unique"):
        ctrl.admit(_spec(0, 1.0))


def test_run_reports_fast_path_shares():
    """``stats`` reports the share of a run's ticks on which the grant and
    service stages ran their vectorized paths alone, fleet-wide.  SHA1-HMAC
    and AES-128-CBC services outlast an 8-cycle tick, so no lane chains and
    the service stage never needs its sequential fallback."""
    rts = _mk_fleet((["sha1_hmac", "aes128_cbc"],) * 2)
    for rt in rts:
        assert rt.register(_spec(0, 2.0, msg=64, load=0.3))
        assert rt.register(_spec(1, 4.0, accel_id=1, msg=256, load=0.3))
    ctrl = FleetController(rts)
    assert ctrl.stats["srv_fast_share"] == ctrl.stats["grant_fast_share"] == 0
    ctrl.run(total_ticks=4_000, window_ticks=2_000, seeds=[1, 2],
             load_ref_gbps=[{0: 12.0, 1: 20.0}] * 2,
             sim_kwargs=dict(k_grant=8, k_srv=8, k_eg=8))
    assert ctrl.stats["srv_fast_share"] == 1.0
    assert 0.0 < ctrl.stats["grant_fast_share"] <= 1.0
