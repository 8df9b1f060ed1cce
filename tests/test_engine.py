"""Compiled-engine tests: cache hits, donated-carry resumption, vmap batch
equivalence (incl. ragged flow counts + heterogeneous system configs), and
vectorized-stage fidelity."""
import dataclasses

import numpy as np
import pytest

import jax

from repro.core import baselines, engine, sim, token_bucket as tb
from repro.core.accelerator import CATALOG, AccelTable
from repro.core.flow import SLO, FlowSet, FlowSpec, Path, TrafficPattern
from repro.core.interconnect import ARB_PRIORITY, LinkSpec
from repro.core.runtime import ArcusRuntime
from repro.core.sim import (SHAPING_HW, SHAPING_NONE, SHAPING_SW, SimConfig,
                            gen_arrivals, gen_stall_mask, simulate,
                            simulate_batch, stack_arrivals)

_COUNTER_KEYS = ("c_adm_msgs", "c_done_msgs", "c_drops")
_EXACT_KEYS = _COUNTER_KEYS + ("c_adm_bytes", "c_done_bytes")


def _assert_results_equal(serial, batch, label=""):
    for k in _EXACT_KEYS:
        assert np.array_equal(serial.counters[k], batch.counters[k]), \
            (label, k, serial.counters[k], batch.counters[k])
    np.testing.assert_array_equal(serial.comp_flow, batch.comp_flow)
    np.testing.assert_array_equal(serial.comp_sz, batch.comp_sz)
    np.testing.assert_allclose(serial.counters["c_lat_sum"],
                               batch.counters["c_lat_sum"], rtol=1e-6)


def _scenario(n_flows=2, n_ticks=15_000, shaping=SHAPING_HW, k_grant=4,
              grant_fast=True, seed=0):
    slos = [10.0 + 5.0 * i for i in range(n_flows)]
    specs = [FlowSpec(i, i, Path.FUNCTION_CALL, 0,
                      TrafficPattern(1024, load=0.8 / n_flows,
                                     process="poisson"), SLO.gbps(s))
             for i, s in enumerate(slos)]
    flows = FlowSet.build(specs)
    cfg = SimConfig(n_ticks=n_ticks, shaping=shaping, k_grant=k_grant,
                    grant_fast=grant_fast)
    arr = gen_arrivals(flows, cfg, seed=seed,
                       load_ref_gbps={i: 55.0 for i in range(n_flows)})
    if shaping == SHAPING_HW:
        tbs = tb.pack([tb.params_for_gbps(s) for s in slos])
    else:
        big = np.full(n_flows, 2**30, np.int32)
        tbs = tb.init(big, big, np.ones(n_flows, np.int32),
                      np.zeros(n_flows, np.int32))
    accels = AccelTable.build([CATALOG["synthetic50"]])
    return flows, accels, LinkSpec(), cfg, tbs, arr


def test_batch_matches_serial_bitwise():
    """simulate_batch over 8 seeds == 8 serial simulate() calls, counter for
    counter (the engine acceptance criterion)."""
    flows, accels, link, cfg, tbs, _ = _scenario(n_ticks=8_000)
    arrs = [gen_arrivals(flows, cfg, seed=s,
                         load_ref_gbps={0: 55.0, 1: 55.0})
            for s in range(8)]
    serial = [simulate(flows, accels, link, cfg, tbs, *a) for a in arrs]
    batch = simulate_batch(flows, accels, link, cfg, [tbs] * 8,
                           *stack_arrivals(arrs))
    assert len(batch) == 8
    for s, b in zip(serial, batch):
        for k in _COUNTER_KEYS + ("c_adm_bytes", "c_done_bytes"):
            assert np.array_equal(s.counters[k], b.counters[k]), k
        np.testing.assert_array_equal(s.comp_flow, b.comp_flow)
        np.testing.assert_array_equal(s.comp_sz, b.comp_sz)
        np.testing.assert_allclose(s.counters["c_lat_sum"],
                                   b.counters["c_lat_sum"], rtol=1e-6)


def test_batch_heterogeneous_registers():
    """Each batch element honours its own TBState registers."""
    flows, accels, link, cfg, _, arr = _scenario(n_ticks=20_000)
    tb_a = tb.pack([tb.params_for_gbps(5.0), tb.params_for_gbps(5.0)])
    tb_b = tb.pack([tb.params_for_gbps(20.0), tb.params_for_gbps(20.0)])
    res = simulate_batch(flows, accels, link, cfg, [tb_a, tb_b],
                         *stack_arrivals([arr, arr]))
    for b, slo in ((0, 5.0), (1, 20.0)):
        got = res[b].mean_ingress_gbps(0, flows)
        assert abs(got - slo) / slo < 0.1, (b, got)


def test_run_managed_compiles_once():
    """10 managed windows (register write each window) hit one engine entry
    with exactly one XLA trace — zero recompiles after window 0."""
    rt = ArcusRuntime([CATALOG["synthetic50"]])
    for i, slo in enumerate((10.0, 20.0)):
        rt.register(FlowSpec(i, i, Path.FUNCTION_CALL, 0,
                             TrafficPattern(1024, load=0.45), SLO.gbps(slo)))
    engine.cache_clear()          # registration profiling uses its own sims
    _, reports = rt.run_managed(total_ticks=30_000, window_ticks=3_000,
                                load_ref_gbps={0: 32.0, 1: 32.0})
    assert len(reports) == 10
    info = engine.cache_info()
    assert info["entries"] == 1, info
    assert info["traces"] == 1, info


def test_live_reconfiguration_cache_hit():
    """A mid-flight register rewrite (new TBState + resumed carry) reuses
    the compiled engine and still changes the shaped rate."""
    flows, accels, link, cfg, _, _ = _scenario(n_flows=1, n_ticks=40_000)
    full = dataclasses.replace(cfg, n_ticks=80_000)
    arr = gen_arrivals(flows, full, load_ref_gbps={0: 50.0})
    engine.cache_clear()
    res1, carry = simulate(flows, accels, link, cfg,
                           tb.pack([tb.params_for_gbps(10)]), *arr,
                           return_carry=True)
    res2 = simulate(flows, accels, link, cfg,
                    tb.pack([tb.params_for_gbps(20)]), *arr,
                    t0_ticks=40_000, carry=carry)
    info = engine.cache_info()
    assert info["entries"] == 1 and info["traces"] == 1, info
    window_s = cfg.n_ticks * cfg.tick_cycles / cfg.clock_hz
    n1 = res1.counters["c_done_msgs"][0]
    n2 = res2.counters["c_done_msgs"][0] - n1
    assert abs(n1 * 1024 * 8 / window_s / 1e9 - 10) < 1.5
    assert abs(n2 * 1024 * 8 / window_s / 1e9 - 20) < 2.0


def test_vectorized_grants_match_sequential():
    """The RR fast path (masked key sort + prefix sums) produces the same
    counters as the sequential argmin loop, shaped and unshaped, at both
    low and high contention."""
    for n_flows, shaping in ((2, SHAPING_HW), (8, SHAPING_HW),
                             (8, SHAPING_NONE)):
        f, a, l, cfg, t, arr = _scenario(n_flows=n_flows, n_ticks=10_000,
                                         shaping=shaping, k_grant=8,
                                         grant_fast=True)
        cfg_seq = dataclasses.replace(cfg, grant_fast=False)
        r_fast = simulate(f, a, l, cfg, t, *arr)
        r_seq = simulate(f, a, l, cfg_seq, t, *arr)
        for k in _COUNTER_KEYS + ("c_adm_bytes", "c_done_bytes"):
            assert np.array_equal(r_fast.counters[k], r_seq.counters[k]), \
                (n_flows, shaping, k)


def _ragged_scenario(n_flows, n_ticks=6_000, seed=None):
    """One batch element with its own flow count / SLOs / registers."""
    specs = [FlowSpec(i, i, Path.FUNCTION_CALL, 0,
                      TrafficPattern(1024, load=0.8 / n_flows,
                                     process="poisson"),
                      SLO.gbps(5.0 + 3.0 * i))
             for i in range(n_flows)]
    flows = FlowSet.build(specs)
    cfg = SimConfig(n_ticks=n_ticks, shaping=SHAPING_HW)
    arr = gen_arrivals(flows, cfg, seed=seed if seed is not None else n_flows,
                       load_ref_gbps={i: 50.0 for i in range(n_flows)})
    tbs = tb.pack([tb.params_for_gbps(5.0 + 3.0 * i)
                   for i in range(n_flows)])
    return flows, cfg, arr, tbs


def test_ragged_batch_matches_serial_bitwise():
    """simulate_batch over FlowSets with DIFFERENT flow counts (padded +
    flow-masked) returns counters bitwise-equal to unpadded serial runs —
    the tentpole acceptance criterion."""
    accels = AccelTable.build([CATALOG["synthetic50"]])
    link = LinkSpec()
    els = [_ragged_scenario(n) for n in (1, 3, 2, 5)]
    serial = [simulate(f, accels, link, c, t, *a) for f, c, a, t in els]
    batch = simulate_batch([f for f, _, _, _ in els], accels, link,
                           els[0][1], [t for _, _, _, t in els],
                           *stack_arrivals([a for _, _, a, _ in els]))
    assert len(batch) == len(els)
    for s, b, (f, *_r) in zip(serial, batch, els):
        assert len(b.counters["c_adm_msgs"]) == f.n   # sliced to unpadded n
        _assert_results_equal(s, b, label=f"n={f.n}")


def test_heterogeneous_system_configs_batch_bitwise():
    """Arcus (HW shaping + RR) and Bypassed_noTS_panic (no shaping +
    priority arbiter) differ only in traced mode words: they run as lanes
    of ONE batched engine call, bitwise-equal to their serial runs."""
    flows, cfg, arr, tbs = _ragged_scenario(2, n_ticks=8_000)
    accels = AccelTable.build([CATALOG["synthetic50"]])
    link = LinkSpec()
    cfg_arcus = cfg
    cfg_panic = dataclasses.replace(cfg, shaping=SHAPING_NONE,
                                    arbiter=ARB_PRIORITY)
    tbs_panic = baselines.make_tb_state(baselines.BYPASSED_NO_TS_PANIC,
                                        [tb.TBParams(1, 1, 1)] * 2)
    s_arcus = simulate(flows, accels, link, cfg_arcus, tbs, *arr)
    s_panic = simulate(flows, accels, link, cfg_panic, tbs_panic, *arr)
    engine.cache_clear()
    batch = simulate_batch(flows, accels, link, [cfg_arcus, cfg_panic],
                           [tbs, tbs_panic], *stack_arrivals([arr, arr]))
    assert engine.cache_info()["entries"] == 1
    _assert_results_equal(s_arcus, batch[0], "arcus")
    _assert_results_equal(s_panic, batch[1], "panic")
    # the two modes really behaved differently (shaped vs free-for-all)
    assert (batch[1].counters["c_done_msgs"].sum()
            > batch[0].counters["c_done_msgs"].sum())


def test_batched_configs_reject_static_mismatch():
    flows, cfg, arr, tbs = _ragged_scenario(2, n_ticks=1_000)
    cfg2 = dataclasses.replace(cfg, k_grant=2)   # structural field differs
    with pytest.raises(ValueError, match="traced fields"):
        simulate_batch(flows, AccelTable.build([CATALOG["synthetic50"]]),
                       LinkSpec(), [cfg, cfg2], [tbs, tbs],
                       *stack_arrivals([arr, arr]))


def _sw_scenario(n_ticks=8_000):
    flows, cfg, arr, _ = _ragged_scenario(2, n_ticks=n_ticks)
    cfg = dataclasses.replace(cfg, shaping=SHAPING_SW)
    tbs = baselines.make_tb_state(baselines.HOST_TS_REFLEX,
                                  [tb.params_for_gbps(5.0),
                                   tb.params_for_gbps(8.0)])
    return flows, cfg, arr, tbs


def test_stall_mask_shared_vs_batched():
    """A shared [T] stall mask applies to every batch element; a [B, T]
    mask applies per element — both match serial runs bitwise (the
    docstring's promise, previously untested)."""
    flows, cfg, arr, tbs = _sw_scenario()
    accels = AccelTable.build([CATALOG["synthetic50"]])
    link = LinkSpec()
    # dense stall process (many events per window) so the two masks
    # observably diverge within a short test run
    m1 = gen_stall_mask(cfg, seed=1, stall_rate_hz=100_000.0,
                        stall_us=(10.0, 60.0))
    m2 = gen_stall_mask(cfg, seed=2, stall_rate_hz=100_000.0,
                        stall_us=(10.0, 60.0))
    assert m1.any() and m2.any() and not np.array_equal(m1, m2)
    s1 = simulate(flows, accels, link, cfg, tbs, *arr, stall_mask=m1)
    s2 = simulate(flows, accels, link, cfg, tbs, *arr, stall_mask=m2)
    # shared [T]: every element sees mask m1
    shared = simulate_batch(flows, accels, link, cfg, [tbs, tbs],
                            *stack_arrivals([arr, arr]), stall_mask=m1)
    _assert_results_equal(s1, shared[0], "shared0")
    _assert_results_equal(s1, shared[1], "shared1")
    # per-element [B, T]
    per_el = simulate_batch(flows, accels, link, cfg, [tbs, tbs],
                            *stack_arrivals([arr, arr]),
                            stall_mask=np.stack([m1, m2]))
    _assert_results_equal(s1, per_el[0], "batched0")
    _assert_results_equal(s2, per_el[1], "batched1")
    # the two masks produced genuinely different dataplanes
    assert not np.array_equal(per_el[0].comp_t_s, per_el[1].comp_t_s)


def test_vectorized_stages_match_sequential():
    """The vectorized accelerator-service + egress stages (prefix-sum slot
    assignment, with the sequential fallback for lane-chaining ticks)
    produce the same counters as the sequential loops — across shaping
    modes and in a chaining-heavy config (service shorter than a tick)."""
    cases = [
        dict(shaping=SHAPING_HW, tick_cycles=8),
        dict(shaping=SHAPING_NONE, tick_cycles=8),
        # tick_cycles=64 >> ~41-cycle service: lanes chain back-to-back
        # within one tick, forcing the sequential fallback path
        dict(shaping=SHAPING_NONE, tick_cycles=64),
        dict(shaping=SHAPING_SW, tick_cycles=8),
    ]
    accels = AccelTable.build([CATALOG["synthetic50"]])
    link = LinkSpec()
    for case in cases:
        n = 2 if case["shaping"] == SHAPING_SW else 4
        flows, cfg, arr, tbs = _ragged_scenario(n, n_ticks=5_000)
        # k_srv=8 (A=1) crosses the service-vectorization width threshold
        cfg = dataclasses.replace(cfg, k_srv=8, k_eg=8, **case)
        if case["shaping"] == SHAPING_SW:
            tbs = baselines.make_tb_state(
                baselines.HOST_TS_REFLEX,
                [tb.params_for_gbps(5.0), tb.params_for_gbps(8.0)])
        cfg_seq = dataclasses.replace(cfg, stage_fast=False)
        r_vec = simulate(flows, accels, link, cfg, tbs, *arr)
        r_seq = simulate(flows, accels, link, cfg_seq, tbs, *arr)
        for k in _EXACT_KEYS:
            assert np.array_equal(r_vec.counters[k], r_seq.counters[k]), \
                (case, k, r_vec.counters[k], r_seq.counters[k])
        np.testing.assert_array_equal(r_vec.comp_flow, r_seq.comp_flow)
        np.testing.assert_array_equal(r_vec.comp_t_s, r_seq.comp_t_s)


def test_distinct_configs_get_distinct_cache_entries():
    flows, accels, link, cfg, tbs, arr = _scenario(n_ticks=2_000)
    engine.cache_clear()
    simulate(flows, accels, link, cfg, tbs, *arr)
    assert engine.cache_info()["entries"] == 1
    cfg2 = dataclasses.replace(cfg, k_grant=2)
    simulate(flows, accels, link, cfg2, tbs, *arr)
    assert engine.cache_info()["entries"] == 2
    # same configs again: no growth
    simulate(flows, accels, link, cfg, tbs, *arr)
    simulate(flows, accels, link, cfg2, tbs, *arr)
    assert engine.cache_info() == {"entries": 2, "traces": 2}


def test_donated_carry_not_reused_by_engine():
    """The caller's TBState survives simulate() (the engine copies register
    arrays into the donated carry instead of aliasing them)."""
    flows, accels, link, cfg, tbs, arr = _scenario(n_ticks=2_000)
    simulate(flows, accels, link, cfg, tbs, *arr)
    # would raise on a deleted (donated) buffer
    assert int(np.asarray(tbs.tokens).sum()) >= 0
    simulate(flows, accels, link, cfg, tbs, *arr)


# ---------------------------------------------------------------------------
# Fleet-wide branch choice of the batched tick (engine._fast_or_fallback)
# ---------------------------------------------------------------------------


def _fig11a_fleet(B=8, n_ticks=2_000):
    """The Fig. 11(a) inline-NIC servers: SHA1-HMAC + AES-128-CBC (100 ns
    overhead, so a service never ends within an 8-cycle tick), two MICA
    users and a migration stream, stage widths 8."""
    specs = [FlowSpec(0, 0, Path.INLINE_NIC_RX, 0,
                      TrafficPattern(64, load=0.3, process="poisson"),
                      SLO.gbps(2.0)),
             FlowSpec(1, 1, Path.INLINE_NIC_RX, 1,
                      TrafficPattern(256, load=0.3, process="poisson"),
                      SLO.gbps(4.0)),
             FlowSpec(2, 2, Path.INLINE_NIC_TX, 1,
                      TrafficPattern(1500, load=0.9, process="onoff"),
                      SLO.gbps(1.0), weight=0.05)]
    flows = FlowSet.build(specs)
    cfg = SimConfig(n_ticks=n_ticks, k_grant=8, k_srv=8, k_eg=8)
    accels = AccelTable.build([CATALOG["sha1_hmac"], CATALOG["aes128_cbc"]])
    arrs = [gen_arrivals(flows, cfg, seed=s,
                         load_ref_gbps={0: 12.0, 1: 20.0, 2: 36.0})
            for s in range(B)]
    tbs = tb.pack([tb.params_for_gbps(g) for g in (2.0, 4.0, 1.0)])
    return flows, [accels] * B, cfg, arrs, [tbs] * B


def _chaining_fleet(names, n_ticks=2_000):
    """One server per accelerator name, at 64-cycle ticks: synthetic50
    serves a 1 KiB message in about 41 cycles, so its lanes chain within a
    tick; sha1_hmac takes over 64 cycles and never chains."""
    flows, cfg, _, tbs = _ragged_scenario(4, n_ticks=n_ticks)
    cfg = dataclasses.replace(cfg, tick_cycles=64, k_srv=8, k_eg=8)
    arrs = [gen_arrivals(flows, cfg, seed=s,
                         load_ref_gbps={i: 50.0 for i in range(4)})
            for s in range(len(names))]
    accels = [AccelTable.build([CATALOG[n]]) for n in names]
    return flows, accels, cfg, arrs, [tbs] * len(names)


def _window_results(raw, cfg, n_flows):
    host = jax.device_get({k: raw[k] for k in
                           sim._RESULT_KEYS + engine.FAST_TICK_KEYS})
    fast = {k: np.atleast_1d(host.pop(k)) for k in engine.FAST_TICK_KEYS}
    els = ([host] if np.ndim(host["comp_n"]) == 0 else
           [{k: v[b] for k, v in host.items()}
            for b in range(len(host["comp_n"]))])
    res = []
    for el in els:
        for k in sim._PER_FLOW_KEYS:
            el[k] = el[k][:n_flows]
        res.append(sim._collect_result(el, cfg, 0))
    return res, fast


@pytest.mark.parametrize("fleet", ["all_fast", "none_fast", "mixed"])
def test_fleet_branch_choice_batch_matches_serial(fleet):
    """The batched tick runs a stage's vectorized path alone on ticks where
    every server qualifies and each server's own branch otherwise: results
    stay bitwise those of serial runs, and the fast-tick counters count
    fleet-uniform ticks (batched) or the server's own fast ticks (serial)."""
    if fleet == "all_fast":
        flows, accels, cfg, arrs, tbss = _fig11a_fleet(B=4)
    elif fleet == "none_fast":
        flows, accels, cfg, arrs, tbss = _chaining_fleet(["synthetic50"] * 3)
    else:
        flows, accels, cfg, arrs, tbss = _chaining_fleet(
            ["synthetic50", "sha1_hmac", "sha1_hmac"])
    link = LinkSpec()
    n = cfg.n_ticks
    arr_t, arr_sz = stack_arrivals(arrs)   # one trace shape: one compile
    batch, b_fast = _window_results(
        engine.run_window_batch(flows, accels, link, cfg, tbss,
                                arr_t, arr_sz), cfg, flows.n)
    serial, s_fast = [], {k: [] for k in engine.FAST_TICK_KEYS}
    for b, (a, t) in enumerate(zip(accels, tbss)):
        (r,), f = _window_results(
            engine.run_window(flows, a, link, cfg, t, arr_t[b], arr_sz[b]),
            cfg, flows.n)
        serial.append(r)
        for k in engine.FAST_TICK_KEYS:
            s_fast[k].append(int(f[k][0]))
    for b, (s, r) in enumerate(zip(serial, batch)):
        _assert_results_equal(s, r, label=f"{fleet} server {b}")

    for k in engine.FAST_TICK_KEYS:
        got = b_fast[k]
        # one fleet-wide choice per tick: every server reads the same count
        assert (got == got[0]).all(), (k, got)
        # a fleet-uniform tick is fast on every server, and each server's
        # slow ticks can rule out at most that many fleet-uniform ones
        assert got[0] <= min(s_fast[k]), (k, got, s_fast[k])
        assert got[0] >= n - sum(n - c for c in s_fast[k]), (k, got, s_fast)
    srv = "c_srv_fast_ticks"
    if fleet == "all_fast":
        assert b_fast[srv][0] == n and s_fast[srv] == [n] * len(arrs)
    else:
        assert b_fast[srv][0] < n
    if fleet == "none_fast":
        assert all(c < n for c in s_fast[srv]), s_fast[srv]
    if fleet == "mixed":
        # only server 0 chains: the fleet's uniform ticks are exactly the
        # ticks its own predicate was true
        assert s_fast[srv][1:] == [n] * (len(arrs) - 1), s_fast[srv]
        assert b_fast[srv][0] == s_fast[srv][0] < n


def _conds_by_stage(jaxpr, found=None):
    """(stage, index aval) of every cond in a jaxpr, sub-jaxprs included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            stack = str(eqn.source_info.name_stack)
            stage = next((s for s in ("grant", "service") if s in stack),
                         None)
            found.append((stage, eqn.invars[0].aval))
        for p in eqn.params.values():
            for q in (p if isinstance(p, (list, tuple)) else [p]):
                if hasattr(q, "jaxpr") and hasattr(q, "consts"):
                    _conds_by_stage(q.jaxpr, found)
                elif hasattr(q, "eqns"):
                    _conds_by_stage(q, found)
    return found


def test_batched_tick_keeps_unbatched_stage_conds():
    """At the Fig. 11(a) cell's shape (B=8, three lanes, two accelerators,
    stage widths 8) the batched window program keeps a real conditional
    for the grant and the service stage: its index is one scalar for the
    fleet, so the sequential fallbacks do not run beside the vectorized
    paths on every tick (a batched index would lower to a select)."""
    flows, accels, cfg, arrs, tbss = _fig11a_fleet(B=8, n_ticks=1_500)
    closed = jax.make_jaxpr(lambda: engine.run_window_batch(
        flows, accels, LinkSpec(), cfg, tbss, *stack_arrivals(arrs)))()
    conds = _conds_by_stage(closed.jaxpr)
    for stage in ("grant", "service"):
        idx = [aval for s, aval in conds if s == stage]
        assert idx, (stage, conds)
        assert all(a.shape == () for a in idx), (stage, idx)
