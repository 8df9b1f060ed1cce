"""The benchmark's traffic and deployment data: pinned trace digests, the
generator copy against the program's generators, and the configuration
files against the program's accelerator catalog."""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tracegen  # noqa: E402
from fleetcell import Cell  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(HERE))


def _workloads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {w["name"]: w for w in json.load(f)["workloads"]}


#: same seed, same traces: the yardstick's traffic may not move.  Digests
#: of server 0's and server 1's incumbent traces at seed 12345.
PINNED = {
    "mica8.fig11a": ("329fe19c5892327d", "c66ad3be259759a8"),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_traffic_digest_pinned(name):
    cell = Cell(_workloads()[name], 12345,
                overrides=dict(servers=2, window_ticks=200))
    got = tuple(tracegen.digest(*cell.traces[b]) for b in range(2))
    assert got == PINNED[name]
    again = Cell(_workloads()[name], 12345,
                 overrides=dict(servers=2, window_ticks=200))
    assert [tracegen.digest(*t) for t in again.traces] == list(got)
    other = Cell(_workloads()[name], 2**31 + 12345,
                 overrides=dict(servers=2, window_ticks=200))
    assert tracegen.digest(*other.traces[0]) != got[0]


PATTERNS = [
    [dict(process="poisson")] * 3,
    [dict(process="onoff", burst_len=128, duty=0.5)],
    [dict(process="mmpp", params=(("states", (1.0, 4.0)),))],
    [dict(process="mmpp", params=(("states", (1.0, 4.0)),)),
     dict(process="poisson"), dict(process="onoff", burst_len=16, duty=0.3)],
]


@pytest.mark.parametrize("kinds", PATTERNS,
                         ids=["poisson3", "onoff", "mmpp", "mixed"])
def test_generator_copy_matches_program(kinds):
    """The copy draws what the program's generators draw, so a trace the
    benchmark hands the program is the one the program would draw."""
    import repro.workloads.generators  # noqa: F401  (registers mmpp)
    from repro.core.engine import SimConfig
    from repro.core.flow import SLO, FlowSet, FlowSpec, Path, TrafficPattern
    from repro.core.sim import gen_arrivals
    sizes = (64, 256, 1500)
    pats = [tracegen.Pattern(msg_bytes=sizes[i % 3], load=0.3 + 0.1 * i,
                             load_ref_gbps=20.0, **k)
            for i, k in enumerate(kinds)]
    specs = [FlowSpec(i, i, Path.FUNCTION_CALL, 0,
                      TrafficPattern(p.msg_bytes, load=p.load,
                                     process=p.process,
                                     burst_len=p.burst_len, duty=p.duty,
                                     params=p.params), SLO.gbps(1.0))
             for i, p in enumerate(pats)]
    cfg = SimConfig(n_ticks=4000)
    want = gen_arrivals(FlowSet.build(specs), cfg, seed=2**31 + 7,
                        load_ref_gbps={i: 20.0 for i in range(len(pats))})
    got = tracegen.gen_traces(pats, 4000, 8, 250e6, 2**31 + 7)
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])


def _configs():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [c["name"] for c in json.load(f)["configs"]]


@pytest.mark.parametrize("config", _configs())
def test_config_accelerators_are_the_catalogs(config):
    from repro.core.accelerator import CATALOG
    with open(os.path.join(HERE, "configs", config + ".json")) as f:
        cfg = json.load(f)
    for name, acc in cfg["accelerators"].items():
        ref = CATALOG[name]
        for k, v in acc.items():
            assert getattr(ref, k) == v, (name, k)


def _config_with(accel: str) -> dict:
    """The deployment's file, with ``accel`` from the program's catalog
    added under the keys the file gives an accelerator."""
    from repro.core.accelerator import CATALOG
    with open(os.path.join(HERE, "configs", "mica-kv-crypto-b8.json")) as f:
        cfg = json.load(f)
    keys = next(iter(cfg["accelerators"].values()))
    cfg["accelerators"][accel] = {k: getattr(CATALOG[accel], k) for k in keys}
    return cfg


#: at the deployment's SLOs and beside them, whole messages
PLAN_CASES = [pytest.param(a, g, m, 1.0, id=f"{a}-{g}-{m}") for a, g, m in [
    ("sha1_hmac", 2.0, 64), ("aes128_cbc", 4.0, 256),
    ("aes128_cbc", 1.0, 1500), ("aes128_cbc", 28.0, 1500),
    ("sha1_hmac", 0.37, 64), ("aes128_cbc", 13.3, 512)]]
#: each accelerator's split threshold (4 x twice its optimal size): just
#: under and over it, 64 KiB and 512 KiB, at the control rule's headrooms
SPLIT_AT = {"aes256": (16384, 10.0), "synthetic50": (2048, 20.0),
            "ipsec32": (5792, 8.0), "nvme_raid0": (2048, 4.0)}
PLAN_CASES += [pytest.param(a, g, m, h, id=f"{a}-{g}-{m}-h{h}")
               for a, (edge, g) in SPLIT_AT.items()
               for m in (edge, edge + 1, 65536, 524288)
               for h in (1.0, 1.3, 2.0)]


@pytest.mark.parametrize("accel,slo_gbps,msg,headroom", PLAN_CASES)
def test_register_plan_is_the_programs(accel, slo_gbps, msg, headroom):
    """The reference's own planner gives the registers (and the chunk
    size) the program's admission and re-planning give, whole messages and
    split ones alike."""
    import plainref
    from repro.core.accelerator import CATALOG
    from repro.core.flow import SLO
    from repro.core.shaper import reshape_decision
    cfg = _config_with(accel)
    tenant = dict(accel=0, slo_gbps=slo_gbps, msg_bytes=msg)
    want = reshape_decision(CATALOG[accel], SLO.gbps(slo_gbps), msg,
                            clock_hz=cfg["clock_hz"], headroom=headroom)
    got = plainref.plan_registers(cfg, [accel], tenant, headroom)
    p = want.params
    assert got == (p.refill_rate, p.bkt_size, p.interval, p.mode)
    assert plainref.split_bytes(cfg, [accel], tenant) == want.resize_to
    if accel in SPLIT_AT:
        assert (want.resize_to is not None) == (msg > SPLIT_AT[accel][0])


def _seeded_lane(seed: int, chunk: int):
    """A padded lane of arrival times with ties, out of order in places,
    and sizes from 1 B to five chunks (most leave a remainder)."""
    rng = np.random.default_rng(seed)
    n = 300
    times = np.sort(rng.integers(0, 20000, n)).astype(np.int32)
    times[::7] = rng.integers(0, 20000, len(times[::7]))
    sizes = rng.integers(1, 5 * chunk + 1, n).astype(np.int32)
    sizes[::5] = chunk * rng.integers(1, 4, len(sizes[::5]))
    pad = 40
    return (np.concatenate([times, np.full(pad, tracegen.INF_I32, np.int32)]),
            np.concatenate([sizes, np.zeros(pad, np.int32)]))


@pytest.mark.parametrize("chunk", [512, 1448, 4096])
@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2**32 + 5])
def test_trace_split_is_the_programs(seed, chunk):
    """The reference splits a lane's trace as the program's shaper splits
    it, on seeded lanes and on a generated trace of whole messages."""
    import plainref
    from repro.core.shaper import reshape_trace
    lanes = [_seeded_lane(seed, chunk)]
    gen_t, gen_s = tracegen.gen_traces(
        [tracegen.Pattern(msg_bytes=5 * chunk + 3, load=0.5,
                          load_ref_gbps=20.0)], 4000, 8, 250e6, seed)
    lanes.append((gen_t[0], gen_s[0]))
    for times, sizes in lanes:
        want_t, want_s = reshape_trace(times, sizes, chunk)
        got_t, got_s = plainref.split_trace(times, sizes, chunk)
        assert got_t == want_t.tolist()
        assert got_s == want_s.tolist()
        assert sum(got_s) == int(sizes.sum())
