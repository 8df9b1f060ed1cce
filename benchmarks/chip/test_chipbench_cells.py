"""Every cell at a tiny size on the host: admission at B=2, the plain
reference against the program, the lower-precision control, and the
check failing under each fault the timed path can have.

One admitted, warmed cell per workload is shared by the module (set-up
dominates), and each test drives the rest of a run through ``run.measure``
with the harness's look for a chip skipped."""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import plainref  # noqa: E402
from fleetcell import load_json  # noqa: E402
import run  # noqa: E402
import tracegen  # noqa: E402

TINY = {
    "mica8.fig11a": dict(servers=2, profile_ticks=1000, window_ticks=300,
                         windows=4),
}


@pytest.fixture(scope="module", params=sorted(TINY))
def prepared(request):
    return run.setup(request.param, 2**31 + 5, require_chip=False,
                     overrides=TINY[request.param])


def _measure(prepared):
    return run.measure(prepared, 0.0, False)


def test_admits_every_tenant(prepared):
    cell = prepared["cell"]
    n = sum(len(cell.specs[b]) for b in range(cell.B))
    assert cell.ctrl.stats["admitted"] == n
    assert cell.ctrl.stats["rejected"] == 0


def test_result_line_and_reference_match(prepared):
    res = _measure(prepared)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checked"
    assert res["correct"] is True, res["checked"]
    assert all(v["value"] == 0 for v in res["checked"].values())
    assert set(res["metrics"]) == {"sim_rate", "setup_s"}
    assert res["metrics"]["sim_rate"]["value"] > 0


def test_traced_run(prepared, tmp_path):
    """A traced run records its span and reports the per-layer metrics it
    finds (the host has no device plane, so none of the trace's); the raw
    trace is deleted once reduced."""
    res = run.measure(prepared, 0.0, True, out_dir=str(tmp_path))
    assert res["correct"] is True, res["checked"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["metrics"]["compiles_in_window"]["value"] == 0.0
    out = tmp_path / f"{prepared['wl']['name']}.seed{prepared['seed']}"
    assert (out / "reduced.json").exists()
    assert (out / "trace.json.gz").exists()
    assert not (out / "raw").exists()


def test_reference_replays_real_traffic(prepared):
    """The replayed servers did work: the comparison is not of zeros."""
    cell = prepared["cell"]
    tl = cell.timeline()
    rep = plainref.replay(cell, 0)
    assert sum(rep["counters"]["c_done_msgs"]) > 50
    assert min(rep["counters"]["c_done_msgs"]) > 0
    assert tl.windows and len(tl.windows) == cell.n_windows
    assert tl.windows[0].writes is not None


def test_lower_precision_control_fails(prepared):
    cell = prepared["cell"]
    tl = cell.timeline()
    verdict = plainref.check(cell, [tl], variant="bf16")
    assert verdict["correct"] is False
    assert max(verdict["numbers"][k]["value"]
               for k in ("counter_gap", "latency_gap")) > 0


def _keep_state(inner):
    """A step that returns its state unchanged (after the first)."""
    def step(*a, carry=None, **kw):
        if carry is None:
            return inner(*a, carry=carry, **kw)
        return carry
    return step


def _half_batch(inner):
    """The second half of the fleet left out: its counters never move."""
    def step(*a, **kw):
        out = dict(inner(*a, **kw))
        B = out["c_done_msgs"].shape[0]
        for k in plainref.ENGINE_COUNTERS:
            out[k] = out[k].at[B // 2:].set(0)
        return out
    return step


def _alter_answer(inner):
    """One completed-bytes counter altered where it is produced."""
    def step(*a, **kw):
        out = dict(inner(*a, **kw))
        out["c_done_b_lo"] = out["c_done_b_lo"].at[:, 0].add(1)
        return out
    return step


@pytest.mark.parametrize("fault", [_keep_state, _half_batch, _alter_answer],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_fault_is_not_correct(prepared, fault):
    rec = prepared["cell"].recorder
    inner = rec.inner
    rec.inner = fault(inner)
    try:
        res = _measure(prepared)
    finally:
        rec.inner = inner
    assert res["correct"] is False, res["checked"]


def _doubled_refill(runtime):
    """Admission and re-planning shape every tenant at twice its plan."""
    inner = runtime.reshape_decision

    def plan(*a, **kw):
        d = inner(*a, **kw)
        p = d.params
        return dataclasses.replace(d, params=dataclasses.replace(
            p, refill_rate=2 * p.refill_rate,
            bkt_size=max(p.bkt_size, 2 * p.refill_rate)))
    return "reshape_decision", plan


def _readjust_skipped(runtime):
    """Algorithm 1's ReAdjustPattern never runs: violated tenants keep
    their registers."""
    def skip(self, *a, **kw):
        return None
    return "ArcusRuntime._re_adjust_pattern", skip


@pytest.mark.parametrize("fault", [_doubled_refill, _readjust_skipped],
                         ids=["register_replanned", "readjust_skipped"])
def test_control_fault_is_not_correct(prepared, fault, monkeypatch):
    """A fault in the controller's decisions: the registers written differ
    from the reference's, whatever the dataplane then does."""
    from repro.core import runtime
    name, fn = fault(runtime)
    if "." in name:
        monkeypatch.setattr(runtime.ArcusRuntime, name.split(".")[1], fn)
    else:
        monkeypatch.setattr(runtime, name, fn)
    res = _measure(prepared)
    assert res["correct"] is False, res["checked"]
    assert res["checked"]["plan_differ"]["value"] > 0


def test_control_rule_acts(prepared):
    """The tiny cell's traffic violates an SLO, so the control rule writes
    registers after the first window: the fault above has work to skip."""
    rep = plainref.replay(prepared["cell"], 1)
    assert any(rep["wrote"][1:])


# ---------------------------------------------------------------------------
# A split deployment: large messages that the paper's re-sizing splits
# ---------------------------------------------------------------------------

def _split_overrides() -> dict:
    """``mica8.fig11a`` turned into a deployment of one ``aes256`` engine
    a server, with a 4 KiB stream beside a 64 KiB one (over ``aes256``'s
    split threshold of 16 KiB), both ``FUNCTION_CALL`` at 8 Gbps."""
    from repro.core.accelerator import CATALOG
    keys = next(iter(load_json("configs", "mica-kv-crypto-b8.json")
                     ["accelerators"].values()))

    def tenant(fid, cls, msg, load_ref):
        return {"class": cls, "flow_id": fid, "accel": 0,
                "path": "FUNCTION_CALL", "msg_bytes": msg, "load": 0.5,
                "load_ref_gbps": load_ref, "slo_gbps": 8.0, "priority": 1,
                "weight": 1.0}
    return dict(
        servers=2, profile_ticks=2000, window_ticks=1000, windows=6,
        complements=[["aes256"]],
        accelerators={"aes256": {k: getattr(CATALOG["aes256"], k)
                                 for k in keys}},
        tenants=[tenant(0, "stream", 4096, 20.0),
                 tenant(1, "large", 65536, 40.0)])


@pytest.fixture(scope="module")
def split_prepared():
    return run.setup("mica8.fig11a", 2**31 + 23, require_chip=False,
                     overrides=_split_overrides())


def _fed_in_chunks(cell, scale: int = 1) -> list:
    """Each server's traces split as the program's own shaper splits
    them (``reshape_decision``'s size, times ``scale``, by
    ``reshape_trace``): the stand-in for the split that the program's
    normal path does not make yet."""
    from repro.core.flow import SLO
    from repro.core.shaper import reshape_decision, reshape_trace
    accels, _link, _flow_spec = cell._program_objects()
    fed = []
    for b in range(cell.B):
        times, sizes = cell.traces[b]
        rows = []
        for i, (t, _p) in enumerate(cell.specs[b]):
            acc = accels[cell.complement(b)[t["accel"]]]
            chunk = reshape_decision(acc, SLO.gbps(t["slo_gbps"]),
                                     t["msg_bytes"]).resize_to
            rows.append((times[i], sizes[i]) if chunk is None
                        else reshape_trace(times[i], sizes[i], scale * chunk))
        m = max(len(r[0]) for r in rows)
        arr_t = np.full((len(rows), m), tracegen.INF_I32, np.int32)
        arr_s = np.zeros((len(rows), m), np.int32)
        for i, (t, sz) in enumerate(rows):
            arr_t[i, :len(t)], arr_s[i, :len(sz)] = t, sz
        fed.append((arr_t, arr_s))
    return fed


def _feed(monkeypatch, fed) -> None:
    """Every ``FleetController.run`` gets ``fed`` as its arrivals."""
    from repro.core.controller import FleetController
    inner = FleetController.run

    def run_fed(self, **kw):
        return inner(self, **dict(kw, arrivals=fed))
    monkeypatch.setattr(FleetController, "run", run_fed)


def test_split_deployment_matches(split_prepared, monkeypatch):
    """Fed the chunks its shaper decides, the program agrees with the
    reference's replay of the whole messages on every number, and the
    split lane did real work under the bucket of four chunks."""
    cell = split_prepared["cell"]
    _feed(monkeypatch, _fed_in_chunks(cell))
    res = _measure(split_prepared)
    assert res["correct"] is True, res["checked"]
    assert all(v["value"] == 0 for v in res["checked"].values())
    rep = plainref.replay(cell, 0)
    assert rep["registers"][0][1][1] == 4 * 4096
    assert rep["counters"]["c_done_msgs"][1] > 2 * 16


def _whole_messages(monkeypatch, cell):
    """The program's normal path today: whole messages, never split."""


def _double_chunks(monkeypatch, cell):
    """Chunks of twice the decided size."""
    _feed(monkeypatch, _fed_in_chunks(cell, scale=2))


def _chunk_bucket_dropped(monkeypatch, cell):
    """Split arrivals, but admission and re-planning write the registers
    of an unsplit tenant (no bucket of four chunks)."""
    from repro.core import runtime
    _feed(monkeypatch, _fed_in_chunks(cell))
    inner = runtime.reshape_decision

    def plan(accel, slo, msg_bytes, **kw):
        d = inner(accel, slo, msg_bytes, **kw)
        if d.resize_to is None:
            return d
        return dataclasses.replace(
            d, params=inner(accel, slo, d.resize_to, **kw).params)
    monkeypatch.setattr(runtime, "reshape_decision", plan)


@pytest.mark.parametrize("fault", [_whole_messages, _double_chunks,
                                   _chunk_bucket_dropped],
                         ids=["whole_messages", "double_chunks",
                              "chunk_bucket_dropped"])
def test_split_fault_is_not_correct(split_prepared, monkeypatch, fault):
    fault(monkeypatch, split_prepared["cell"])
    res = _measure(split_prepared)
    assert res["correct"] is False, res["checked"]
    if fault is _chunk_bucket_dropped:
        assert res["checked"]["plan_differ"]["value"] > 0


def test_exits_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "mica8.fig11a", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
