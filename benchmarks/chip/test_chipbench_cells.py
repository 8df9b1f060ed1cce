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

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import plainref  # noqa: E402
import run  # noqa: E402

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


def test_exits_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "mica8.fig11a", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
