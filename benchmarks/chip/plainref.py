"""Plain reference of the Arcus dataplane, and the check that decides
``correct``.

``ServerRef`` simulates one client server tick by tick in plain Python:
per-tenant queues, token buckets, the round-robin arbiter with link budgets
and root-complex credits, accelerator queues and lanes, egress queues.  It
follows the engine's sequential semantics (one grant, one service start,
one egress pop at a time), with integer state in Python integers and the
link budgets and lane times in float32, as the configuration states.  It
imports nothing of the program and takes nothing from the run: the
accelerators, link, tenants and their SLOs come from the configuration
file, the traces from the benchmark's own generator (``tracegen.py``), and
the token-bucket registers from ``plan_registers``, the paper's recipe
(Bkt_Size fixed, Refill_Rate / Interval swept to the SLO) computed here.
A tenant whose messages are large for its accelerator (``split_bytes``) is
replayed as the paper's message re-sizing makes it: every message split
into chunks at its arrival time, under a bucket of four chunks; the program
receives the whole messages and has to split them itself.  Between windows
it applies the configuration's control rule (Arcus Algorithm 1: a violated
tenant's headroom widens and its registers are planned again) to its own
measured rates.

The controller's decisions in a timeline are the lane layout and the
registers it writes; ``check`` holds both against the reference's, and
compares, over every server of every timeline of the window:

* ``counter_gap``: the largest relative gap of a final per-lane counter
  (admitted and completed messages and bytes, drops);
* ``latency_gap``: the largest relative gap of a lane's summed completion
  latency (exact cycles) over the completion records the program keeps;
* ``rate_gap``: the largest relative gap of a per-window measured rate in
  the program's WindowReports;
* ``verdicts_differ``: window/tenant SLO verdicts that differ;
* ``plan_differ``: server-windows whose lane layout, lane masks or written
  registers differ from the reference's (written in the first window and
  after each window in which the control rule changed one).

``variant="bf16"`` computes every float32 quantity in bfloat16 instead:
the lower-precision control that the limits are set against.
"""
from __future__ import annotations

import math
from collections import deque

import numpy as np

F32 = np.float32
MODE_GBPS = 0
#: direction of each path's ingress and egress stage: 0 host-to-device,
#: 1 device-to-host, 2 off the host fabric (Arcus Fig. 2)
PATH_DIRS = {"FUNCTION_CALL": (0, 1), "INLINE_NIC_TX": (0, 2),
             "INLINE_NIC_RX": (2, 1), "INLINE_P2P": (1, 0)}
PATH_IDS = {"FUNCTION_CALL": 0, "INLINE_NIC_TX": 1, "INLINE_NIC_RX": 2,
            "INLINE_P2P": 3}
PATH_NAMES = {v: k for k, v in PATH_IDS.items()}
GRID_LOG2_MIN, GRID_LOG2_MAX, GRID_N = 5, 20, 31
COUNTERS = ("c_adm_msgs", "c_done_msgs", "c_drops", "c_adm_bytes",
            "c_done_bytes")
#: the engine carry's counter leaves (bytes split into 20-bit lo and hi)
ENGINE_COUNTERS = ("c_adm_msgs", "c_adm_b_lo", "c_adm_b_hi", "c_done_msgs",
                   "c_done_b_lo", "c_done_b_hi", "c_drops")

#: the limits each compared number is held to, set from the readings in
#: PERF.md: exact (0) where every sound run read 0; ``latency_gap`` between
#: the largest sound reading (4.8e-5: the chip's float32 ``log2`` differs
#: from the host's, so a lane's summed latency can move by a few cycles)
#: and the smallest lower-precision control reading (8.6e-3)
LIMITS = dict(counter_gap=0.0, latency_gap=1e-3, rate_gap=0.0,
              verdicts_differ=0, plan_differ=0)


# ---------------------------------------------------------------------------
# Accelerator service model (the configuration's curves, tabulated)
# ---------------------------------------------------------------------------

def _throughput_gbps(acc: dict, m: np.ndarray) -> np.ndarray:
    ref = acc["curve_ref_bytes"]
    curve = acc["curve"]
    if curve == "linear":
        f = np.ones_like(m)
    elif curve == "log":
        f = np.minimum(np.log2(1.0 + m / ref) / np.log2(1.0 + 65536.0 / ref),
                       1.0)
    elif curve == "exp":
        f = 1.0 - np.exp(-m / ref)
    else:
        raise ValueError(f"curve {curve!r} has no reference model")
    return acc["peak_gbps"] * np.maximum(f, 1e-3)


def _grid_tables(acc: dict, clock_hz: float) -> tuple[np.ndarray, np.ndarray]:
    """Service cycles and egress bytes on the log2 size grid (float32)."""
    grid = np.logspace(GRID_LOG2_MIN, GRID_LOG2_MAX, GRID_N, base=2.0)
    bps = _throughput_gbps(acc, grid) * 1e9 / 8.0
    svc = (grid / bps + acc["overhead_ns"] * 1e-9) * clock_hz
    if acc["r_kind"] == "fixed":
        eg = np.full_like(grid, float(acc["fixed_egress_bytes"]))
    else:
        eg = grid * acc["r_value"]
    return svc.astype(np.float32), eg.astype(np.float32)


def _interp(rows: np.ndarray, sizes: list[int]) -> np.ndarray:
    """Linear interpolation of grid rows at message sizes, on a log2 axis,
    evaluated in float32 by XLA on the host CPU (its ``log2`` and rounding
    are what the dataplane's own float32 arithmetic uses)."""
    import jax
    import jax.numpy as jnp

    def f(rows, m):
        m = jnp.maximum(m, 1.0)
        x = ((jnp.log2(m) - GRID_LOG2_MIN) / (GRID_LOG2_MAX - GRID_LOG2_MIN)
             * (GRID_N - 1))
        x = jnp.clip(x, 0.0, GRID_N - 1.001)
        i0 = x.astype(jnp.int32)
        frac = x - i0
        v0 = jnp.take_along_axis(rows, i0[None, :].repeat(rows.shape[0], 0),
                                 axis=1)
        v1 = jnp.take_along_axis(rows, (i0 + 1)[None, :].repeat(
            rows.shape[0], 0), axis=1)
        return v0 * (1 - frac) + v1 * frac
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        out = jax.jit(f)(jnp.asarray(rows, jnp.float32),
                         jnp.asarray(np.asarray(sizes, np.float32)))
        return np.asarray(out)


def _bf16(x):
    import ml_dtypes
    return F32(np.asarray(x, np.float32).astype(ml_dtypes.bfloat16))


# ---------------------------------------------------------------------------
# One server, tick by tick
# ---------------------------------------------------------------------------

class ServerRef:
    """The dataplane of one server over one timeline."""

    def __init__(self, config: dict, complement: list[str], width: int,
                 sizes: list[int], variant: str = "exact"):
        dp = config["dataplane"]
        self.tc = int(config["tick_cycles"])
        self.clock_hz = float(config["clock_hz"])
        self.qlen, self.aq_len = int(dp["qlen"]), int(dp["aq_len"])
        self.aq_byte_cap, self.eq_len = int(dp["aq_byte_cap"]), int(dp["eq_len"])
        self.k_arr, self.k_grant = int(dp["k_arr"]), int(dp["k_grant"])
        self.k_srv, self.k_eg = int(dp["k_srv"]), int(dp["k_eg"])
        self.comp = deque(maxlen=int(dp["comp_cap"]))
        lmax = int(dp["lmax"])
        link = config["link"]
        bpc = [g * link["efficiency"] * 1e9 / 8.0 / self.clock_hz
               for g in (link["h2d_gbps"], link["d2h_gbps"])]
        self.rnd = _bf16 if variant == "bf16" else F32
        self.bpc = [self.rnd(b) for b in bpc]
        self.ovh = F32(link["msg_overhead_bytes"])
        self.credits = int(link["credits"])
        accs = [config["accelerators"][n] for n in complement]
        self.A = len(accs)
        sizes = sorted(set(int(s) for s in sizes) | {1})
        svc_rows, eg_rows = zip(*(_grid_tables(a, self.clock_hz)
                                  for a in accs))
        svc = _interp(np.stack(svc_rows), sizes)
        eg = _interp(np.stack(eg_rows), sizes)
        self.svc = [{s: self.rnd(svc[a, i]) for i, s in enumerate(sizes)}
                    for a in range(self.A)]
        self.eg = [{s: F32(eg[a, i]) for i, s in enumerate(sizes)}
                   for a in range(self.A)]
        self.N = width
        # per lane
        self.q = [deque() for _ in range(width)]
        self.ptr = [0] * width
        self.times = [[] for _ in range(width)]
        self.szs = [[] for _ in range(width)]
        self.tok = [0] * width
        self.cyc = [0] * width
        self.reg = [(1, 1, 1, MODE_GBPS)] * width
        self.cnt = {k: [0] * width for k in COUNTERS}
        self.lane_cfg = [None] * width
        # link, credits, arbiter
        self.lres = [F32(0.0), F32(0.0)]
        self.credits_used = 0
        self.rr = 0
        # accelerators and egress
        self.aq = [deque() for _ in range(self.A)]
        self.aq_bytes = [0] * self.A
        big = F32(3e38)
        self.lanes = [[F32(0.0)] * int(a["parallelism"])
                      + [big] * (lmax - int(a["parallelism"])) for a in accs]
        self.eq = [deque(), deque(), deque()]

    # -- control-plane inputs --------------------------------------------
    def set_trace(self, lane: int, times, sizes) -> None:
        self.times[lane] = [int(t) for t in times]
        self.szs[lane] = [int(s) for s in sizes]

    def set_lanes(self, lanes: list) -> None:
        for i, ln in enumerate(lanes):
            path = PATH_NAMES[ln["path"]]
            d_in, d_eg = PATH_DIRS[path]
            self.lane_cfg[i] = (ln["accel"], d_in, d_eg,
                                path == "INLINE_NIC_RX")

    def write_registers(self, regs, first: bool) -> None:
        """``regs`` = (refill, bkt, interval, mode) arrays; the first write
        starts each bucket full, later ones keep tokens up to the new
        bucket size."""
        refill, bkt, interval, mode = (np.asarray(r).tolist() for r in regs)
        for i in range(self.N):
            self.reg[i] = (refill[i], bkt[i], interval[i], mode[i])
            if first:
                self.tok[i], self.cyc[i] = bkt[i], 0
            else:
                self.tok[i] = min(self.tok[i], bkt[i])

    # -- the tick ----------------------------------------------------------
    def tick(self, t: int) -> None:
        tc, rnd = self.tc, self.rnd
        now, now_end = t * tc, t * tc + tc
        now_f, now_end_f = F32(now), F32(now_end)
        N = self.N
        cnt = self.cnt
        # 1. token-bucket timers
        for i in range(N):
            refill, bkt, interval, _mode = self.reg[i]
            total = self.cyc[i] + tc
            k = min(total // interval, bkt // max(refill, 1) + 1)
            self.cyc[i] = total % interval
            self.tok[i] = min(self.tok[i] + k * refill, bkt)
        # 2. arrivals
        for i in range(N):
            ts = self.times[i]
            p = self.ptr[i]
            n_due = 0
            while (n_due < self.k_arr and p + n_due < len(ts)
                   and ts[p + n_due] < now_end):
                n_due += 1
            if n_due:
                q = self.q[i]
                take = min(n_due, max(self.qlen - len(q), 0))
                for j in range(take):
                    q.append((self.szs[i][p + j], ts[p + j]))
                self.ptr[i] = p + n_due
                cnt["c_drops"][i] += n_due - take
        # 3. link budgets
        budget = [rnd(rnd(self.bpc[d] * F32(tc)) + self.lres[d])
                  for d in (0, 1)]
        # 4. shaper + round-robin grants
        for _ in range(self.k_grant):
            if self.credits_used >= self.credits:
                break
            best, best_key = -1, N
            for i in range(N):
                if not self.q[i]:
                    continue
                sz = self.q[i][0][0]
                refill, bkt, interval, mode = self.reg[i]
                cost = sz if mode == MODE_GBPS else 1
                if self.tok[i] < cost:
                    continue
                a, d_in, _d_eg, _full = self.lane_cfg[i]
                if (len(self.aq[a]) >= self.aq_len
                        or self.aq_bytes[a] + sz > self.aq_byte_cap):
                    continue
                if d_in != 2 and not budget[min(d_in, 1)] > 0.0:
                    continue
                key = (i - self.rr - 1) % N
                if key < best_key:
                    best, best_key = i, key
            if best < 0:
                break
            g = best
            sz, at = self.q[g].popleft()
            refill, bkt, interval, mode = self.reg[g]
            self.tok[g] -= sz if mode == MODE_GBPS else 1
            a, d_in, _d_eg, _full = self.lane_cfg[g]
            if d_in != 2:
                budget[d_in] = rnd(budget[d_in] - (F32(sz) + self.ovh))
            self.credits_used += 1
            self.aq[a].append((sz, g, at))
            self.aq_bytes[a] += sz
            self.rr = g
            cnt["c_adm_msgs"][g] += 1
            cnt["c_adm_bytes"][g] += sz
        # 5. accelerator service, pass-major
        for _p in range(self.k_srv):
            for a in range(self.A):
                if not self.aq[a]:
                    continue
                la = self.lanes[a]
                lane = la.index(min(la))
                if not la[lane] < now_end_f:
                    continue
                sz, fl, at = self.aq[a].popleft()
                self.aq_bytes[a] -= sz
                end = rnd(max(la[lane], now_f) + self.svc[a][sz])
                la[lane] = end
                _a, _d_in, d_eg, full = self.lane_cfg[fl]
                esz = F32(sz) if full else self.eg[a][sz]
                if len(self.eq[d_eg]) < self.eq_len:
                    self.eq[d_eg].append((max(int(esz), 1), sz, fl, at,
                                          int(end)))
        # 6. egress, all three directions per step
        live = [True, True, True]
        for _ in range(self.k_eg):
            popped = 0
            for d in (0, 1, 2):
                if not live[d]:
                    continue
                eq = self.eq[d]
                if not (eq and eq[0][4] < now_end
                        and (d == 2 or budget[d] > 0.0)):
                    live[d] = False
                    continue
                esz, isz, fl, at, rd = eq.popleft()
                ser = 0
                if d < 2:
                    budget[d] = rnd(budget[d] - (F32(esz) + self.ovh))
                    ser = int(rnd(F32(esz) / self.bpc[d]))
                self.comp.append((fl, max(rd, now) + ser - at))
                cnt["c_done_msgs"][fl] += 1
                cnt["c_done_bytes"][fl] += isz
                popped += 1
            self.credits_used -= popped
            if not popped:
                break
        self.lres = [min(budget[d], F32(0.0)) for d in (0, 1)]


# ---------------------------------------------------------------------------
# The configuration's plan: lane layout and token-bucket registers
# ---------------------------------------------------------------------------

def _gbps_registers(slo_gbps: float, clock_hz: float,
                    max_interval: int = 1024) -> tuple[int, int, int]:
    """(refill, bkt, interval) for a Gbps rate: the longest interval whose
    rounded refill gives the least rate error, and a bucket of 16 refills
    (at least 512 B, at most 1 MiB, never under one refill)."""
    per_cycle = slo_gbps * 1e9 / 8.0 / clock_hz
    best = None
    for interval in range(max_interval, 0, -1):
        refill = per_cycle * interval
        if refill < 1:
            continue
        r = int(round(refill))
        err = abs(r / interval - per_cycle) / per_cycle
        if best is None or err < best[0] - 1e-12:
            best = (err, r, interval)
        if err == 0.0:
            break
    if best is None:
        raise ValueError(f"{slo_gbps} Gbps is under one byte per interval")
    _err, refill, interval = best
    return refill, max(int(max(512, min(1 << 20, 16 * refill))), refill), \
        interval


def _optimal_msg_bytes(acc: dict) -> int:
    """Smallest grid size (256 B to 64 KiB) at 95% of the peak."""
    grid = np.logspace(GRID_LOG2_MIN, GRID_LOG2_MAX, GRID_N, base=2.0)
    grid = grid[(grid >= 256) & (grid <= 65536)]
    tput = _throughput_gbps(acc, grid)
    good = grid[tput >= 0.95 * tput.max()]
    return int(good.min()) if len(good) else int(grid[-1])


def split_bytes(config: dict, complement: list[str], tenant: dict
                ) -> int | None:
    """The chunk size a tenant's messages are split to, or None: a message
    over four chunks of twice the accelerator's optimal size is split
    into chunks of that size (the paper's message re-sizing, use case 1)."""
    acc = config["accelerators"][complement[tenant["accel"]]]
    chunk = 2 * _optimal_msg_bytes(acc)
    return chunk if int(tenant["msg_bytes"]) > 4 * chunk else None


def split_trace(times, sizes, chunk: int) -> tuple[list[int], list[int]]:
    """One lane's arrivals with every message over ``chunk`` bytes split
    into chunks of ``chunk`` bytes and a remainder, each chunk at its
    message's arrival time, in stable time order; padding (size 0) is
    dropped."""
    out = []
    for t, s in zip(np.asarray(times).tolist(), np.asarray(sizes).tolist()):
        n = -(-s // chunk)
        out += [(t, min(chunk, s - j * chunk)) for j in range(n)]
    out.sort(key=lambda ts: ts[0])
    return [t for t, _s in out], [s for _t, s in out]


def plan_registers(config: dict, complement: list[str], tenant: dict,
                   headroom: float = 1.0) -> tuple[int, int, int, int]:
    """The (refill, bkt, interval, mode) registers that shape a tenant at
    its Gbps SLO times ``headroom`` on its accelerator (ingress rate: the
    SLO, or SLO / R for an expanding accelerator).  A split tenant's bucket
    holds four chunks (at least one refill), so its chunks are paced
    smoothly rather than in bursts of whole messages."""
    acc = config["accelerators"][complement[tenant["accel"]]]
    gbps = float(tenant["slo_gbps"])
    if acc["r_kind"] == "expand":
        gbps /= max(acc["r_value"], 1e-6)
    refill, bkt, interval = _gbps_registers(gbps * headroom,
                                            float(config["clock_hz"]))
    chunk = split_bytes(config, complement, tenant)
    if chunk is not None:
        bkt = max(refill, 4 * chunk)
    return refill, bkt, interval, MODE_GBPS


def plan(cell, b: int) -> tuple[list[dict], list[dict]]:
    """Server ``b``'s tenants and lanes (one per tenant, in flow-id
    order), from the configuration alone."""
    tenants = [t for t, _p in cell.specs[b]]
    lanes = [dict(accel=int(t["accel"]), path=PATH_IDS[t["path"]],
                  flow_id=int(t["flow_id"])) for t in tenants]
    return tenants, lanes


def plan_differ(cell, tl, reps: list[dict]) -> int:
    """Server-windows of timeline ``tl`` whose lanes or masks differ from
    ``plan``, or whose registers differ from those the reference's replay
    ``reps`` put in force: written in the first window and after each
    window in which the control rule changed one (a window may rewrite a
    server's registers unchanged, since the program re-packs the fleet)."""
    bad = 0
    for b in range(cell.B):
        _tenants, lanes = plan(cell, b)
        rep = reps[b]
        for w, win in enumerate(tl.windows):
            ok = (win.lanes[b] == lanes
                  and np.asarray(win.masks[b]).tolist()
                  == [True] * len(lanes))
            if win.writes is None:
                ok &= not rep["wrote"][w]
            else:
                want = [np.asarray(r, np.int64)
                        for r in zip(*rep["registers"][w])]
                got = [np.asarray(r).astype(np.int64)
                       for r in _registers(win.writes[b])]
                ok &= all(g.shape == x.shape and np.array_equal(g, x)
                          for g, x in zip(got, want))
            bad += int(not ok)
        bad += abs(len(tl.windows) - len(rep["wrote"]))
    return bad


# ---------------------------------------------------------------------------
# Replaying a server and comparing
# ---------------------------------------------------------------------------

def _registers(tb_state):
    return (tb_state.refill_rate, tb_state.bkt_size, tb_state.interval,
            tb_state.mode)


def replay(cell, b: int, variant: str = "exact") -> dict:
    """Replay server ``b`` over the cell's horizon from admission: the
    registers planned at each tenant's SLO, then re-planned by the
    configuration's control rule from the reference's own measured rates.
    Returns final per-lane counters, per-window measured rates (Gbps) by
    flow id, the completion records, and per window the registers in
    force and whether they were written before it."""
    cfg = cell.config
    tc, hz = int(cfg["tick_cycles"]), float(cfg["clock_hz"])
    rule = cfg["control"]
    tol = float(cfg["slo_tol"])
    comp = cell.complement(b)
    tenants, lanes = plan(cell, b)
    width = len(lanes)
    times, sizes = cell.traces[b]
    lane_traces = []
    for i, t in enumerate(tenants):
        chunk = split_bytes(cfg, comp, t)
        lane_traces.append((times[i], sizes[i]) if chunk is None
                           else split_trace(times[i], sizes[i], chunk))
    lane_sizes = np.concatenate([np.asarray(s) for _t, s in lane_traces])
    ref = ServerRef(cfg, comp, width, np.unique(lane_sizes).tolist(), variant)
    for i, (lt, ls) in enumerate(lane_traces):
        ref.set_trace(i, lt, ls)
    ref.set_lanes(lanes)
    headroom = [1.0] * width
    regs = [plan_registers(cfg, comp, t) for t in tenants]
    window_s = cell.window_ticks * tc / hz
    prev = [0] * width
    rates, in_force, wrote = [], [], []
    pending = True
    for w in range(cell.n_windows):
        if pending:
            ref.write_registers(list(zip(*regs)), first=(w == 0))
        in_force.append(list(regs))
        wrote.append(pending)
        t0 = w * cell.window_ticks
        for t in range(t0, t0 + cell.window_ticks):
            ref.tick(t)
        cur = ref.cnt["c_done_bytes"]
        measured = [(cur[i] - prev[i]) * 8 / window_s / 1e9
                    for i in range(width)]
        rates.append({lanes[i]["flow_id"]: measured[i]
                      for i in range(width)})
        prev = list(cur)
        pending = False
        for i, t in enumerate(tenants):
            slo = float(t["slo_gbps"])
            if not measured[i] < slo * (1 - tol):
                continue
            step = min(slo / max(measured[i], 1e-9), float(rule["step_max"]))
            headroom[i] = min(max(headroom[i] * step, 1.0),
                              float(rule["headroom_max"]))
            new = plan_registers(cfg, comp, t, headroom[i])
            if new != regs[i]:
                regs[i] = new
                pending = True
    return dict(counters={k: list(v) for k, v in ref.cnt.items()},
                rates=rates, completions=list(ref.comp),
                registers=in_force, wrote=wrote)


def _slo(cell) -> dict:
    return {t["flow_id"]: float(t["slo_gbps"])
            for t in cell.config["tenants"]}


def compare(cell, tl, b: int, rep: dict) -> dict:
    """Gaps between the program's timeline ``tl`` on server ``b`` and the
    reference's replay ``rep`` of it."""
    res = tl.results[b]
    gap = 0.0
    for k in COUNTERS:
        prog = np.asarray(res.counters[k], np.int64)
        refv = np.asarray(rep["counters"][k], np.int64)
        if prog.shape != refv.shape:
            gap = math.inf
            continue
        d = np.abs(prog - refv) / np.maximum(np.abs(refv), 1)
        gap = max(gap, float(d.max(initial=0.0)))
    # per-lane latency of the completions the program's ring holds (exact
    # cycles), summed over the lane
    hz = float(cell.config["clock_hz"])
    prog_lat = np.rint(np.asarray(res.comp_lat_s) * hz).astype(np.int64)
    comps = rep["completions"][len(rep["completions"]) - len(prog_lat):] \
        if len(prog_lat) else []
    lanes = max(len(res.counters["c_done_msgs"]), 1)
    p_sum = np.bincount(np.asarray(res.comp_flow, np.int64),
                        weights=prog_lat, minlength=lanes)
    r_sum = np.bincount(np.asarray([c[0] for c in comps], np.int64),
                        weights=np.asarray([c[1] for c in comps], np.float64),
                        minlength=lanes)
    n = max(len(p_sum), len(r_sum))
    p_sum = np.pad(p_sum, (0, n - len(p_sum)))
    r_sum = np.pad(r_sum, (0, n - len(r_sum)))
    lat_gap = float((np.abs(p_sum - r_sum) / np.maximum(np.abs(r_sum), 1))
                    .max(initial=0.0)) if len(comps) == len(prog_lat) \
        else math.inf
    rgap, vdiff = 0.0, 0
    slo = _slo(cell)
    tol = float(cell.config["slo_tol"])
    if len(tl.reports[b]) != len(rep["rates"]):
        rgap = math.inf
    for w, report in enumerate(tl.reports[b][:len(rep["rates"])]):
        ref_rates = rep["rates"][w]
        if set(report.measured) != set(ref_rates):
            rgap = math.inf
        for fid, r in ref_rates.items():
            measured = report.measured.get(fid, math.nan)
            rgap = max(rgap, abs(measured - r) / max(abs(r), 1e-3)
                       if not math.isnan(measured) else math.inf)
            ref_violated = r < slo[fid] * (1 - tol)
            vdiff += int(ref_violated != (fid in report.violated))
    return dict(counter_gap=gap, latency_gap=lat_gap, rate_gap=rgap,
                verdicts_differ=vdiff)


def check(cell, timelines, variant: str = "exact") -> dict:
    """Decide ``correct``: replay every server once, and hold every
    timeline of the window (each admits afresh and replays the same
    traces) to it, each number within its limit."""
    reps = [replay(cell, b, variant) for b in range(cell.B)]
    nums = dict(counter_gap=0.0, latency_gap=0.0, rate_gap=0.0,
                verdicts_differ=0, plan_differ=0)
    failed = 0
    for tl in timelines:
        got = dict(plan_differ=plan_differ(cell, tl, reps))
        for b in range(cell.B):
            for k, v in compare(cell, tl, b, reps[b]).items():
                got[k] = (got.get(k, 0) + v if k == "verdicts_differ"
                          else max(got.get(k, 0.0), v))
        failed += int(any(v > LIMITS[k] for k, v in got.items()))
        for k, v in got.items():
            nums[k] = nums[k] + v if k.endswith("differ") else max(nums[k], v)
    numbers = {k: dict(value=v, limit=LIMITS[k]) for k, v in nums.items()}
    ok = all(v <= LIMITS[k] for k, v in nums.items())
    return dict(correct=bool(ok), failed=failed, numbers=numbers)
