"""Compiled dataplane engine: cached jit, donated carries, vmap batching.

The cycle-accurate simulator in ``repro.core.sim`` used to close its jitted
``lax.scan`` over every input (arrival trace, stall mask, window start, flow
tables, link parameters), so *each* ``simulate()`` call re-traced and
re-compiled the whole tick loop.  The control plane (``ArcusRuntime.run_managed``,
Algorithm 1) reconfigures shaping registers every window, which made XLA
compile time — not simulated ticks — the dominant cost.

This module splits trace-time constants from runtime data:

* **static** (compile-cache key): the *structural* ``SimConfig`` fields (tick
  counts, queue depths, grant widths) plus the shapes of the flow set,
  accelerator tables, arrival traces and stall mask;
* **traced** (plain arguments): the arrival trace, stall mask, window start
  ``t0``, per-flow routing/weight tables, the **per-flow validity mask**,
  accelerator service tables, link rates, the shaping / arbiter mode words,
  the software-shaping delay model, and the full carry — including the
  TBState parameter "registers", so a live register write (Sec. 5.3.1
  "Dynamism") never retraces.

Because the shaping mode and arbiter are traced *mode words* rather than
compile-time constants, heterogeneous system configurations (Arcus vs the
Host/Bypassed baselines of Sec. 5.1) share one compiled engine and can run
as lanes of the same ``jax.vmap`` batch.

Compiled entry points are cached at module level (``_RUN_CACHE``); the carry
is donated (``donate_argnums``) so window-to-window resumption reuses device
buffers instead of copying the ~30-array carry each window.

``run_window_batch`` wraps the same core in ``jax.vmap`` over a leading batch
axis of (arrival trace, TBState registers, optionally flow tables, system
mode words, accelerator/link tables and stall masks).  Flow sets with
*different flow counts* are padded to a shared ``n_flows_max`` and masked
with ``fl_mask``: padded lanes never receive arrivals, are never eligible
for grants, and the arbiter keys are computed modulo the *active* flow
count, so every counter of an active lane is bitwise-identical to a serial
unpadded run.  The vmap axis is named (``FLEET_AXIS``): where a tick stage
chooses between its vectorized path and a sequential fallback, it chooses
once for the fleet when every server qualifies, so the fallback runs only
on ticks where some server needs it (``_fast_or_fallback``).

Accelerator tables batch the same way: elements with *different accelerator
counts* are padded to a shared ``n_accels_max`` (``pad_accel_table``) with a
per-accelerator validity mask ``ac_mask`` threaded through the pipeline —
padded accelerators have every lane disabled, are never routed to (flow
tables only reference active accelerators), never start service, and the
software-shaping host-delay LCG advances once per *active* service
iteration only, so a padded element stays bitwise-identical to its serial
unpadded run in every shaping mode.

``run_window_batch`` also accepts a resumed ``carry`` (with fresh per-element
TBState registers applied, exactly like ``run_window``): this is what lets
``ArcusRuntime.run_managed_batch`` drive B client servers' control loops as
one compiled program, re-provisioning token buckets between windows.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import token_bucket as tb
from repro.core.accelerator import GRID_N, AccelTable, interp_grid
from repro.core.flow import FlowSet, Path
from repro.core.interconnect import (ARB_PRIORITY, ARB_RR, ARB_WFQ, ARB_WRR,
                                     LinkSpec)

SHAPING_NONE = 0
SHAPING_HW = 1
SHAPING_SW = 2

INF_I32 = np.int32(2**31 - 1)
_LCG_A = np.int32(1103515245)
_LCG_C = np.int32(12345)


def _own_tb(tb_state: tb.TBState) -> tb.TBState:
    """Copy TBState leaves into engine-owned buffers.

    The carry is donated to the compiled engine, so it must not alias the
    caller's arrays (donation would invalidate them) nor alias itself
    (``tb.init`` starts ``tokens`` as the very ``bkt_size`` buffer, and XLA
    rejects donating one buffer twice)."""
    return jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                  tb_state)


@dataclasses.dataclass(frozen=True)
class SimConfig:
    n_ticks: int
    tick_cycles: int = 8
    clock_hz: float = 250e6
    qlen: int = 256            # per-flow queue slots
    aq_len: int = 256          # per-accelerator queue slots
    aq_byte_cap: int = 1 << 20  # shared accel input buffer (bytes) — large
                                # messages congest it (Sec. 3.1 / Fig. 8)
    eq_len: int = 2048         # per-direction egress queue slots
    comp_cap: int = 1 << 15    # completion record ring capacity
    k_arr: int = 4             # max arrivals drained per flow per tick
    k_grant: int = 4           # max arbiter grants per tick
    k_srv: int = 2             # service starts per accelerator per tick
    k_eg: int = 4              # egress pops per direction per tick
    lmax: int = 16             # max accelerator lanes
    shaping: int = SHAPING_HW   # traced mode word — NOT in the compile key
    arbiter: int = ARB_RR       # traced mode word — NOT in the compile key
    # software-shaping pathology model (traced — NOT in the compile key)
    sw_host_delay_cycles: int = 500      # ~2 us base host processing delay
    sw_jitter_cycles: int = 2500         # up to +10 us heavy-tail jitter
    # one-shot vectorized grant selection for uncontended RR ticks (falls
    # back to the sequential argmin loop whenever semantics require it)
    grant_fast: bool = True
    # one-shot vectorized accelerator-service and egress stages.  Egress is
    # always vectorized under this flag; the service stage additionally
    # requires A * k_srv >= 8 (below that the unrolled loop wins on CPU)
    # and falls back to the sequential loop whenever a lane could chain
    # back-to-back messages within one tick.
    stage_fast: bool = True
    # service-vectorization width threshold: the one-shot service stage
    # engages when A * k_srv >= service_vec_min (8 was measured on XLA-CPU;
    # other backends want other knees).  Structural — part of the compile
    # key, NOT traced.  Default comes from $REPRO_SERVICE_VEC_MIN.
    service_vec_min: int = dataclasses.field(
        default_factory=lambda: int(
            os.environ.get("REPRO_SERVICE_VEC_MIN", "8")))

    @property
    def seconds(self) -> float:
        return self.n_ticks * self.tick_cycles / self.clock_hz


#: SimConfig fields passed to the engine as traced values: two SimConfigs
#: differing only in these share one compiled executable (and may be lanes
#: of the same batch).
TRACED_CFG_FIELDS = ("shaping", "arbiter", "sw_host_delay_cycles",
                     "sw_jitter_cycles")


def _static_cfg(cfg: SimConfig) -> SimConfig:
    """Canonical compile-cache form of a SimConfig (traced fields zeroed)."""
    return dataclasses.replace(
        cfg, **{f: 0 for f in TRACED_CFG_FIELDS})


# ---------------------------------------------------------------------------
# Carry construction
# ---------------------------------------------------------------------------


def init_carry(flows: FlowSet, accels: AccelTable, cfg: SimConfig,
               tb_state: tb.TBState, *, n_flows: int | None = None,
               n_res: int = 0) -> dict[str, Any]:
    N, A = (n_flows or flows.n), accels.n
    lanes_busy = np.zeros((A, cfg.lmax), np.float32)
    for a in range(A):
        lanes_busy[a, accels.parallelism[a]:] = np.float32(3e38)  # lane disabled
    return dict(
        # per-flow ingress queues
        q_sz=jnp.zeros((N, cfg.qlen), jnp.int32),
        q_at=jnp.zeros((N, cfg.qlen), jnp.int32),
        q_head=jnp.zeros((N,), jnp.int32),
        q_cnt=jnp.zeros((N,), jnp.int32),
        arr_ptr=jnp.zeros((N,), jnp.int32),
        # shaper
        tb=_own_tb(tb_state),
        sw_pend=jnp.zeros((N,), jnp.int32),
        # arbiter
        rr_ptr=jnp.zeros((), jnp.int32),
        vft=jnp.zeros((N,), jnp.float32),
        # link / credits
        lres=jnp.zeros((2,), jnp.float32),
        # extra resource axes (token-bucket residue: unused budget up to
        # each axis' burst_bytes, or the serialization debt when negative)
        res_res=jnp.zeros((n_res,), jnp.float32),
        credits_used=jnp.zeros((), jnp.int32),
        # accelerator queues + lanes
        aq_sz=jnp.zeros((A, cfg.aq_len), jnp.int32),
        aq_fl=jnp.zeros((A, cfg.aq_len), jnp.int32),
        aq_at=jnp.zeros((A, cfg.aq_len), jnp.int32),
        aq_head=jnp.zeros((A,), jnp.int32),
        aq_cnt=jnp.zeros((A,), jnp.int32),
        aq_bytes=jnp.zeros((A,), jnp.int32),
        lanes=jnp.asarray(lanes_busy),
        # egress queues, one per direction (0 h2d, 1 d2h, 2 off-fabric)
        eq_sz=jnp.zeros((3, cfg.eq_len), jnp.int32),
        eq_isz=jnp.zeros((3, cfg.eq_len), jnp.int32),  # original ingress bytes
        eq_fl=jnp.zeros((3, cfg.eq_len), jnp.int32),
        eq_at=jnp.zeros((3, cfg.eq_len), jnp.int32),
        eq_rd=jnp.zeros((3, cfg.eq_len), jnp.int32),
        eq_head=jnp.zeros((3,), jnp.int32),
        eq_cnt=jnp.zeros((3,), jnp.int32),
        # telemetry ("hardware counters", Arcus step 7)
        c_adm_msgs=jnp.zeros((N,), jnp.int32),
        # exact byte counters, split lo (20 bits) / hi to stay in int32
        c_adm_b_lo=jnp.zeros((N,), jnp.int32),
        c_adm_b_hi=jnp.zeros((N,), jnp.int32),
        c_done_msgs=jnp.zeros((N,), jnp.int32),
        c_done_b_lo=jnp.zeros((N,), jnp.int32),
        c_done_b_hi=jnp.zeros((N,), jnp.int32),
        c_drops=jnp.zeros((N,), jnp.int32),
        c_lat_sum=jnp.zeros((N,), jnp.float32),
        # completion record ring (one scratch slot at index comp_cap)
        comp_fl=jnp.zeros((cfg.comp_cap + 1,), jnp.int32),
        comp_lat=jnp.zeros((cfg.comp_cap + 1,), jnp.int32),
        comp_t=jnp.zeros((cfg.comp_cap + 1,), jnp.int32),
        comp_sz=jnp.zeros((cfg.comp_cap + 1,), jnp.int32),
        comp_n=jnp.zeros((), jnp.int32),
        rng=jnp.asarray(np.int32(0x1234567)),
        # ticks on which the grant / service stage ran its vectorized path
        # alone (see _fast_or_fallback)
        c_grant_fast_ticks=jnp.zeros((), jnp.int32),
        c_srv_fast_ticks=jnp.zeros((), jnp.int32),
    )


def reconfigure_carry(carry: dict, tb_state: tb.TBState) -> dict:
    """Live reconfiguration: write only the parameter "registers"
    (Refill_Rate / Bkt_Size / Interval / mode); in-flight tokens and timers
    are hardware state and keep running."""
    carry = dict(carry)
    old = carry["tb"]
    new = _own_tb(tb_state)
    carry["tb"] = old._replace(
        refill_rate=new.refill_rate,
        bkt_size=new.bkt_size,
        interval=new.interval,
        mode=new.mode,
        tokens=jnp.minimum(old.tokens, new.bkt_size),
    )
    return carry


# ---------------------------------------------------------------------------
# Membership-change carry resumption (tenant lifecycle)
# ---------------------------------------------------------------------------


def release_flow_lane(carry: dict, b: int, lane: int) -> dict:
    """Depart: flush one flow lane of a resumed batched carry.

    Queued-but-unadmitted messages are discarded (their bytes were never
    counted — admission counters tick at grant time) and the lane stops
    being grant-eligible via the caller's ``fl_mask``; messages already
    admitted into accelerator/egress queues drain naturally.  Shapes are
    untouched, so resuming the carry stays on the same compiled engine."""
    carry = dict(carry)
    carry["q_cnt"] = carry["q_cnt"].at[b, lane].set(0)
    carry["sw_pend"] = carry["sw_pend"].at[b, lane].set(0)
    return carry


def recycle_flow_lane(carry: dict, b: int, lane: int) -> dict:
    """Arrive: reset a (possibly previously occupied) flow lane so no
    dataplane state leaks from an earlier tenant.

    The arrival pointer rewinds to the lane's (fresh) trace row, the
    ingress queue and arbiter virtual-finish-time reset, and the token
    count is pre-set to INF so the next register write's
    ``min(tokens, bkt_size)`` clamp hands the new tenant a full initial
    bucket (exactly what ``tb.init(start_full=True)`` grants a freshly
    built carry).

    The lane's cumulative hardware counters zero too — the measurement
    baseline reset.  The control plane measures per-window deltas, and a
    delta straddling the splice would mix the departed tenant's totals
    into the newcomer's first measured rate (callers must reset their
    host-side previous-counter snapshot for the lane as well — the
    controller does).  One residue is documented and accepted: messages
    the predecessor already pushed into the accelerator/egress queues
    drain naturally and their completions land on this lane's counters
    (at most the in-flight queue depth, the same tolerance the depart
    path's freeze tests allow)."""
    carry = dict(carry)
    for k in ("q_cnt", "q_head", "arr_ptr", "sw_pend",
              "c_adm_msgs", "c_adm_b_lo", "c_adm_b_hi", "c_done_msgs",
              "c_done_b_lo", "c_done_b_hi", "c_drops"):
        carry[k] = carry[k].at[b, lane].set(0)
    carry["vft"] = carry["vft"].at[b, lane].set(0.0)
    carry["c_lat_sum"] = carry["c_lat_sum"].at[b, lane].set(0.0)
    carry["tb"] = carry["tb"]._replace(
        tokens=carry["tb"].tokens.at[b, lane].set(INF_I32))
    return carry


# ---------------------------------------------------------------------------
# Flow / register padding (ragged multi-tenant batching)
# ---------------------------------------------------------------------------


def pad_tb_state(state: tb.TBState, n_max: int) -> tb.TBState:
    """Pad per-flow TB registers to ``n_max`` lanes with benign parameters
    (interval 1 avoids div-by-zero in the shared timer advance; padded lanes
    are never offered messages, so their token state is inert)."""
    n = int(np.asarray(state.tokens).shape[0])
    if n == n_max:
        return state
    if n > n_max:
        raise ValueError(f"TBState has {n} lanes > n_max={n_max}")
    pad = n_max - n

    def ext(x, fill):
        x = np.asarray(x)
        return np.concatenate([x, np.full((pad,), fill, x.dtype)])

    return tb.TBState(
        tokens=jnp.asarray(ext(state.tokens, 0)),
        cyc=jnp.asarray(ext(state.cyc, 0)),
        refill_rate=jnp.asarray(ext(state.refill_rate, 1)),
        bkt_size=jnp.asarray(ext(state.bkt_size, 1)),
        interval=jnp.asarray(ext(state.interval, 1)),
        mode=jnp.asarray(ext(state.mode, 0)),
    )


def _accel_mask(tab: AccelTable) -> np.ndarray:
    """Per-accelerator validity mask (active = has at least one lane).

    Active accelerators must occupy a prefix of the table: the service
    stage's closed-form LCG draw indexes iterations as ``k * n_active + a``,
    which equals the sequential walk only when every active row precedes
    every padded row (``pad_accel_table`` always appends padding; a
    hand-built table with a mid-table ``parallelism=0`` row would silently
    diverge, so reject it here)."""
    m = np.asarray(tab.parallelism) > 0
    if np.any(~m[:-1] & m[1:]):
        raise ValueError(
            "active accelerators (parallelism > 0) must form a prefix of "
            f"the AccelTable (got parallelism={list(tab.parallelism)})")
    return m


def pad_accel_table(tab: AccelTable, a_max: int) -> AccelTable:
    """Pad an accelerator table to ``a_max`` rows (ragged accel batching).

    Padded accelerators carry benign service/egress curves (never read:
    no flow routes to them) and ``parallelism=0``, which disables every
    lane at ``init_carry`` time — they can never start service."""
    if tab.n == a_max:
        return tab
    if tab.n > a_max:
        raise ValueError(f"AccelTable has {tab.n} accels > a_max={a_max}")
    pad = a_max - tab.n
    return AccelTable(
        n=a_max,
        service_cycles=np.concatenate(
            [tab.service_cycles,
             np.ones((pad, GRID_N), np.float32)]).astype(np.float32),
        egress_bytes=np.concatenate(
            [tab.egress_bytes,
             np.ones((pad, GRID_N), np.float32)]).astype(np.float32),
        parallelism=np.concatenate(
            [tab.parallelism, np.zeros(pad, np.int32)]).astype(np.int32),
        names=list(tab.names) + ["__pad__"] * pad,
        # padded rows carry no spec: spec_of() guards, and no flow ever
        # routes to them anyway
        specs=list(tab.specs),
    )


def _flow_args(flows: FlowSet, n_max: int) -> dict[str, np.ndarray]:
    """Per-flow routing/weight tables padded to ``n_max`` plus the validity
    mask.  Padded lanes route to accel 0 / direction 0 (any in-range value:
    they are never granted) and carry weight 1 to keep 1/w finite."""
    n = flows.n

    def pad(x, fill, dtype):
        x = np.asarray(x, dtype)
        return np.concatenate(
            [x, np.full((n_max - n,), fill, dtype)]) if n_max > n else x

    return dict(
        fl_accel=pad(flows.accel_id, 0, np.int32),
        fl_in_dir=pad(flows.ingress_dir, 0, np.int32),
        fl_eg_dir=pad(flows.egress_dir, 0, np.int32),
        # inline-NIC-RX delivers the full payload to the host no matter what
        # the accelerator emits; other paths transfer the accel's output.
        fl_eg_full=pad(flows.path == int(Path.INLINE_NIC_RX), False, bool),
        fl_prio=pad(flows.priority, 0, np.float32),
        fl_w=pad(np.maximum(flows.weight, 1e-3), 1.0, np.float32),
        fl_mask=pad(np.ones(n, bool), False, bool),
    )


def _resource_tables(flows: FlowSet, accels: AccelTable, link: LinkSpec,
                     n_max: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-flow demand coefficients on the extra resource axes.

    Returns ``(w_in, w_eg)``, each ``[R-1, n_max]`` float32: bytes charged
    on axis r per ingress byte granted / per egress byte popped for flow i.
    Resolution order: a flow's own ``res_demand`` hint, else its
    accelerator's ``AcceleratorSpec.res_demand``, else 1.0/1.0 (every byte
    crosses the axis).  ``fabric_only`` axes charge nothing for off-fabric
    (dir == 2) stage directions.  Padded flow lanes keep 0 coefficients —
    they are never granted, so the value is inert either way."""
    rspecs = getattr(link, "resources", ())
    R = len(rspecs)
    w_in = np.zeros((R, n_max), np.float32)
    w_eg = np.zeros((R, n_max), np.float32)
    specs = getattr(flows, "specs", ())
    for r, rs in enumerate(rspecs):
        for i in range(flows.n):
            sp = specs[i] if i < len(specs) else None
            ic = ec = None
            if sp is not None:
                for nm, a, b in getattr(sp, "res_demand", ()):
                    if nm == rs.name:
                        ic, ec = float(a), float(b)
                        break
            if ic is None:
                aspec = (accels.spec_of(int(flows.accel_id[i]))
                         if hasattr(accels, "spec_of") else None)
                ic, ec = (aspec.resource_demand(rs.name)
                          if aspec is not None else (1.0, 1.0))
            if rs.fabric_only:
                if int(flows.ingress_dir[i]) == 2:
                    ic = 0.0
                if int(flows.egress_dir[i]) == 2:
                    ec = 0.0
            # clamp: negative demand would refill a bucket mid-tick,
            # breaking the eligibility monotonicity the fast grant path
            # relies on
            w_in[r, i] = max(ic, 0.0)
            w_eg[r, i] = max(ec, 0.0)
    return w_in, w_eg


# ---------------------------------------------------------------------------
# Traced-argument packing (everything here may change without a retrace)
# ---------------------------------------------------------------------------


def _window_stall(stall_mask, cfg: SimConfig, t0_ticks) -> np.ndarray:
    """Window-relative stall mask, always ``[n_ticks]`` so the compiled
    signature is independent of the window start ``t0``."""
    if stall_mask is None:
        return np.zeros(cfg.n_ticks, bool)
    stall_mask = np.asarray(stall_mask, bool)
    if stall_mask.shape[-1] == cfg.n_ticks:
        return stall_mask
    t0 = int(t0_ticks)
    if stall_mask.shape[-1] < t0 + cfg.n_ticks:
        raise ValueError(
            f"stall mask covers {stall_mask.shape[-1]} ticks < "
            f"t0+n_ticks={t0 + cfg.n_ticks}")
    return stall_mask[..., t0:t0 + cfg.n_ticks]


def _check_modes(cfg: SimConfig) -> None:
    """Traced mode words bypass compile-time checks — validate up front."""
    if cfg.arbiter not in (ARB_RR, ARB_WRR, ARB_PRIORITY, ARB_WFQ):
        raise ValueError(cfg.arbiter)
    if cfg.shaping not in (SHAPING_NONE, SHAPING_HW, SHAPING_SW):
        raise ValueError(cfg.shaping)


def _pack_args(flows: FlowSet, accels: AccelTable, link: LinkSpec,
               cfg: SimConfig, arr_t, arr_sz, stall_mask,
               t0_ticks) -> dict[str, Any]:
    _check_modes(cfg)
    h2d_bpc, d2h_bpc = link.bytes_per_cycle()
    args = dict(
        arr_t=jnp.asarray(arr_t, jnp.int32),
        arr_sz=jnp.asarray(arr_sz, jnp.int32),
        t0=jnp.asarray(t0_ticks, jnp.int32),
        svc_tab=jnp.asarray(accels.service_cycles, jnp.float32),
        eg_tab=jnp.asarray(accels.egress_bytes, jnp.float32),
        # per-accelerator validity (ragged accel batching): a padded row is
        # never routed to, never serves, and never draws host-delay jitter
        ac_mask=jnp.asarray(_accel_mask(accels), bool),
        bpc=jnp.asarray([h2d_bpc, d2h_bpc], jnp.float32),
        ovh=jnp.asarray(link.msg_overhead_bytes, jnp.float32),
        credits=jnp.asarray(link.credits, jnp.int32),
        # system mode words (Sec. 5.1 configurations) — traced, so
        # heterogeneous baselines share one compiled engine
        mode=jnp.asarray(cfg.shaping, jnp.int32),
        arb=jnp.asarray(cfg.arbiter, jnp.int32),
        sw_delay=jnp.asarray(cfg.sw_host_delay_cycles, jnp.float32),
        sw_jit=jnp.asarray(cfg.sw_jitter_cycles, jnp.float32),
        stall=jnp.asarray(_window_stall(stall_mask, cfg, t0_ticks), bool),
        # extra contended resource axes (R-1 of them; empty arrays in the
        # scalar default, where the whole resource pipeline compiles away)
        res_cap=jnp.asarray(link.resource_caps_per_cycle(), jnp.float32),
        res_burst=jnp.asarray(link.resource_burst_bytes(), jnp.float32),
    )
    w_in, w_eg = _resource_tables(flows, accels, link, flows.n)
    args["res_w_in"] = jnp.asarray(w_in)
    args["res_w_eg"] = jnp.asarray(w_eg)
    for k, v in _flow_args(flows, flows.n).items():
        args[k] = jnp.asarray(v)
    return args


def _args_sig(args: dict[str, Any]) -> tuple:
    return tuple(sorted((k, v.shape) for k, v in args.items()))


# ---------------------------------------------------------------------------
# The tick body
# ---------------------------------------------------------------------------

#: inner pipeline-stage loops (k_grant / k_srv / k_eg, trip counts 2-16) are
#: unrolled into the scan body up to this bound: XLA while-loop per-iteration
#: overhead dominates these tiny bodies on CPU.
_UNROLL_MAX = 32


def _fori(n: int, body, init):
    """fori_loop that statically unrolls small trip counts."""
    if n <= _UNROLL_MAX:
        val = init
        for i in range(n):
            val = body(i, val)
        return val
    return jax.lax.fori_loop(0, n, body, init)


@functools.lru_cache(maxsize=None)
def _lcg_tables(n: int) -> tuple[np.ndarray, np.ndarray]:
    """Closed-form LCG step tables: r_m = r0 * POW[m-1] + SUM[m-1] (int32
    wraparound) equals m iterated ``r = r * A + C`` updates."""
    a, c, m = int(_LCG_A), int(_LCG_C), 1 << 32
    pows, sums = [], []
    p, s = 1, 0
    for _ in range(n):
        p = (p * a) % m
        s = (s * a + c) % m
        pows.append(p)
        sums.append(s)
    to_i32 = lambda v: np.array(v, np.uint32).astype(np.int32)  # noqa: E731
    return to_i32(pows), to_i32(sums)


def _interp_mat(table, msg_bytes_f32):
    """interp_grid over a full [A, K] message matrix (one row per accel)."""
    A = table.shape[0]
    a_grid = jnp.broadcast_to(jnp.arange(A, dtype=jnp.int32)[:, None],
                              msg_bytes_f32.shape)
    return interp_grid(table, a_grid, msg_bytes_f32)


#: vmap axis name of the batched engine's fleet axis (``run_window_batch``)
FLEET_AXIS = "fleet"

#: carry counters of the ticks a stage ran its vectorized path alone
FAST_TICK_KEYS = ("c_grant_fast_ticks", "c_srv_fast_ticks")


def _fast_or_fallback(axis, pred, fast, slow, *operands):
    """``lax.cond(pred, fast, slow, *operands)``, chosen once for the fleet.

    Under ``vmap`` a per-server predicate is batched, and a batched cond
    lowers to a select that runs both branches.  With the fleet's
    ``axis`` name, the predicate is first reduced over the fleet: on a
    tick where every server takes ``fast``, an unbatched cond runs it
    alone; otherwise each server takes its own branch through the
    per-server select.  Either way a server's result comes from the
    branch its own predicate picks, so results are bitwise those of the
    serial engine (``axis`` None: a plain cond).  Returns the result and
    whether ``fast`` ran alone on this tick."""
    if axis is None:
        return jax.lax.cond(pred, fast, slow, *operands), pred
    all_fast = (jax.lax.psum(pred.astype(jnp.int32), axis)
                == jax.lax.axis_size(axis))
    out = jax.lax.cond(all_fast, fast,
                       lambda *o: jax.lax.cond(pred, fast, slow, *o),
                       *operands)
    return out, all_fast


def _tick(cfg: SimConfig, args: dict, carry: dict, t, axis=None):
    arr_t, arr_sz = args["arr_t"], args["arr_sz"]
    fl_accel, fl_in_dir = args["fl_accel"], args["fl_in_dir"]
    fl_eg_dir, fl_eg_full = args["fl_eg_dir"], args["fl_eg_full"]
    fl_prio, fl_w, fl_mask = args["fl_prio"], args["fl_w"], args["fl_mask"]
    svc_tab, eg_tab = args["svc_tab"], args["eg_tab"]
    ac_mask = args["ac_mask"]
    bpc, ovh, credits = args["bpc"], args["ovh"], args["credits"]
    mode, arb = args["mode"], args["arb"]
    N = fl_accel.shape[0]
    A = svc_tab.shape[0]
    iota_n = jnp.arange(N, dtype=jnp.int32)
    sw = mode == SHAPING_SW
    shaped = (mode == SHAPING_HW) | sw
    arb_rr = arb == ARB_RR
    # active (unpadded) lanes; arbiter keys cycle modulo this count so a
    # padded batch element is bitwise-identical to its unpadded serial run
    n_act = jnp.maximum(jnp.sum(fl_mask.astype(jnp.int32)), 1)
    # active accelerators (padded accel rows fill the trailing positions);
    # the service stage and its host-delay LCG skip padded rows entirely
    ac_n = jnp.maximum(jnp.sum(ac_mask.astype(jnp.int32)), 1)

    now = t * cfg.tick_cycles
    now_end = now + cfg.tick_cycles
    is_stall = sw & args["stall"][t - args["t0"]]

    # Each stage below runs under a named scope (intake, grant, service,
    # egress): op metadata only, so a device trace can attribute the
    # tick's operations to its stages; the numerics are unchanged.
    with jax.named_scope("intake"):
        # -- 1. token-bucket timers ------------------------------------
        # host descheduled (software shaping): refills deferred, catch up on
        # wakeup; hardware shaping and unshaped systems tick every cycle
        pend = carry["sw_pend"] + cfg.tick_cycles
        elapsed = jnp.where(sw, jnp.where(is_stall, 0, pend), cfg.tick_cycles)
        carry["sw_pend"] = jnp.where(sw & is_stall, pend, 0)
        carry["tb"] = tb.advance(carry["tb"], elapsed)

        # -- 2. arrivals -> per-flow queues (single gather) ----------------
        # one [N, k_arr] gather of the next candidate arrivals per flow; the
        # due set is a per-row prefix (traces are time-sorted, INF-padded), so
        # counts replace the old k_arr-iteration drain loop exactly: the first
        # `room` due messages are taken, the remaining due ones dropped.
        M = arr_t.shape[1]
        jj_a = jnp.arange(cfg.k_arr, dtype=jnp.int32)
        pos = carry["arr_ptr"][:, None] + jj_a[None, :]
        gidx = jnp.minimum(pos, M - 1)
        nxt_t = arr_t[iota_n[:, None], gidx]
        nxt_s = arr_sz[iota_n[:, None], gidx]
        due = (nxt_t < now_end) & (pos < M)
        n_due = due.astype(jnp.int32).sum(1)
        n_take = jnp.minimum(n_due, jnp.maximum(cfg.qlen - carry["q_cnt"], 0))
        take = due & (jj_a[None, :] < n_take[:, None])
        slot = (carry["q_head"][:, None] + carry["q_cnt"][:, None]
                + jj_a[None, :]) % cfg.qlen
        row = jnp.where(take, iota_n[:, None], N)        # OOB rows are dropped
        carry["q_sz"] = carry["q_sz"].at[row, slot].set(nxt_s, mode="drop")
        carry["q_at"] = carry["q_at"].at[row, slot].set(nxt_t, mode="drop")
        carry["q_cnt"] = carry["q_cnt"] + n_take
        carry["arr_ptr"] = carry["arr_ptr"] + n_due
        carry["c_drops"] = carry["c_drops"] + (n_due - n_take)

        # -- 3. per-tick link budgets ------------------------------------
        budget = bpc * cfg.tick_cycles + carry["lres"]  # [2] bytes
        # extra resource axes (R_res = R-1; 0 in the scalar default).  R_res is
        # a *static* shape, so every resource op below sits behind a python
        # `if R_res:` guard — the R=1 compiled graph is structurally identical
        # to the pre-vector engine, which is what guarantees the bitwise
        # degenerate contract.  The empty [0] arrays still thread through the
        # cond/loop state tuples so branch signatures stay consistent.
        R_res = args["res_cap"].shape[0]
        res_bud = args["res_cap"] * cfg.tick_cycles + carry["res_res"]
        res_w_in, res_w_eg = args["res_w_in"], args["res_w_eg"]
        if R_res:
            # axes a flow charges in EITHER direction: its grants stall while
            # any of them is in debt.  Only the grant stage is gated — egress
            # charges its bytes as additional debt when it pops (gating pops
            # too would let the earlier grant stage starve egress forever at
            # saturation); sustainable ingress goodput on a saturated axis is
            # then cap / (w_in + w_eg * egress_ratio), which is exactly the
            # demand-coefficient algebra CapacityEntry margins use.
            res_w_any = (res_w_in > 0.0) | (res_w_eg > 0.0)

    with jax.named_scope("grant"):
        # -- 4. shaper + arbiter grants ----------------------------------
        def grant_inputs(c, budget, res_bud):
            """Head-of-line state + eligibility + arbiter key per flow."""
            head_sz = c["q_sz"][iota_n, c["q_head"]]
            head_at = c["q_at"][iota_n, c["q_head"]]
            have = c["q_cnt"] > 0
            cost = tb.cost_of(c["tb"], head_sz)
            tok_ok = jnp.logical_or(~shaped, c["tb"].tokens >= cost)
            a_of = fl_accel
            aq_room = jnp.logical_and(
                c["aq_cnt"][a_of] < cfg.aq_len,
                c["aq_bytes"][a_of] + head_sz <= cfg.aq_byte_cap)
            cred_ok = c["credits_used"] < credits
            # A message may start whenever the link has *any* remaining
            # budget; it then drives the budget negative, which models its
            # serialization time (the link stays busy / in debt until the
            # per-tick replenishment pays it off).
            bud_f = jnp.where(fl_in_dir == 2, jnp.float32(3e38),
                              budget[jnp.minimum(fl_in_dir, 1)])
            bud_ok = bud_f > 0.0
            elig = (have & tok_ok & aq_room & cred_ok & bud_ok & fl_mask
                    & jnp.logical_not(is_stall))
            if R_res:
                # a flow stalls while ANY axis it demands is in debt (same
                # start-when-positive semantics as the link budget above)
                res_ok = jnp.all((~res_w_any) | (res_bud[:, None] > 0.0),
                                 axis=0)
                elig = elig & res_ok

            # arbiter key (lower = served first), selected by the traced mode
            # word.  Pure RR cycles by lane index modulo the *static* lane
            # count N: for any active subset this induces exactly the cyclic
            # lane order after rr_ptr, so it is grant-for-grant identical to
            # the old modulo-n_act key when active lanes form a prefix AND
            # stays correct when departures punch holes mid-table (mod n_act
            # would alias two active lanes onto one key there).  The WRR/WFQ/
            # priority tie-break term keeps the modulo-n_act *values* so those
            # float keys stay bitwise-identical between padded and unpadded
            # runs.
            rr_cyc = ((iota_n - c["rr_ptr"] - 1) % N).astype(jnp.float32)
            rr_key = ((iota_n - c["rr_ptr"] - 1) % n_act).astype(jnp.float32)
            key = jnp.where(
                arb_rr, rr_cyc,
                jnp.where(arb == ARB_PRIORITY, -fl_prio * 1e6 + rr_key,
                          c["vft"] + 1e-6 * rr_key))        # WRR / WFQ
            key = jnp.where(elig, key, jnp.float32(3e38))
            return head_sz, head_at, cost, elig, key

        def grant_body(_, st):
            c, budget, res_bud = st
            head_sz, head_at, cost, elig, key = grant_inputs(c, budget, res_bud)
            g = jnp.argmin(key).astype(jnp.int32)
            ok = elig[g]

            sz = head_sz[g]
            at = head_at[g]
            onehot = (iota_n == g) & ok
            # consume tokens (transparent when unshaped)
            c["tb"] = c["tb"]._replace(
                tokens=c["tb"].tokens - jnp.where(onehot & shaped, cost, 0))
            # pop flow queue
            c["q_head"] = (c["q_head"] + onehot) % cfg.qlen
            c["q_cnt"] = c["q_cnt"] - onehot
            # link budget + credits (per-message fabric overhead included)
            dir_idx = jnp.minimum(fl_in_dir[g], 1)
            spend = jnp.where((fl_in_dir[g] != 2) & ok,
                              sz.astype(jnp.float32) + ovh, 0.0)
            budget = budget.at[dir_idx].add(-spend)
            if R_res:
                # charge the granted message's ingress demand on every axis
                # (payload bytes only — the TLP overhead is a link artifact)
                res_bud = res_bud - jnp.where(
                    ok, res_w_in[:, g] * sz.astype(jnp.float32), 0.0)
            c["credits_used"] = c["credits_used"] + ok.astype(jnp.int32)
            # accel queue push
            a = fl_accel[g]
            slot = (c["aq_head"][a] + c["aq_cnt"][a]) % cfg.aq_len
            c["aq_sz"] = c["aq_sz"].at[a, slot].set(
                jnp.where(ok, sz, c["aq_sz"][a, slot]))
            c["aq_fl"] = c["aq_fl"].at[a, slot].set(
                jnp.where(ok, g, c["aq_fl"][a, slot]))
            c["aq_at"] = c["aq_at"].at[a, slot].set(
                jnp.where(ok, at, c["aq_at"][a, slot]))
            c["aq_cnt"] = c["aq_cnt"].at[a].add(ok.astype(jnp.int32))
            c["aq_bytes"] = c["aq_bytes"].at[a].add(jnp.where(ok, sz, 0))
            # arbiter state.  WRR is message-granular (one packet per flow
            # per round — how the paper's Host_noTS FPGA arbiter behaves,
            # letting large messages steal bytes); WFQ is byte-granular.
            c["rr_ptr"] = jnp.where(ok, g, c["rr_ptr"])
            vft_inc = jnp.where(arb == ARB_WRR, jnp.float32(1.0),
                                sz.astype(jnp.float32)) / fl_w
            c["vft"] = c["vft"] + jnp.where(onehot, vft_inc, 0.0)
            # counters
            c["c_adm_msgs"] = c["c_adm_msgs"] + onehot.astype(jnp.int32)
            lo = c["c_adm_b_lo"] + jnp.where(onehot, sz, 0)
            c["c_adm_b_hi"] = c["c_adm_b_hi"] + (lo >> 20)
            c["c_adm_b_lo"] = lo & 0xFFFFF
            return c, budget, res_bud

        def seq_grants(c, budget, res_bud, *_aux):
            c, budget, res_bud = _fori(cfg.k_grant, grant_body,
                                       (c, budget, res_bud))
            return c, budget, res_bud

        use_fast = cfg.grant_fast and cfg.k_grant > 1 and N > 1
        if use_fast:
            # One-shot grant selection for the common uncontended RR tick.
            # Sorting eligible flows by the RR key visits them in exactly the
            # cyclic order the sequential argmin loop would (each grant moves
            # rr_ptr to the granted flow, so the next argmin is the next
            # eligible flow after it); eligibility is monotone within a tick
            # (budgets/credits/queues only move toward ineligibility), so the
            # first-K selection equals the sequential one whenever
            #   (a) every candidate passes its *cumulative* budget / credit /
            #       accel-queue check (prefix sums below), and
            #   (b) no flow could be granted twice (either >= k_grant flows
            #       are eligible, or every eligible flow has a single queued
            #       message).
            # Any contended (or non-RR) tick falls back to the sequential loop.
            K = min(cfg.k_grant, N)
            head_sz, head_at, cost, elig, key = grant_inputs(carry, budget,
                                                             res_bud)
            order = jnp.argsort(key)[:K]             # candidate flows, RR order
            valid = elig[order]                       # eligible prefix
            vi = valid.astype(jnp.int32)
            csz = head_sz[order]
            cat = head_at[order]
            ccost = cost[order]
            cdir = fl_in_dir[order]
            d01 = jnp.minimum(cdir, 1)
            cacc = fl_accel[order]
            spend = jnp.where((cdir != 2) & valid,
                              csz.astype(jnp.float32) + ovh, 0.0)
            # Prefix sums over the candidates.  The f32 ones are matmuls at
            # HIGHEST precision: at DEFAULT, XLA:TPU runs an f32 dot as one
            # bf16 pass, which rounds byte sums to 8 mantissa bits (XLA:CPU
            # ignores the precision field, so CPU results are unchanged).  The
            # int32 ones are masked sums — exact on every backend.
            hi = jax.lax.Precision.HIGHEST
            lt_i = jnp.tril(jnp.ones((K, K), jnp.int32), -1)   # [j, i]: i < j
            lt_f = lt_i.astype(jnp.float32)
            same_dir = (d01[None, :] == d01[:, None])
            cum_spend = jnp.dot(lt_f * same_dir.astype(jnp.float32), spend,
                                precision=hi)
            bud_ok = (cdir == 2) | (budget[d01] - cum_spend > 0.0)
            same_acc = (cacc[None, :] == cacc[:, None]).astype(jnp.int32)
            cnt_before = jnp.sum(lt_i * same_acc * vi[None, :], axis=1)
            byt_before = jnp.sum(lt_i * same_acc
                                 * jnp.where(valid, csz, 0)[None, :], axis=1)
            aq_ok = ((carry["aq_cnt"][cacc] + cnt_before < cfg.aq_len)
                     & (carry["aq_bytes"][cacc] + byt_before + csz
                        <= cfg.aq_byte_cap))
            idx_before = jnp.sum(lt_i * vi[None, :], axis=1)
            cred_ok = carry["credits_used"] + idx_before < credits
            ok_all = jnp.all(~valid | (bud_ok & aq_ok & cred_ok))
            if R_res:
                # cumulative per-axis check: candidate j must see a positive
                # bucket after the spends of every valid candidate before it
                # (the sequential loop's mid-tick eligibility re-check)
                c_any = res_w_any[:, order]                         # [R, K]
                c_rspend = (res_w_in[:, order]
                            * jnp.where(valid, csz, 0).astype(jnp.float32))
                cum_res = jnp.dot(c_rspend, lt_f.T, precision=hi)   # [R, K]
                res_ok_c = jnp.all(
                    (~c_any) | (res_bud[:, None] - cum_res > 0.0), axis=0)
                ok_all = ok_all & jnp.all(~valid | res_ok_c)
            n_elig = jnp.sum(elig.astype(jnp.int32))
            regrant_safe = ((n_elig >= cfg.k_grant)
                            | jnp.all(~elig | (carry["q_cnt"] <= 1)))
            fast_pred = ok_all & regrant_safe & arb_rr

            # Batched, the branch is chosen once per tick for the whole fleet
            # (_fast_or_fallback): the sequential loop runs only on ticks where
            # some server needs it, and each server keeps the branch its own
            # predicate picks, so simulate_batch() counters stay bitwise those
            # of serial simulate() without relying on fast==sequential.
            def vec_grants(c, budget, res_bud, order, valid, vi, csz, cat,
                           ccost, cdir, d01, cacc, spend, cnt_before):
                c["tb"] = c["tb"]._replace(
                    tokens=c["tb"].tokens.at[order].add(
                        -jnp.where(valid & shaped, ccost, 0)))
                if R_res:
                    # subtract in the exact sequential chain order: non-dyadic
                    # demand coefficients make float sums order-sensitive, and
                    # the carried residue must match the sequential loop's
                    r_spend = (res_w_in[:, order]
                               * jnp.where(valid, csz, 0).astype(jnp.float32))
                    for j in range(K):
                        res_bud = res_bud - r_spend[:, j]
                c["q_head"] = (c["q_head"]
                               + jnp.zeros((N,), jnp.int32).at[order].add(vi)) \
                    % cfg.qlen
                c["q_cnt"] = c["q_cnt"] - jnp.zeros((N,), jnp.int32) \
                    .at[order].add(vi)
                budget = budget - jnp.zeros((2,), jnp.float32).at[d01].add(spend)
                n_g = jnp.sum(vi)
                c["credits_used"] = c["credits_used"] + n_g
                slot = (c["aq_head"][cacc] + c["aq_cnt"][cacc] + cnt_before) \
                    % cfg.aq_len
                row = jnp.where(valid, cacc, A)       # OOB rows are dropped
                c["aq_sz"] = c["aq_sz"].at[row, slot].set(csz, mode="drop")
                c["aq_fl"] = c["aq_fl"].at[row, slot].set(order, mode="drop")
                c["aq_at"] = c["aq_at"].at[row, slot].set(cat, mode="drop")
                c["aq_cnt"] = c["aq_cnt"].at[cacc].add(vi)
                c["aq_bytes"] = c["aq_bytes"].at[cacc].add(
                    jnp.where(valid, csz, 0))
                c["rr_ptr"] = jnp.where(
                    n_g > 0, order[jnp.maximum(n_g - 1, 0)], c["rr_ptr"])
                vft_inc = jnp.where(arb == ARB_WRR, jnp.float32(1.0),
                                    csz.astype(jnp.float32)) / fl_w[order]
                c["vft"] = c["vft"].at[order].add(jnp.where(valid, vft_inc, 0.0))
                c["c_adm_msgs"] = c["c_adm_msgs"].at[order].add(vi)
                lo = c["c_adm_b_lo"].at[order].add(jnp.where(valid, csz, 0))
                c["c_adm_b_hi"] = c["c_adm_b_hi"] + (lo >> 20)
                c["c_adm_b_lo"] = lo & 0xFFFFF
                return c, budget, res_bud

            (carry, budget, res_bud), alone = _fast_or_fallback(
                axis, fast_pred, vec_grants, seq_grants,
                carry, budget, res_bud, order, valid, vi, csz, cat, ccost,
                cdir, d01, cacc, spend, cnt_before)
            carry["c_grant_fast_ticks"] = (carry["c_grant_fast_ticks"]
                                           + alone.astype(jnp.int32))
        else:
            carry, budget, res_bud = seq_grants(carry, budget, res_bud)

    with jax.named_scope("service"):
        # -- 5. accelerator service --------------------------------------
        # sequential reference: one accel per iteration, pass-major order
        # (iteration i serves accel i % A on pass i // A)
        def srv_body(i, c):
            a = i % A
            act = ac_mask[a]      # padded accel rows (ragged batching) are inert
            lanes_a = c["lanes"][a]
            lane = jnp.argmin(lanes_a).astype(jnp.int32)
            # a lane that frees during this tick may chain back-to-back
            # (no tick-quantization idle gap between messages)
            free = lanes_a[lane] < jnp.float32(now_end)
            ok = free & (c["aq_cnt"][a] > 0) & act
            h = c["aq_head"][a]
            sz = c["aq_sz"][a, h]
            fl = c["aq_fl"][a, h]
            at = c["aq_at"][a, h]
            svc = interp_grid(svc_tab, a, sz.astype(jnp.float32))
            esz = interp_grid(eg_tab, a, sz.astype(jnp.float32))
            esz = jnp.where(fl_eg_full[fl], sz.astype(jnp.float32), esz)
            end = jnp.maximum(lanes_a[lane], jnp.float32(now)) + svc
            c["lanes"] = c["lanes"].at[a, lane].set(
                jnp.where(ok, end, lanes_a[lane]))
            # the pop is a masked select over the accel axis, not a scatter-add
            # at [a]: unrolled, `a` is a constant, and XLA:TPU miscompiled those
            # constant-index scatter-adds in the unbatched engine (idle accels'
            # queues were popped too).  Integer selects: the same bits anywhere.
            pop = (jnp.arange(A, dtype=jnp.int32) == a) & ok
            c["aq_head"] = (c["aq_head"] + jnp.where(pop, 1, 0)) % cfg.aq_len
            c["aq_cnt"] = c["aq_cnt"] - jnp.where(pop, 1, 0)
            c["aq_bytes"] = c["aq_bytes"] - jnp.where(pop, sz, 0)
            # host-processing delay (software-mediated shaping only; the LCG
            # advances once per *active-accelerator* iteration whenever shaping
            # is software, busy or idle, exactly like the closed-form batch
            # draw below — padded rows draw nothing, so a ragged element's
            # jitter stream matches its unpadded serial run)
            r = c["rng"] * _LCG_A + _LCG_C
            c["rng"] = jnp.where(sw & act, r, c["rng"])
            u = (jnp.abs(r) % 65536).astype(jnp.float32) / 65536.0
            hostd = jnp.where(sw, args["sw_delay"] + (u ** 4) * args["sw_jit"],
                              jnp.float32(0.0))
            ready = (end + hostd).astype(jnp.int32)
            # egress queue push
            d = fl_eg_dir[fl]
            slot = (c["eq_head"][d] + c["eq_cnt"][d]) % cfg.eq_len
            full = c["eq_cnt"][d] >= cfg.eq_len
            okq = ok & jnp.logical_not(full)
            c["eq_sz"] = c["eq_sz"].at[d, slot].set(
                jnp.where(okq, jnp.maximum(esz.astype(jnp.int32), 1),
                          c["eq_sz"][d, slot]))
            c["eq_isz"] = c["eq_isz"].at[d, slot].set(
                jnp.where(okq, sz, c["eq_isz"][d, slot]))
            c["eq_fl"] = c["eq_fl"].at[d, slot].set(
                jnp.where(okq, fl, c["eq_fl"][d, slot]))
            c["eq_at"] = c["eq_at"].at[d, slot].set(
                jnp.where(okq, at, c["eq_at"][d, slot]))
            c["eq_rd"] = c["eq_rd"].at[d, slot].set(
                jnp.where(okq, ready, c["eq_rd"][d, slot]))
            c["eq_cnt"] = c["eq_cnt"].at[d].add(okq.astype(jnp.int32))
            return c

        def seq_srv(c):
            return _fori(A * cfg.k_srv, srv_body, c)

        # Vectorized service pays off only once the stage is wide enough:
        # measured on XLA-CPU, narrow service next to the vectorized egress
        # stage fuses pathologically (3x slower than the unrolled loop), while
        # wide stages gain 2-4x.  The knee (8 on XLA-CPU) is backend-dependent:
        # SimConfig.service_vec_min / $REPRO_SERVICE_VEC_MIN override it.  The
        # threshold is static, so serial and batched runs share the path.
        if cfg.stage_fast and A * cfg.k_srv >= cfg.service_vec_min:
            # Prefix-sum slot assignment (the treatment PR 1 gave RR grants):
            # sort each accelerator's lanes by busy-time; the k-th queued
            # message starts on the k-th least-busy lane.  This equals the
            # sequential argmin walk whenever no assigned lane frees again
            # within this tick (its end >= now_end): assigned lanes then sort
            # strictly after every still-free lane, so the sequential argmin
            # sequence is exactly the sorted order.  A chaining tick (tiny
            # service times) falls back to the sequential loop.
            Ks = cfg.k_srv
            ia = jnp.arange(A, dtype=jnp.int32)
            kk = jnp.arange(Ks, dtype=jnp.int32)
            kl = jnp.minimum(kk, cfg.lmax - 1)
            sl = jnp.sort(carry["lanes"], axis=1)[:, kl]       # [A, Ks]
            si = jnp.argsort(carry["lanes"], axis=1)[:, kl].astype(jnp.int32)
            free = (sl < jnp.float32(now_end)) & (kk < cfg.lmax)[None, :]
            have = kk[None, :] < carry["aq_cnt"][:, None]
            s_ok = free & have & ac_mask[:, None]               # prefix rows
            aslot = (carry["aq_head"][:, None] + kk[None, :]) % cfg.aq_len
            s_sz = carry["aq_sz"][ia[:, None], aslot]
            s_fl = carry["aq_fl"][ia[:, None], aslot]
            s_at = carry["aq_at"][ia[:, None], aslot]
            s_svc = _interp_mat(svc_tab, s_sz.astype(jnp.float32))
            s_esz = _interp_mat(eg_tab, s_sz.astype(jnp.float32))
            s_esz = jnp.where(fl_eg_full[s_fl], s_sz.astype(jnp.float32), s_esz)
            s_end = jnp.maximum(sl, jnp.float32(now)) + s_svc
            srv_fast = jnp.all(~s_ok | (s_end >= jnp.float32(now_end)))

            def vec_srv(c, s_ok, si, s_sz, s_fl, s_at, s_esz, s_end):
                n_start = s_ok.astype(jnp.int32).sum(1)
                lrow = jnp.where(s_ok, ia[:, None], A)   # OOB rows are dropped
                c["lanes"] = c["lanes"].at[lrow, si].set(s_end, mode="drop")
                c["aq_head"] = (c["aq_head"] + n_start) % cfg.aq_len
                c["aq_cnt"] = c["aq_cnt"] - n_start
                c["aq_bytes"] = c["aq_bytes"] - jnp.where(s_ok, s_sz, 0).sum(1)
                # host-processing delay: closed-form LCG draw for *active*
                # iteration i = k*ac_n + a (padded accel rows draw nothing),
                # bitwise-equal to the sequential per-step update of a run
                # with only the active accelerators
                powv, sumv = _lcg_tables(A * Ks)
                it = jnp.minimum(kk[None, :] * ac_n + ia[:, None],
                                 A * Ks - 1)                     # [A, Ks]
                r = c["rng"] * jnp.asarray(powv)[it] + jnp.asarray(sumv)[it]
                adv = jnp.maximum(ac_n * Ks - 1, 0)
                c["rng"] = jnp.where(sw, c["rng"] * jnp.asarray(powv)[adv]
                                     + jnp.asarray(sumv)[adv], c["rng"])
                u = (jnp.abs(r) % 65536).astype(jnp.float32) / 65536.0
                hostd = jnp.where(sw, args["sw_delay"]
                                  + (u ** 4) * args["sw_jit"], jnp.float32(0.0))
                ready = (s_end + hostd).astype(jnp.int32)
                # egress pushes in sequential iteration order (k-major flatten)
                flat = lambda x: x.T.reshape(-1)                 # noqa: E731
                okf = flat(s_ok)
                d = fl_eg_dir[flat(s_fl)]
                Mt = A * Ks
                lt = jnp.tril(jnp.ones((Mt, Mt), jnp.int32), -1)
                same_d = (d[None, :] == d[:, None]).astype(jnp.int32)
                rank = jnp.sum(lt * same_d * okf.astype(jnp.int32)[None, :],
                               axis=1)
                okq = okf & (c["eq_cnt"][d] + rank < cfg.eq_len)
                eslot = (c["eq_head"][d] + c["eq_cnt"][d] + rank) % cfg.eq_len
                drow = jnp.where(okq, d, 3)           # OOB rows are dropped
                c["eq_sz"] = c["eq_sz"].at[drow, eslot].set(
                    jnp.maximum(flat(s_esz).astype(jnp.int32), 1), mode="drop")
                c["eq_isz"] = c["eq_isz"].at[drow, eslot].set(
                    flat(s_sz), mode="drop")
                c["eq_fl"] = c["eq_fl"].at[drow, eslot].set(
                    flat(s_fl), mode="drop")
                c["eq_at"] = c["eq_at"].at[drow, eslot].set(
                    flat(s_at), mode="drop")
                c["eq_rd"] = c["eq_rd"].at[drow, eslot].set(
                    flat(ready), mode="drop")
                c["eq_cnt"] = c["eq_cnt"] + jnp.zeros((3,), jnp.int32) \
                    .at[d].add(okq.astype(jnp.int32))
                return c

            carry, alone = _fast_or_fallback(
                axis, srv_fast, vec_srv, lambda c, *_a: seq_srv(c),
                carry, s_ok, si, s_sz, s_fl, s_at, s_esz, s_end)
            carry["c_srv_fast_ticks"] = (carry["c_srv_fast_ticks"]
                                         + alone.astype(jnp.int32))
        else:
            carry = seq_srv(carry)

    with jax.named_scope("egress"):
        # -- 6. egress link + completions ----------------------------------
        dirs = jnp.arange(3, dtype=jnp.int32)

        def eg_body(_, st):
            c, budget, res_bud = st
            h = c["eq_head"]                       # [3]
            sz = c["eq_sz"][dirs, h]
            isz = c["eq_isz"][dirs, h]
            fl = c["eq_fl"][dirs, h]
            at = c["eq_at"][dirs, h]
            rd = c["eq_rd"][dirs, h]
            have = c["eq_cnt"] > 0
            ready = rd < now_end
            bud3 = jnp.concatenate([budget, jnp.asarray([3e38], jnp.float32)])
            bud_ok = bud3[dirs] > 0.0
            pop = have & ready & bud_ok            # [3]
            c["eq_head"] = (c["eq_head"] + pop) % cfg.eq_len
            c["eq_cnt"] = c["eq_cnt"] - pop
            spend = jnp.where(pop[:2], sz[:2].astype(jnp.float32) + ovh, 0.0)
            budget = budget - spend
            if R_res:
                # ungated debt charge — see res_w_any above; the three
                # directions' spends of one iteration subtract together
                res_bud = res_bud - (
                    res_w_eg[:, fl] * jnp.where(pop, sz, 0)
                    .astype(jnp.float32)[None, :]).sum(1)
            c["credits_used"] = c["credits_used"] - pop.sum().astype(jnp.int32)
            # completion = transfer start + own serialization delay
            ser = jnp.where(dirs < 2,
                            sz.astype(jnp.float32) / bpc[jnp.minimum(dirs, 1)],
                            0.0)
            comp_time = jnp.maximum(rd, now) + ser.astype(jnp.int32)
            lat = comp_time - at
            # record (scratch slot comp_cap for non-pops)
            base = c["comp_n"]
            offs = jnp.cumsum(pop.astype(jnp.int32)) - pop.astype(jnp.int32)
            idx = jnp.where(pop, (base + offs) % cfg.comp_cap, cfg.comp_cap)
            c["comp_fl"] = c["comp_fl"].at[idx].set(fl)
            c["comp_lat"] = c["comp_lat"].at[idx].set(lat)
            c["comp_t"] = c["comp_t"].at[idx].set(comp_time)
            c["comp_sz"] = c["comp_sz"].at[idx].set(isz)
            c["comp_n"] = base + pop.sum().astype(jnp.int32)
            # per-flow counters (SLO accounting is on ingress payload bytes,
            # as the paper's traffic generator measures); scatter-adds
            # accumulate duplicate flow ids across the three directions.
            c["c_done_msgs"] = c["c_done_msgs"].at[fl].add(pop.astype(jnp.int32))
            lo = c["c_done_b_lo"].at[fl].add(jnp.where(pop, isz, 0))
            c["c_done_b_hi"] = c["c_done_b_hi"] + (lo >> 20)
            c["c_done_b_lo"] = lo & 0xFFFFF
            c["c_lat_sum"] = c["c_lat_sum"].at[fl].add(
                jnp.where(pop, lat.astype(jnp.float32), 0.0))
            return c, budget, res_bud

        if cfg.stage_fast:
            # Vectorized egress: gather the next k_eg ring entries of every
            # direction at once.  Pops per direction are a prefix (a head that
            # is not ready / not funded stays at the head for the rest of the
            # tick), so one cumulative-AND replaces the k_eg-iteration loop.
            # The budget chain is evaluated in the exact sequential subtraction
            # order to keep the carried link debt bitwise-identical.
            Ke = cfg.k_eg
            jj = jnp.arange(Ke, dtype=jnp.int32)
            eh = (carry["eq_head"][:, None] + jj[None, :]) % cfg.eq_len
            e_sz = carry["eq_sz"][dirs[:, None], eh]
            e_isz = carry["eq_isz"][dirs[:, None], eh]
            e_fl = carry["eq_fl"][dirs[:, None], eh]
            e_at = carry["eq_at"][dirs[:, None], eh]
            e_rd = carry["eq_rd"][dirs[:, None], eh]
            e_have = jj[None, :] < carry["eq_cnt"][:, None]
            e_ready = e_rd < now_end
            spend_mat = jnp.where((dirs < 2)[:, None],
                                  e_sz.astype(jnp.float32) + ovh, 0.0)
            pops, prev = [], jnp.ones((3,), bool)
            b_run = budget
            r_run = res_bud
            for j in range(Ke):
                bud_ok = jnp.concatenate(
                    [b_run, jnp.asarray([3e38], jnp.float32)]) > 0.0
                pop_j = prev & e_have[:, j] & e_ready[:, j] & bud_ok
                b_run = b_run - jnp.where(pop_j[:2], spend_mat[:2, j], 0.0)
                if R_res:
                    r_run = r_run - (
                        res_w_eg[:, e_fl[:, j]]
                        * jnp.where(pop_j, e_sz[:, j], 0)
                        .astype(jnp.float32)[None, :]).sum(1)
                pops.append(pop_j)
                prev = pop_j
            pop = jnp.stack(pops, axis=1)                       # [3, Ke]
            budget = b_run
            res_bud = r_run
            npop = pop.astype(jnp.int32).sum(1)
            carry["eq_head"] = (carry["eq_head"] + npop) % cfg.eq_len
            carry["eq_cnt"] = carry["eq_cnt"] - npop
            carry["credits_used"] = carry["credits_used"] - npop.sum()
            ser = jnp.where((dirs < 2)[:, None],
                            e_sz.astype(jnp.float32)
                            / bpc[jnp.minimum(dirs, 1)][:, None], 0.0)
            comp_time = jnp.maximum(e_rd, now) + ser.astype(jnp.int32)
            lat = comp_time - e_at
            # completion ring in sequential (iteration, direction) order
            flat = lambda x: x.T.reshape(-1)                    # noqa: E731
            popf = flat(pop)
            offs = jnp.cumsum(popf.astype(jnp.int32)) - popf.astype(jnp.int32)
            idx = jnp.where(popf, (carry["comp_n"] + offs) % cfg.comp_cap,
                            cfg.comp_cap)
            carry["comp_fl"] = carry["comp_fl"].at[idx].set(flat(e_fl))
            carry["comp_lat"] = carry["comp_lat"].at[idx].set(flat(lat))
            carry["comp_t"] = carry["comp_t"].at[idx].set(flat(comp_time))
            carry["comp_sz"] = carry["comp_sz"].at[idx].set(flat(e_isz))
            carry["comp_n"] = carry["comp_n"] + npop.sum()
            carry["c_done_msgs"] = carry["c_done_msgs"].at[flat(e_fl)].add(
                popf.astype(jnp.int32))
            lo = carry["c_done_b_lo"].at[flat(e_fl)].add(
                jnp.where(popf, flat(e_isz), 0))
            carry["c_done_b_hi"] = carry["c_done_b_hi"] + (lo >> 20)
            carry["c_done_b_lo"] = lo & 0xFFFFF
            carry["c_lat_sum"] = carry["c_lat_sum"].at[flat(e_fl)].add(
                jnp.where(popf, flat(lat).astype(jnp.float32), 0.0))
        else:
            carry, budget, res_bud = _fori(cfg.k_eg, eg_body,
                                           (carry, budget, res_bud))

        # Positive leftover budget is lost (a link cannot save idle time);
        # negative budget (serialization debt of in-flight messages) carries.
        carry["lres"] = jnp.minimum(budget, 0.0)
        if R_res:
            # each axis is a token bucket: unused budget carries up to the
            # axis' burst depth (burst 0 reproduces the link's lose-idle-time
            # semantics); debt always carries
            carry["res_res"] = jnp.minimum(res_bud, args["res_burst"])
    return carry


def _run_core(cfg: SimConfig, carry: dict, args: dict, axis=None) -> dict:
    xs = args["t0"] + jnp.arange(cfg.n_ticks, dtype=jnp.int32)
    carry, _ = jax.lax.scan(lambda c, t: (_tick(cfg, args, c, t, axis), None),
                            carry, xs)
    return carry


# ---------------------------------------------------------------------------
# Module-level compile cache
# ---------------------------------------------------------------------------

_RUN_CACHE: dict[Any, Any] = {}
_CACHE_MAX = 64     # profiler sweeps can touch many context shapes; evict
                    # oldest engines (FIFO) so a long-lived control plane
                    # does not accumulate compiled executables unboundedly


def _get_run(key, builder):
    fn = _RUN_CACHE.get(key)
    if fn is None:
        if len(_RUN_CACHE) >= _CACHE_MAX:
            _RUN_CACHE.pop(next(iter(_RUN_CACHE)))
        fn = builder()
        _RUN_CACHE[key] = fn
    return fn


def cache_info() -> dict[str, int]:
    """Compile-cache stats: distinct engine signatures + live XLA traces.

    ``traces`` counts actual jit-cache entries across all cached engines —
    a steady value across repeated ``simulate()`` / ``run_managed`` windows
    proves zero recompiles.  ``_cache_size`` is a private attribute of
    jit-wrapped functions (present in the jax pinned by
    requirements-dev.txt); without it each entry counts as one trace."""
    return {"entries": len(_RUN_CACHE),
            "traces": sum(getattr(f, "_cache_size", lambda: 1)()
                          for f in _RUN_CACHE.values())}


def cache_clear() -> None:
    _RUN_CACHE.clear()


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def run_window(flows: FlowSet, accels: AccelTable, link: LinkSpec,
               cfg: SimConfig, tb_state: tb.TBState, arr_t, arr_sz,
               stall_mask=None, *, t0_ticks: int = 0,
               carry: dict | None = None) -> dict:
    """Run one compiled window; returns the raw device carry.

    The input carry is **donated**: device buffers are reused in place, so
    do not touch a carry after passing it back in (hand the returned one
    forward instead, as ``ArcusRuntime.run_managed`` does)."""
    args = _pack_args(flows, accels, link, cfg, arr_t, arr_sz, stall_mask,
                      t0_ticks)
    if carry is None:
        carry = init_carry(flows, accels, cfg, tb_state,
                           n_res=len(getattr(link, "resources", ())))
    else:
        carry = reconfigure_carry(carry, tb_state)
    key = ("single", _static_cfg(cfg), _args_sig(args))
    run = _get_run(key, lambda: jax.jit(
        functools.partial(_run_core, _static_cfg(cfg)),
        donate_argnums=(0,)))
    return run(carry, args)


def _as_list(x, B):
    return list(x) if isinstance(x, (list, tuple)) else [x] * B


def run_window_batch(flows: FlowSet | Sequence[FlowSet],
                     accels: AccelTable | Sequence[AccelTable],
                     link: LinkSpec | Sequence[LinkSpec],
                     cfg: SimConfig | Sequence[SimConfig],
                     tb_states: Sequence[tb.TBState] | None,
                     arr_t, arr_sz, stall_mask=None, *,
                     t0_ticks: int = 0, carry: dict | None = None,
                     fl_masks: Sequence[np.ndarray] | None = None) -> dict:
    """Run B independent windows in one compiled ``jax.vmap`` call.

    Batched per element: arrival trace, TBState registers, and (when
    sequences are passed) flow sets, SimConfigs, accelerator tables, link
    specs and ``[B, T]`` stall masks.  Flow sets may have *different flow
    counts*: they are padded to the largest count and masked (``fl_mask``),
    with counters of active lanes bitwise-equal to unpadded serial runs.
    Accelerator tables may likewise have *different accelerator counts*:
    they are padded to the largest count (``pad_accel_table``) and masked
    (``ac_mask``), with the same bitwise guarantee.  SimConfigs may differ
    only in the traced mode fields (``TRACED_CFG_FIELDS``: shaping,
    arbiter, software-delay model) — the structural fields form the single
    compile signature.

    Passing back the returned ``carry`` resumes all B dataplanes with fresh
    per-element TBState registers applied (the fleet-scale analogue of
    ``run_window``'s resumption: ``ArcusRuntime.run_managed_batch`` drives
    its whole window loop through this).  On resumption ``tb_states=None``
    skips the register rewrite entirely — the carry's registers are
    already current (the fast path for a window after which no server
    reconfigured; bitwise-identical to rewriting the unchanged values).
    The input carry is **donated** — hand the returned one forward, never
    reuse the one passed in.  Returns the raw batched carry.

    ``fl_masks`` (one ``[n_flows_max]`` bool array per element) overrides
    the default validity masks: the tenant-lifecycle control plane uses it
    to punch *mid-table holes* (a departed tenant's lane goes inert while
    every other lane keeps its position, so a resumed carry never needs a
    re-pack or a recompile).  Without it, masks are the usual active
    prefix derived from each element's flow count."""
    with jax.profiler.TraceAnnotation("arcus.engine.prepare"):
        if not hasattr(arr_t, "ndim"):       # nested python lists
            arr_t = np.asarray(arr_t)
            arr_sz = np.asarray(arr_sz)
        if arr_t.ndim != 3:
            raise ValueError(
                f"arr_t must be [B, N, M] (got ndim={arr_t.ndim}) — "
                "see stack_arrivals()")
        B = arr_t.shape[0]
        flows_l = _as_list(flows, B)
        accels_l = _as_list(accels, B)
        links_l = _as_list(link, B)
        cfgs_l = _as_list(cfg, B)
        if tb_states is None and carry is None:
            raise ValueError("tb_states=None is only valid when resuming a "
                             "carry (initial registers are required)")
        if not (len(accels_l) == B and len(links_l) == B
                and (tb_states is None or len(tb_states) == B)
                and len(flows_l) == B and len(cfgs_l) == B):
            raise ValueError(
                f"batch size mismatch: arr_t has B={B} but "
                f"flows={len(flows_l)}, accels={len(accels_l)}, "
                f"links={len(links_l)}, "
                f"tb_states={len(tb_states or [])}, cfgs={len(cfgs_l)}")
        cfg0 = cfgs_l[0]
        if any(_static_cfg(c) != _static_cfg(cfg0) for c in cfgs_l[1:]):
            raise ValueError(
                "batched SimConfigs may differ only in traced fields "
                f"{TRACED_CFG_FIELDS}")
        for c in cfgs_l[1:]:
            _check_modes(c)    # element 0 is checked by _pack_args below
        a_max = max(a.n for a in accels_l)
        padded_l = [pad_accel_table(a, a_max) for a in accels_l]

        n_res = len(getattr(links_l[0], "resources", ()))
        if any(len(getattr(l, "resources", ())) != n_res
               for l in links_l[1:]):
            raise ValueError(
                "batched LinkSpecs must all carry the same number of resource "
                "axes (resource tables are a shared traced shape; a huge-"
                "capacity axis is inert if an element needs fewer)")

        n_max = max(f.n for f in flows_l)
        if arr_t.shape[1] != n_max:
            raise ValueError(
                f"arr_t flow axis {arr_t.shape[1]} != n_flows_max {n_max} — "
                "see stack_arrivals()")

        if fl_masks is not None and len(fl_masks) != B:
            raise ValueError(
                f"fl_masks must have one mask per element (got {len(fl_masks)} "
                f"for B={B})")
        flows_batched = (fl_masks is not None
                         or (isinstance(flows, (list, tuple))
                             and (len(set(f.n for f in flows_l)) > 1
                                  or any(f is not flows_l[0] for f in flows_l))))
        accel_batched = isinstance(accels, (list, tuple))
        link_batched = isinstance(link, (list, tuple))
        cfg_batched = (isinstance(cfg, (list, tuple))
                       and any(c != cfg0 for c in cfgs_l[1:]))
        stall_np = None if stall_mask is None else np.asarray(stall_mask, bool)
        stall_batched = stall_np is not None and stall_np.ndim == 2

        # pack with tiny placeholders for the per-element entries (the real
        # batched trace / stall arrays replace them below) so a multi-megabyte
        # single-element trace is never uploaded just to be discarded
        ph = np.zeros((n_max, 1), np.int32)
        flows0 = flows_l[0] if flows_l[0].n == n_max else flows_l[
            int(np.argmax([f.n for f in flows_l]))]
        args = _pack_args(flows0, padded_l[0], links_l[0], cfg0,
                          ph, ph, None, t0_ticks)
        axes = {k: None for k in args}
        args["arr_t"] = jnp.asarray(arr_t, jnp.int32)
        args["arr_sz"] = jnp.asarray(arr_sz, jnp.int32)
        axes["arr_t"] = axes["arr_sz"] = 0
        if flows_batched:
            per_el = [_flow_args(f, n_max) for f in flows_l]
            if fl_masks is not None:
                for p, m in zip(per_el, fl_masks):
                    m = np.asarray(m, bool)
                    if m.shape != (n_max,):
                        raise ValueError(
                            f"fl_masks entries must be [{n_max}] bool "
                            f"(got shape {m.shape})")
                    p["fl_mask"] = m
            for k in per_el[0]:
                args[k] = jnp.stack([jnp.asarray(p[k]) for p in per_el])
                axes[k] = 0
        if cfg_batched:
            args["mode"] = jnp.asarray([c.shaping for c in cfgs_l], jnp.int32)
            args["arb"] = jnp.asarray([c.arbiter for c in cfgs_l], jnp.int32)
            args["sw_delay"] = jnp.asarray(
                [c.sw_host_delay_cycles for c in cfgs_l], jnp.float32)
            args["sw_jit"] = jnp.asarray(
                [c.sw_jitter_cycles for c in cfgs_l], jnp.float32)
            axes["mode"] = axes["arb"] = axes["sw_delay"] = axes["sw_jit"] = 0
        if accel_batched:
            args["svc_tab"] = jnp.stack(
                [jnp.asarray(a.service_cycles, jnp.float32) for a in padded_l])
            args["eg_tab"] = jnp.stack(
                [jnp.asarray(a.egress_bytes, jnp.float32) for a in padded_l])
            args["ac_mask"] = jnp.stack(
                [jnp.asarray(_accel_mask(a), bool) for a in padded_l])
            axes["svc_tab"] = axes["eg_tab"] = axes["ac_mask"] = 0
        if link_batched:
            args["bpc"] = jnp.asarray([l.bytes_per_cycle() for l in links_l],
                                      jnp.float32)
            args["ovh"] = jnp.asarray(
                [l.msg_overhead_bytes for l in links_l], jnp.float32)
            args["credits"] = jnp.asarray([l.credits for l in links_l], jnp.int32)
            axes["bpc"] = axes["ovh"] = axes["credits"] = 0
            if n_res:
                args["res_cap"] = jnp.asarray(
                    np.stack([l.resource_caps_per_cycle() for l in links_l]),
                    jnp.float32)
                args["res_burst"] = jnp.asarray(
                    np.stack([l.resource_burst_bytes() for l in links_l]),
                    jnp.float32)
                axes["res_cap"] = axes["res_burst"] = 0
        if n_res and (flows_batched or accel_batched or link_batched):
            # demand coefficients depend on flows x accels x link axes; batch
            # the [R-1, n_max] tables whenever any of the three is per-element
            tabs = [_resource_tables(flows_l[b], padded_l[b], links_l[b], n_max)
                    for b in range(B)]
            args["res_w_in"] = jnp.asarray(np.stack([t[0] for t in tabs]),
                                           jnp.float32)
            args["res_w_eg"] = jnp.asarray(np.stack([t[1] for t in tabs]),
                                           jnp.float32)
            axes["res_w_in"] = axes["res_w_eg"] = 0
        if stall_np is not None:
            args["stall"] = jnp.asarray(
                _window_stall(stall_np, cfg0, t0_ticks), bool)
            axes["stall"] = 0 if stall_batched else None

        if carry is None:
            tb_padded = [pad_tb_state(tb_states[b], n_max) for b in range(B)]
            carries = [init_carry(flows_l[b], padded_l[b], cfg0, tb_padded[b],
                                  n_flows=n_max, n_res=n_res)
                       for b in range(B)]
            carry = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *carries)
        elif tb_states is not None:
            # resumed fleet window: write only the per-element parameter
            # "registers" (stacked [B, n_max] leaves), like run_window does;
            # tb_states=None resumes without touching the registers
            tb_padded = [pad_tb_state(tb_states[b], n_max) for b in range(B)]
            stacked_tb = jax.tree_util.tree_map(
                lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *tb_padded)
            carry = reconfigure_carry(carry, stacked_tb)

        key = ("batch", _static_cfg(cfg0), B, _args_sig(args),
               tuple(sorted(axes.items())))
        run = _get_run(key, lambda: jax.jit(
            jax.vmap(functools.partial(_run_core, _static_cfg(cfg0),
                                       axis=FLEET_AXIS),
                     in_axes=(0, axes), axis_name=FLEET_AXIS),
            donate_argnums=(0,)))
    with jax.profiler.TraceAnnotation("arcus.engine.dispatch"):
        return run(carry, args)
