"""The benchmark's own arrival-trace generator.

One general generator reads a traffic mix (``traffic/<mix>.json``) and a
deployment (``configs/<config>.json``) and draws every tenant's arrival
trace from ``--seed``.  It is a frozen copy of the program's built-in
``cbr`` / ``poisson`` / ``onoff`` processes (``repro.core.sim``) and of
``mmpp`` (``repro.workloads.generators``), so the yardstick's traffic does
not move when the program's generators do.  It imports nothing of the
program.

Traces are ``(times [N, M] int32 cycles, sizes [N, M] int32 bytes)``,
padded with ``INF_I32`` / 0, in the same layout and from the same random
stream order as the program's ``gen_arrivals``: processes draw in the
order ``cbr, poisson, onoff, mmpp``.
"""
from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

INF_I32 = 2**31 - 1
MAX_MSGS = 1 << 18


@dataclasses.dataclass(frozen=True)
class Pattern:
    """One tenant's injection pattern, as plain data."""

    msg_bytes: int
    load: float
    load_ref_gbps: float
    process: str = "poisson"
    burst_len: int = 32
    duty: float = 0.25
    params: tuple = ()

    @property
    def rate(self) -> float:
        """Mean messages per second."""
        line_bps = self.load_ref_gbps * 1e9 / 8.0
        return max(self.load * line_bps / max(self.msg_bytes, 1), 1e-9)

    def param(self, name, default=None):
        for k, v in self.params:
            if k == name:
                return v
        return default


def _cbr(pats, rates, rng, m0, horizon_s):
    return np.broadcast_to(1.0 / rates[:, None], (len(pats), m0))


def _poisson(pats, rates, rng, m0, horizon_s):
    return rng.exponential(1.0, (len(pats), m0)) / rates[:, None]


def _onoff(pats, rates, rng, m0, horizon_s):
    col = np.arange(m0)
    bl = np.array([p.burst_len for p in pats])[:, None]
    duty = np.array([p.duty for p in pats])[:, None]
    period = bl / rates[:, None]
    on_gap = duty * period / bl
    idle = (col[None, :] % bl) == bl - 1
    return on_gap + idle * (1 - duty) * period


def _mmpp_weights(pat):
    mults = np.asarray(pat.param("states", (0.25, 2.5)), float)
    soj = pat.param("sojourn_s", None)
    w = (np.ones_like(mults) if soj is None
         else np.broadcast_to(np.asarray(soj, float), mults.shape))
    return mults, max(float((mults * w).sum() / w.sum()), 1e-12)


def _mmpp(pats, rates, rng, m0, horizon_s):
    """Markov-modulated Poisson: cyclic rate states with exponential
    sojourns (default ``horizon / 6``), normalized so the long-run mean is
    the nominal rate; arrivals by inverting the cumulative intensity."""
    out = np.empty((len(pats), m0))
    for j, (pat, rate) in enumerate(zip(pats, rates)):
        mults, wmean = _mmpp_weights(pat)
        soj = pat.param("sojourn_s", None)
        if soj is None:
            soj = horizon_s / 6.0
        soj = np.broadcast_to(np.asarray(soj, float), mults.shape)
        t_knots, lam_knots = [0.0], [0.0]
        s, t = 0, 0.0
        while t < horizon_s:
            dur = rng.exponential(soj[s])
            lam = rate * mults[s] / wmean
            t += dur
            t_knots.append(t)
            lam_knots.append(lam_knots[-1] + lam * dur)
            s = (s + 1) % len(mults)
        u = np.cumsum(rng.exponential(1.0, m0))
        tt = np.interp(u, np.asarray(lam_knots), np.asarray(t_knots))
        out[j] = np.diff(tt, prepend=0.0)
    return out


def _mmpp_budget(pat):
    mults, wmean = _mmpp_weights(pat)
    return float(mults.max()) / wmean + 0.05


#: process name -> (gap drawer, message-budget factor); draw order is the
#: insertion order
PROCESSES = {
    "cbr": (_cbr, lambda p: 1.0),
    "poisson": (_poisson, lambda p: 1.0),
    "onoff": (_onoff, lambda p: 1.0),
    "mmpp": (_mmpp, _mmpp_budget),
}


def trace_budget(pat: Pattern, horizon_s: float) -> int:
    """Message columns one tenant's trace may take over ``horizon_s``."""
    fac = PROCESSES[pat.process][1](pat)
    return int(np.ceil(pat.rate * fac * horizon_s)) + 16


def gen_traces(pats: list[Pattern], n_ticks: int, tick_cycles: int,
               clock_hz: float, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Arrival traces of ``pats`` (lane order) over ``n_ticks`` ticks."""
    unknown = sorted({p.process for p in pats} - set(PROCESSES))
    if unknown:
        raise ValueError(f"unknown arrival process(es) {unknown}")
    rng = np.random.default_rng(seed)
    horizon_cycles = n_ticks * tick_cycles
    horizon_s = horizon_cycles / clock_hz
    n = len(pats)
    rates = np.array([p.rate for p in pats])
    procs = np.array([p.process for p in pats])
    fac = np.array([PROCESSES[p.process][1](p) for p in pats])
    ms = np.minimum(MAX_MSGS,
                    np.ceil(rates * fac * horizon_s) + 16).astype(np.int64)
    m0 = int(max(1, ms.max()))
    gaps = np.empty((n, m0))
    for name, (draw, _budget) in PROCESSES.items():
        idx = np.flatnonzero(procs == name)
        if idx.size:
            gaps[idx] = draw([pats[i] for i in idx], rates[idx], rng, m0,
                             horizon_s)
    t = np.cumsum(gaps, axis=1) * clock_hz
    sizes = np.broadcast_to(
        np.array([p.msg_bytes for p in pats], np.int64)[:, None],
        (n, m0))
    valid = (t < horizon_cycles) & (np.arange(m0)[None, :] < ms[:, None])
    m = int(max(1, valid.sum(axis=1).max()))
    times = np.where(valid, np.minimum(t, INF_I32 - 1), INF_I32) \
        .astype(np.int32)[:, :m]
    szs = np.where(valid, sizes, 0).astype(np.int32)[:, :m]
    return times, szs


def pattern_for(tenant: dict, mix: dict) -> Pattern:
    """A config tenant's pattern under a traffic mix: the mix's process
    entry for the tenant's class, else its ``"*"`` entry."""
    procs = mix["processes"]
    p = dict(procs.get(tenant["class"], procs.get("*")))
    params = tuple((k, tuple(v) if isinstance(v, list) else v)
                   for k, v in sorted(p.pop("params", {}).items()))
    return Pattern(msg_bytes=int(tenant["msg_bytes"]),
                   load=float(tenant["load"]),
                   load_ref_gbps=float(tenant["load_ref_gbps"]),
                   params=params, **p)


def digest(times: np.ndarray, sizes: np.ndarray) -> str:
    """Short content hash of a trace pair (pinned in the tests)."""
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(times).tobytes())
    h.update(np.ascontiguousarray(sizes).tobytes())
    return h.hexdigest()[:16]
