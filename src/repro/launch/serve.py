"""Serving launcher: multi-tenant Arcus-shaped model serving.

Dev mode (default, CPU): reduced variant of the selected arch, real token
generation through the continuous-batching engine, virtual-clocked by the
FULL config's roofline cost model — per-tenant SLOs enforced by the Arcus
token buckets.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b \\
        --tenants 1200,800 --duration 3
"""
from __future__ import annotations

import argparse

import numpy as np

from repro import compile_cache
from repro.configs.registry import ARCH_IDS, get_config, get_reduced_config
from repro.core.flow import SLO
from repro.models import transformer as T
from repro.serving.costmodel import HardwareSpec, StepCostModel
from repro.serving.engine import ServingEngine
from repro.serving.request import Request, Tenant
from repro.serving.scheduler import ArcusScheduler, FCFSScheduler


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma3-12b")
    ap.add_argument("--tenants", default="1200,800",
                    help="comma-separated tokens/s SLOs")
    ap.add_argument("--background", action="store_true", default=True,
                    help="add an opportunistic background tenant")
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--chips", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--unshaped", action="store_true",
                    help="FCFS baseline instead of Arcus shaping")
    args = ap.parse_args()
    compile_cache.configure()

    cfg = get_reduced_config(args.arch)
    params, _ = T.init_model(0, cfg)
    engine = ServingEngine(cfg, params, max_batch=args.max_batch,
                           max_len=256)
    cost = StepCostModel(get_config(args.arch),
                         HardwareSpec(chips=args.chips))
    slos = [float(x) for x in args.tenants.split(",")]
    tenants = [Tenant(i, SLO.iops(s), "reserved")
               for i, s in enumerate(slos)]
    if args.background:
        tenants.append(Tenant(len(tenants), SLO.iops(1e9), "opportunistic"))
    cls = FCFSScheduler if args.unshaped else ArcusScheduler
    sched = cls(engine, tenants, cost)

    rng = np.random.default_rng(0)
    rid = 0
    if args.background:
        for _ in range(24):
            sched.submit(Request(rid, len(slos),
                                 list(rng.integers(0, cfg.vocab, 64)), 16))
            rid += 1
    for k in range(16):
        for tid in range(len(slos)):
            sched.submit(Request(rid, tid,
                                 list(rng.integers(0, cfg.vocab, 12)), 6,
                                 arrive_s=k * args.duration / 32))
            rid += 1

    stats = sched.run(args.duration, max_rounds=2000)
    mode = "FCFS (unshaped)" if args.unshaped else "Arcus"
    print(f"{mode} on {cfg.name} family, {args.chips} chips, "
          f"virtual time {sched.now_s:.2f}s")
    for tid, st in sorted(stats.items()):
        ttft = (f"{np.percentile(st.ttft, 99)*1e3:8.1f}ms p99"
                if st.ttft else "     n/a")
        print(f"  tenant{tid} [{tenants[tid].policy:13s}] "
              f"tokens={st.served_tokens:5d} finished={st.finished:3d} "
              f"ttft={ttft}")


if __name__ == "__main__":
    main()
