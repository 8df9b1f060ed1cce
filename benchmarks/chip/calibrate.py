#!/usr/bin/env python3
"""Readings that the check's limits are set from (not run by the benchmark).

    python3 benchmarks/chip/calibrate.py --workload mica8.fig11a \\
        --seeds 1,2,3 [--out bench_out/calib.jsonl]

For each seed, in one process (set-up is long: the ProfileTable is shared
across seeds), build the cell at its own size, run one timeline through
the program, and compare it with the plain reference twice: as it stands
(the program's reading, which sets each limit's lower end) and computed in
bfloat16 where the configuration states float32 (the lower-precision
control, which sets the upper end).  One JSON line per seed and variant.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax
    if jax.default_backend() != "tpu":
        print(f"calibrate.py needs a TPU; backend {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    import plainref
    import run
    from fleetcell import Cell
    from repro import compile_cache
    compile_cache.configure()
    wl = {w["name"]: w for w in run.benchmark()["workloads"]}[a.workload]
    out = open(a.out, "a") if a.out else None
    profile = None
    try:
        for seed in (int(s) for s in a.seeds.split(",")):
            cell = Cell(wl, seed)
            cell.setup(profile=profile, warm=False)
            profile = cell.profile
            t = time.perf_counter()
            tl = cell.timeline()
            run_s = time.perf_counter() - t
            for variant in ("exact", "bf16"):
                t = time.perf_counter()
                v = plainref.check(cell, [tl], variant=variant)
                rec = dict(workload=a.workload, seed=seed, variant=variant,
                           correct=v["correct"], run_s=run_s, check_s=time.perf_counter() - t,
                           **{k: n["value"] for k, n in v["numbers"].items()})
                line = json.dumps(rec)
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
            cell.recorder.close()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
