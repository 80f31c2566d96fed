"""Benchmark aggregator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus the roofline table from the
dry-run artifacts if they exist).  A module that raises is reported as a
``<name>_ERROR`` row and the run exits non-zero.  Usage:
    PYTHONPATH=src python -m benchmarks.run [--only matching,scaling,...]
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import List

MODULES = ("matching", "churn", "frontend", "scaling", "memory",
           "attention_bench", "moe_bench", "context_parallel_bench")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all")
    args = ap.parse_args()
    selected = MODULES if args.only == "all" else tuple(args.only.split(","))
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    failed: List[str] = []
    rows: List[str] = []
    print("name,us_per_call,derived")
    for name in selected:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            mod.run(rows)
        except Exception as e:   # report it, run the rest, exit non-zero
            rows.append(f"{name}_ERROR,0,{e}")
            failed.append(name)
        for r in rows:
            print(r, flush=True)
        rows.clear()
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)

    # roofline summary (reads dry-run artifacts; skipped if absent)
    try:
        from benchmarks import roofline
        cells = roofline.load_cells()
        if cells:
            ok = [roofline.roofline_row(r) for r in cells]
            ok = [r for r in ok if r.get("status") == "ok"]
            for r in sorted(ok, key=lambda r: (r["arch"], r["shape"])):
                print(f"roofline_{r['arch']}_{r['shape']},"
                      f"{r['step_time_lb_s']*1e6:.0f},"
                      f"dominant={r['dominant']} mfu_bound={r['achievable_mfu']:.3f}")
    except Exception as e:
        print(f"roofline_ERROR,0,{e}")
        failed.append("roofline")
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
