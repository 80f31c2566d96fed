#!/usr/bin/env python3
"""Run one cell's window under the profiler and print where an operation's
time goes: device time by program stage and device idle by program span.

    python3 bench/trace/stages.py --workload <cell> --seed <n> --seconds <s>

Run on a chip, from the root of a checkout.  The set-up and the window
are the harness's own (``--trace 1``); the trace is then reduced twice,
as the benchmark does (``bench/trace/reduce.py``) and by stage and span
(``bench/trace/scopes.py``).  The last line of standard output is one
JSON object, its times in milliseconds per timed operation:
``<stage>_dev_ms`` for each ``ddm.*`` stage and ``unscoped_dev_ms``,
``sync_idle_ms`` (idle under a ``ddm.*.readback`` span),
``dispatch_idle_ms`` (under ``ddm.probe``/``ddm.emit`` outside their
readbacks), ``idle_ms`` by innermost span, the blocking ``readbacks`` of
the last call, the driver's own phase spans, and the largest operations
as ``program/stage/operation``.  A program without the spans or stages
leaves those entries empty.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)

from bench import harness  # noqa: E402
from bench.trace import reduce as trace_reduce  # noqa: E402
from bench.trace import scopes  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    ctx = harness.Context(cell.config, cell.traffic, args.seed, True)
    sys.path.insert(0, str(cell.root / "src"))
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    device = harness.device_info(cell.chips)
    driver = cell.driver().build(ctx)
    window, latencies, trace_dir = harness.run_window(driver, args.seconds,
                                                      True)
    try:
        base = trace_reduce.reduce_dir(trace_dir, driver.SPANS)
        stage = scopes.reduce_dir(trace_dir, driver.SPANS)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ops = len(latencies)

    def per_op_ms(seconds: float) -> float:
        return 1000.0 * seconds / ops

    recorder = getattr(driver, "recorder", None)
    last = recorder.last if recorder is not None else None
    under_match = sum(v for k, v in stage.idle_by_span.items()
                      if k == "audit.match" or k.startswith(scopes.PREFIX))
    line = {
        "workload": args.workload, "seed": args.seed, "device": device,
        "ops": ops, "window_s": window, "trace_window_s": stage.window_s,
        "busy_s": stage.busy_s, "busy_s_reduce": base.busy_s,
        "scoped_share_of_busy": (stage.scoped_s / stage.busy_s
                                 if stage.busy_s else None),
        **{f"{k.removeprefix(scopes.PREFIX)}_dev_ms": per_op_ms(v)
           for k, v in sorted(stage.stages.items())},
        "sync_idle_ms": per_op_ms(scopes.sync_idle_s(stage)),
        "dispatch_idle_ms": per_op_ms(scopes.dispatch_idle_s(stage)),
        "idle_under_match_ms": per_op_ms(under_match),
        "idle_ms": {k: per_op_ms(v) for k, v in
                    sorted(stage.idle_by_span.items())},
        "idle_ms_reduce": {k: per_op_ms(v) for k, v in
                           sorted(base.idle_by_span.items())},
        "readbacks": getattr(last, "readbacks", None),
        "phase_ms": {k: 1000.0 * sum(s.get(k, 0.0) for s in driver.spans)
                     / ops for k in ("probe", "emit", "d2h")},
        "device_ops": trace_reduce.top(stage.ops, 15),
    }
    driver.finish()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
