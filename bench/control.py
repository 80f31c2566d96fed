#!/usr/bin/env python3
"""The control of ``correct``: the reference in bfloat16 in the program's place.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds S]
                             [--program]

Runs each seed of the cell through the harness with the system under
test replaced by the plain reference computed on bfloat16-rounded bounds
(the configuration states float32), at the cell's own size and load, for
a short window, and prints one JSON line per run with the compared
numbers.  Every such run has to come out not correct.  ``--program``
also runs the program itself on each seed in the same process, so that
the readings of sound runs and of the control come from one set-up of
JAX.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    systems = ("program", "control") if args.program else ("control",)
    for seed in args.seeds:
        for system in systems:
            line = harness.execute(cell, seed, args.seconds, False,
                                   t_start=time.perf_counter(),
                                   system=system)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "system": system, "correct": line["correct"],
                              "attempted": line["attempted"],
                              "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
