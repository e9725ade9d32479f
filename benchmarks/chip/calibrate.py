#!/usr/bin/env python3
"""Read a cell's compared numbers over many seeds, for the program and for
the lower-precision control, in one process on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1-12 --control-seeds 101-103 --seconds 2 \\
        --out calib.jsonl

Each seed is one whole run of the cell (``run.run_cell``) with a short
window at the cell's own load; the control runs put the adapter's
``control`` (the plain reference at ``high``, three bfloat16 passes) in
the program's place.  The largest program reading and the smallest control
reading are the two ends each limit is set between (PERF.md).
``--witness-seeds`` adds runs of the program itself traced at ``high``
and at ``default`` (one bfloat16 pass), readings that show where those
shortcuts land.  One JSON line per run.  The benchmark's own runs never
call this.
"""

from __future__ import annotations

import argparse
import json
import sys
from functools import partial

import jax

import run
import spec


def seeds(text: str) -> list[int]:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def at_precision(entry, precision: str):
    """``entry`` with its matrix products traced at ``precision``."""
    def call(params, x):
        with jax.default_matmul_precision(precision):
            return entry(params, x)
    return call


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=seeds("1-12"))
    ap.add_argument("--control-seeds", type=seeds, default=seeds("101-103"))
    ap.add_argument("--witness-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    bench = spec.Bench()
    cfg = bench.config(bench.workload(args.workload)["config"])
    control = partial(bench.adapter(cfg["model"]).control, cfg)
    program = run.entry_point(cfg)
    out = open(args.out, "a") if args.out else None
    try:
        for kind, entry, ss in (
                ("program", None, args.seeds),
                ("control", control, args.control_seeds),
                ("program_high", at_precision(program, "high"),
                 args.witness_seeds),
                ("program_default", at_precision(program, "default"),
                 args.witness_seeds)):
            for s in ss:
                r = run.run_cell(bench, args.workload, s, args.seconds, False,
                                 entry=entry)
                line = json.dumps({"workload": args.workload, "kind": kind,
                                   "seed": s, "correct": r["correct"],
                                   "checks": r["checks"],
                                   "metrics": r["metrics"]})
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
