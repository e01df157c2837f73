"""Readings that set a cell's limits: the program against the reference
(the lower reading) and the control against the reference (the upper
reading), over many seeds in one process.

    python3 chipbench/calibrate.py --workload seg720_x6_sat \
        --seeds 11,12,13 --seconds 3 [--controls bf16_3x,high]

Each seed builds the cell's system from its own video, serves a short
window at the cell's own load through the timed path, and compares every
answer with the reference at the configuration's precision. The control
is the reference computed in the nearest precision below the stated one
(``bf16_3x``: three bf16 passes, emulated; ``high``: the backend's own
``Precision.HIGH``, the same three passes on a TPU), put in the
program's place: its answers cover the same stream-chunks as the
program's and are compared the same way. One JSON line per seed, then the
largest program reading and the smallest control reading of each number.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def control_answers(ans, ctrl):
    from chipbench.compare import Answer

    return [Answer(a.units, sum(ctrl[u][0] for u in a.units),
                   sum(ctrl[u][1] for u in a.units)) for a in ans]


def readings(cell, seed, seconds, controls=("bf16_3x",)):
    from chipbench import compare, load, system

    cfg, mix = cell["cfg"], cell["traffic_mix"]
    n = cfg["streams_per_chip"] * cell["chips"]
    sysm = system.build(cfg, seed, n, mix["pool_chunks"])
    load.warm_up(sysm, mix)
    window = load.drive(sysm, mix, seconds)
    sysm.engine = None
    gc.collect()
    ans, attempted, failed = compare.answers(window, mix["pool_chunks"], n)
    units = [u for a in ans for u in a.units]
    ref = compare.reference_units(sysm, units)
    out = {"seed": seed, "answers": len(ans), "attempted": attempted,
           "failed": failed, "program": compare.gaps(ans, ref)}
    for precision in controls:
        ctrl = compare.reference_units(sysm, units, precision=precision)
        out["control." + precision] = compare.gaps(
            control_answers(ans, ctrl), ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--controls", default="bf16_3x",
                    help="comma-separated control precisions, or none")
    args = ap.parse_args(argv)
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    harness.configure_jax(cell["cfg"], cell["chips"], require_chip=True)
    controls = [c for c in args.controls.split(",") if c != "none"]
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        row = readings(cell, seed, args.seconds, controls)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows),
               "lower": {k: max(r["program"][k] for r in rows)
                         for k in rows[0]["program"]}}
    for c in controls:
        summary["upper." + c] = {k: min(r["control." + c][k] for r in rows)
                                 for k in rows[0]["program"]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
