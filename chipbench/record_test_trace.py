"""Record the small chip trace the trace-reduction test reads.

    python3 chipbench/record_test_trace.py OUT.xplane.pb.gz

Runs ``det720_x6_sat`` at 96x160 for one traced second on the chip and
writes the profiler's XSpace, gzipped, to OUT. The checked-in trace was
recorded with the configuration's earlier 4 streams.
"""
from __future__ import annotations

import gzip
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(out: str) -> int:
    from chipbench import harness

    cell = harness.load_cell("det720_x6_sat")
    cell["cfg"].update(height=96, width=160)
    cell["traffic_mix"].update(pool_chunks=2, trace_seconds=1)
    harness.execute(cell, 12345, 1, True, T_START)
    src = sorted((ROOT / ".bench_out" / "trace" / cell["name"]).glob(
        "plugins/profile/*/*.xplane.pb"))[-1]
    with open(src, "rb") as f, gzip.open(out, "wb") as g:
        g.write(f.read())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
