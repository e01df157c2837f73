#!/usr/bin/env python3
"""Run one cell once through ``run.py`` with the program's span tracer
installed around the measured window.

    python3 chipbench/spans_run.py --workload det720_x6_sat --seed 7 \
        --seconds 51 --trace 0

Takes ``run.py``'s arguments and prints its result line. With ``--trace
0`` the profiler stays off, so the end-to-end metrics give the tracer's
cost against ``run.py``'s. With ``--trace 1`` the tracer's payload is
written beside the profiler trace (``program_spans.json``) and one more
line follows, the last: ``{"program_spans": ...}``, the engine loop's
numbers on the trace's clock (``attribution``).

The benchmark's harness does not install the tracer yet; once it does
(PERF.md section 7), it reads these numbers itself and this file goes.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import run  # noqa: E402  (its T_START: this process's start)

SPANS = "program_spans.json"


@contextlib.contextmanager
def tracing():
    """While the block runs, each ``load.drive`` call (the window) runs
    with a tracer installed; yields that tracer."""
    from chipbench import load
    from repro.obs import trace as obs_trace

    tracer = obs_trace.Tracer()
    drive = load.drive

    def traced(*args, **kwargs):
        obs_trace.install(tracer)
        try:
            return drive(*args, **kwargs)
        finally:
            obs_trace.uninstall()

    load.drive = traced
    try:
        yield tracer
    finally:
        load.drive = drive


def attribution(xplane: Path, tracer) -> dict:
    """The trace at ``xplane`` read with the program's spans written
    beside it: the loop's numbers, raw and with the spans moved earlier
    by each bound on the device's lead over the host clock (``low``: how
    far server steps start before their dispatch; ``high``: the least
    time from a camera step's end to the end of a wait on it); the idle
    gaps they name; the clock check; what each in-window warm-up
    compiled."""
    from chipbench import program_spans, trace_reduce

    tr = program_spans.load(str(xplane), str(xplane.with_name(SPANS)))
    span = trace_reduce.window(tr)
    if span is None:  # no program ran on a device
        return {}
    lo, hi = span
    out = dict(program_spans.numbers(tr, lo, hi) or {})
    waits = program_spans.wait_offsets_ns(tr)
    bounds = {"low": program_spans.lead_ns(tr),
              "high": min(waits) if waits else None}
    out["lead_corrected"] = {
        k: {"lead_ms": ns * 1e-6, **{
            m: v for m, v in (program_spans.numbers(
                program_spans.shifted(tr, ns), lo, hi) or {}).items()
            if "share" in m}}
        for k, ns in bounds.items() if ns is not None}
    out["idle_gaps"] = [[n, s] for n, s in trace_reduce.idle_gaps(tr, lo,
                                                                  hi)]
    ms = sorted(o * 1e-6 for o in waits)
    out["wait_camera_offset_ms"] = {
        "n": len(ms), "waits": len(program_spans.spans(tr, "wait_camera"))}
    if ms:
        out["wait_camera_offset_ms"].update(
            min=ms[0], median=ms[len(ms) // 2], max=ms[-1],
            in_0_2=sum(0.0 <= m <= 2.0 for m in ms) / len(ms))
    out["warm_compiled"] = [e.args.get("compiled") for e in tracer.events
                            if e.name == "warm"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    ap.add_argument("--trace", type=int, default=0)
    args, _ = ap.parse_known_args(argv)
    with tracing() as tracer:
        rc = run.main(argv)
    if rc or not args.trace:
        return rc
    xplane = sorted((ROOT / ".bench_out" / "trace" / args.workload).glob(
        "plugins/profile/*/*.xplane.pb"))[-1]
    with open(xplane.with_name(SPANS), "w") as f:
        json.dump(tracer.payload(), f)
    print(json.dumps({"program_spans": attribution(xplane, tracer)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
