"""The program's own spans on a device trace's clock, and what they say
about the device's idle time.

The engine loop (``repro.engine.multistream``) records one span per layer
boundary per chunk interval through the ``repro.obs.trace`` tracer:
``ingest``, ``dispatch_camera``, ``wait_camera``, ``dispatch_server``,
``fetch`` and ``scoring``, each with the interval id ``ci``; ``warm``
around an in-call warm-up and ``run`` around each engine call. A tracer's
``payload()`` holds them on the host's monotonic clock with one wall-clock
anchor pair. A profiler trace counts its event times from the wall time
in its ``Task Environment`` plane's ``profile_start_time`` (epoch ns), so
a span at monotonic ``ts`` seconds lies at
``(ts - anchor_mono + anchor_wall) * 1e9 - profile_start_time`` trace ns.
``load`` puts the spans into ``Trace.host`` as lines ``program/<lane>``,
where ``trace_reduce.idle_gaps`` names each idle gap by the innermost span
at its middle (the benchmark's traces hold no other host events).

The numbers, per interval where they are times:

- ``ingest_ms``: mean ``ingest`` span, the host slice and device put of
  the interval's chunk;
- ``host_block_ms``: ``wait_camera`` plus ``fetch``, the host blocked on
  the device;
- ``in_call_warm_ms``: ``warm`` spans per ``run`` span;
- ``idle_host_busy_share`` / ``idle_host_blocked_share``: the shares of
  the first device's idle time under a span in which the host works
  (``HOST_BUSY``) and, of the rest, under one in which it waits
  (``HOST_BLOCKED``); ``idle_no_span_share`` is what is left.

The profiler stamps device events about a millisecond ahead of host
events (a program appears to start before its dispatch), which matters
for idle gaps of a few ms: ``lead_ns`` and ``wait_offsets_ns`` bound
that lead from below and above, and ``shifted`` moves the spans by a
bound, so the idle split can be given at each.
"""
from __future__ import annotations

import bisect
import json
import re
import statistics
from typing import Dict, List, Optional

from chipbench import trace_reduce
from chipbench.trace_reduce import Event, Trace

PROGRAM = "program/"  # prefix of the host lines that hold program spans
#: the engine loop's spans in which the host works, and in which it waits
#: on the device or on the warm-up
HOST_BUSY = ("ingest", "dispatch_camera", "dispatch_server", "scoring")
HOST_BLOCKED = ("wait_camera", "fetch", "warm")


def profile_start_ns(pd) -> Optional[int]:
    """``profile_start_time`` of the ``Task Environment`` plane: the wall
    time (epoch ns) at which the trace's event times start."""
    for plane in pd.planes:
        if plane.name == "Task Environment":
            for key, value in plane.stats:
                if key == "profile_start_time":
                    return int(value)
    return None


def program_lines(payload: dict, start_ns: int) -> Dict[str, List[Event]]:
    """The complete spans of a tracer payload on the trace's clock, one
    line ``program/<lane>`` per lane (instants cover no time and stay
    out)."""
    off = round((payload["anchor_wall"] - payload["anchor_mono"]) * 1e9) \
        - start_ns
    lines: Dict[str, List[Event]] = {}
    for e in payload["events"]:
        if e["phase"] != "X":
            continue
        t0 = off + round(e["ts"] * 1e9)
        lines.setdefault(PROGRAM + e["stage"], []).append(
            Event(t0, t0 + round(e["dur"] * 1e9), e["name"]))
    return lines


def load(path: str, program: str) -> Trace:
    """The trace at ``path`` with the spans of the tracer payload written
    as JSON at ``program`` on its clock."""
    with open(program) as f:
        payload = json.load(f)
    pd = trace_reduce._profile(path)
    start = profile_start_ns(pd)
    if start is None:
        raise ValueError(f"{path}: no profile_start_time to map the "
                         f"program's spans onto")
    trace = trace_reduce.from_profile(pd)
    trace.host.update(program_lines(payload, start))
    return trace


def spans(trace: Trace, name: str) -> List[Event]:
    """The program spans named ``name``, in start order."""
    return sorted((e for line, evs in trace.host.items()
                   if line.startswith(PROGRAM) for e in evs
                   if e.name == name), key=lambda e: e.start)


def _idle(trace: Trace, dev: str, lo: int, hi: int):
    """The gaps between busy intervals of ``dev`` in [lo, hi), in order."""
    busy = trace_reduce.union(trace.ops[dev], lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _intersect(a, b):
    """Intersection of two ordered lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if t > s:
            out.append((s, t))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a, b):
    """``a`` less ``b``, both ordered lists of disjoint intervals."""
    out, j = [], 0
    for s, t in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < t:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if t > s:
            out.append((s, t))
    return out


def idle_shares(trace: Trace, lo: int, hi: int, classes) -> List[float]:
    """-> for each set of span names in ``classes``, the share of the
    first device's idle time in [lo, hi) that spans of that set cover;
    an instant two sets cover counts for the earlier, so the shares sum
    to at most 1. Empty where no device ran anything."""
    devs = trace.devices()
    if not devs:
        return []
    left = _idle(trace, devs[0], lo, hi)
    idle = sum(t - s for s, t in left)
    shares = []
    for names in classes:
        cover = trace_reduce.union([e for n in names
                                    for e in spans(trace, n)], lo, hi)
        hit = _intersect(left, cover)
        shares.append(sum(t - s for s, t in hit) / idle if idle else 0.0)
        left = _subtract(left, hit)
    return shares


def wait_offsets_ns(trace: Trace, wait: str = "wait_camera",
                    step: str = r"^jit__step\b") -> List[int]:
    """For each ``wait`` span that began before the device execution it
    waited on had ended (a wait that blocked), its end less that
    execution's end. The last n of the ``wait`` spans pair in order with
    the last n executions of ``step`` on the first device (the executions
    before them ran inside the warm-up). Small and non-negative where
    the spans sit on the trace's clock; a wait that began after its step
    ended returned at once and says nothing of the clocks."""
    devs = trace.devices()
    waits = spans(trace, wait)
    if not devs or not waits:
        return []
    ends = sorted(m.end for m in trace.modules.get(devs[0], [])
                  if re.search(step, m.name))[-len(waits):]
    return [w.end - e for w, e in zip(waits[-len(ends):], ends)
            if w.start < e]


def lead_ns(trace: Trace, dispatch: str = "dispatch_server",
            step: str = r"^jit__server\b",
            reach_ns: int = 5_000_000) -> Optional[int]:
    """How far, at the median, the first device's executions of ``step``
    start before the ``dispatch`` spans that issued them (each paired
    with the first execution that starts no more than ``reach_ns``
    before it): a lower bound on the device clock's lead over the
    host's, as no program starts before its dispatch (a dispatch takes
    time of its own, so the lead may be larger; ``wait_offsets_ns``
    bounds it from above). 0 where they start after it; None where
    nothing pairs."""
    devs = trace.devices()
    if not devs:
        return None
    starts = sorted(m.start for m in trace.modules.get(devs[0], [])
                    if re.search(step, m.name))
    offsets = []
    for d in spans(trace, dispatch):
        i = bisect.bisect_left(starts, d.start - reach_ns)
        if i < len(starts):
            offsets.append(starts[i] - d.start)
    if not offsets:
        return None
    return max(0, -statistics.median_low(offsets))


def shifted(trace: Trace, ns: int) -> Trace:
    """The trace with its program spans moved ``ns`` earlier."""
    host = {line: ([Event(e.start - ns, e.end - ns, e.name) for e in evs]
                   if line.startswith(PROGRAM) else evs)
            for line, evs in trace.host.items()}
    return Trace(trace.ops, trace.modules, host)


def _mean_ms(evs: List[Event], n: int) -> float:
    return sum(e.end - e.start for e in evs) / n * 1e-6


def numbers(trace: Trace, lo: int, hi: int) -> Optional[dict]:
    """The engine loop's per-layer numbers over the traced window, None
    where the trace holds no loop span (a program that records none)."""
    waits = spans(trace, "wait_camera")
    if not waits:
        return None
    calls = spans(trace, "run")
    out = {
        "ingest_ms": _mean_ms(spans(trace, "ingest"), len(waits)),
        "host_block_ms": _mean_ms(waits + spans(trace, "fetch"),
                                  len(waits)),
        "in_call_warm_ms": (_mean_ms(spans(trace, "warm"), len(calls))
                            if calls else None),
    }
    shares = idle_shares(trace, lo, hi, (HOST_BUSY, HOST_BLOCKED))
    if shares:
        out["idle_host_busy_share"] = 100.0 * shares[0]
        out["idle_host_blocked_share"] = 100.0 * shares[1]
        out["idle_no_span_share"] = 100.0 * (1.0 - sum(shares))
    return out
