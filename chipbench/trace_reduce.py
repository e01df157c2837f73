"""From a profiler trace to per-layer numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes (or the
same bytes gzipped) with nothing but JAX. Device planes are the
``/device:TPU:<i>`` planes; on each, the ``XLA Ops`` line holds one event
per device operation and the ``XLA Modules`` line one event per program
execution (named after the jitted function, e.g. ``jit__step(3)``). Host
events are the ``/host:CPU`` plane's, on the same clock; the benchmark
records none (its traces run with the host tracer off), but a trace
recorded with it on names idle gaps by what the host was doing.

The reductions, all clipped to a window ``[lo, hi)`` in trace
nanoseconds:

- ``busy_ns``: the union of one device's operation intervals; averaged
  over the devices that ran anything;
- ``module_ns`` / ``op_ns``: summed device time of the programs or the
  operations whose name matches a pattern, over all devices (an
  operation's name is the instruction's own, ``fusion.5`` or
  ``vmap_jit_mbcodec_chunk_scores_pallas__.1``);
- ``top_ops``: the operations that took most device time, named
  ``<program>/<instruction>``;
- ``idle_gaps``: the longest gaps between busy intervals on the first
  device, each named by the innermost host event at its middle, or by
  the program the device ran next;
- ``window``: the traced window, a host span where there is one, else
  the first to the last program execution.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    start: int  # ns
    end: int
    name: str   # a device operation's name is its whole HLO instruction

    @property
    def op(self) -> str:
        """The instruction's own name (``fusion.5`` of ``%fusion.5 = ...``)."""
        return self.name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]      # device plane -> operations
    modules: Dict[str, List[Event]]  # device plane -> program executions
    host: Dict[str, List[Event]]     # host line -> events

    def devices(self) -> List[str]:
        return sorted(d for d, evs in self.ops.items() if evs)

    def span(self, name: str) -> Optional[Tuple[int, int]]:
        """First and last edge of the host events named ``name``."""
        evs = [e for line in self.host.values() for e in line
               if e.name == name]
        if not evs:
            return None
        return min(e.start for e in evs), max(e.end for e in evs)


def _events(line) -> List[Event]:
    return [Event(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def from_profile(pd) -> Trace:
    ops, modules, host = {}, {}, {}
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host[line.name] = _events(line)
    return Trace(ops, modules, host)


def _profile(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def load(path: str) -> Trace:
    return from_profile(_profile(path))


def _clip(evs, lo, hi):
    for e in evs:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            yield s, t, e


def union(evs: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Merged busy intervals of ``evs`` inside [lo, hi)."""
    merged: List[List[int]] = []
    for s, t, _ in sorted(_clip(evs, lo, hi)):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_ns(trace: Trace, lo: int, hi: int) -> float:
    """Device-busy nanoseconds in [lo, hi), averaged over the devices
    that ran an operation (0.0 when none did)."""
    devs = trace.devices()
    if not devs:
        return 0.0
    return sum(sum(t - s for s, t in union(trace.ops[d], lo, hi))
               for d in devs) / len(devs)


def _matching(evs, pattern, lo, hi, key):
    rx = re.compile(pattern)
    return [(s, t) for s, t, e in _clip(evs, lo, hi) if rx.search(key(e))]


def module_ns(trace: Trace, pattern: str, lo: int, hi: int):
    """-> (device ns, executions) of the programs whose name matches."""
    hits = [h for evs in trace.modules.values()
            for h in _matching(evs, pattern, lo, hi, lambda e: e.name)]
    return float(sum(t - s for s, t in hits)), len(hits)


def op_ns(trace: Trace, pattern: str, lo: int, hi: int):
    """-> (device ns, events) of the operations whose own name matches."""
    hits = [h for evs in trace.ops.values()
            for h in _matching(evs, pattern, lo, hi, lambda e: e.op)]
    return float(sum(t - s for s, t in hits)), len(hits)


def _program(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def top_ops(trace: Trace, lo: int, hi: int, k: int = 10):
    """-> [(``<program>/<instruction>``, device seconds)], most time
    first; an operation's program is the execution that encloses it."""
    tot: Dict[str, float] = {}
    for dev, evs in trace.ops.items():
        mods = sorted(trace.modules.get(dev, []), key=lambda e: e.start)
        starts = [m.start for m in mods]
        for s, t, e in _clip(evs, lo, hi):
            i = bisect.bisect_right(starts, e.start) - 1
            prog = (_program(mods[i].name)
                    if i >= 0 and e.end <= mods[i].end else "")
            key = f"{prog}/{e.op}"
            tot[key] = tot.get(key, 0.0) + (t - s) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:k]


def host_at(trace: Trace, t: int) -> str:
    """The innermost (shortest) host event that covers instant t."""
    best = None
    for evs in trace.host.values():
        for e in evs:
            if e.start <= t < e.end and (best is None or e.end - e.start
                                         < best.end - best.start):
                best = e
    return best.name if best is not None else "no host event"


def _gap_label(trace: Trace, dev: str, s: int, t: int) -> str:
    """The host event at the gap's middle or, where the trace holds no
    host event there, the program the device ran next."""
    host = host_at(trace, (s + t) // 2)
    if host != "no host event":
        return host
    nxt = [m for m in trace.modules.get(dev, []) if m.start >= t]
    return ("before " + _program(min(nxt, key=lambda m: m.start).name)
            if nxt else "after the last program")


def idle_gaps(trace: Trace, lo: int, hi: int, k: int = 10):
    """-> [(what the host was doing, idle seconds)] for the k longest
    gaps between busy intervals on the first device."""
    devs = trace.devices()
    if not devs:
        return []
    busy = union(trace.ops[devs[0]], lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [(_gap_label(trace, devs[0], s, t), (t - s) * 1e-9)
            for s, t in gaps[:k]]


def window(trace: Trace, name: str = "bench.window"):
    """[lo, hi) of the traced window: the host span ``name`` where the
    trace holds it, else the first to the last program execution on the
    devices."""
    span = trace.span(name)
    if span is not None:
        return span
    mods = [m for evs in trace.modules.values() for m in evs]
    if not mods:
        return None
    return min(m.start for m in mods), max(m.end for m in mods)


def summary(path: str, k: int = 12) -> str:
    """Planes, lines, event counts and the most frequent event names of a
    trace: what to look at before keying a reduction on a name."""
    out = []
    for plane in _profile(path).planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = _events(line)
            tot: Dict[str, List[float]] = {}
            for e in evs:
                c = tot.setdefault(_program(e.op), [0, 0.0])
                c[0] += 1
                c[1] += (e.end - e.start) * 1e-6
            top = sorted(tot.items(), key=lambda kv: -kv[1][1])[:k]
            out.append(f"  line {line.name!r}: {len(evs)} events; "
                       + "; ".join(f"{n} x{c} {ms:.3f} ms"
                                   for n, (c, ms) in top))
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(summary(sys.argv[1]))
