"""The traffic generator: one driver for every traffic mix, read from data.

A mix (``traffic/<name>.json``) fixes:

``loop``
    ``"closed"``: the cameras' chunks are all waiting, and one engine
    call serves ``intervals_per_s`` chunk intervals per second of the
    window back to back: a fixed amount of work for a given ``--seconds``,
    whatever the program's speed.
    ``"open"``: the chunk of every camera becomes due each
    ``period_chunks`` chunk durations (``chunk_size / fps`` seconds, the
    capture time of a chunk), and each due chunk goes through its own
    engine call as soon as the previous call has returned. A call that
    starts late still counts from its due time.
``pool_chunks``
    distinct chunks per stream, served in turn.
``trace_seconds``
    the length of the window a ``--trace 1`` run records.

A driver returns a :class:`Window`: what the engine returned for every
call, the host-clock times the end-to-end metrics are taken from, and
the program's own ``FleetTiming`` of every call.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import List


@dataclasses.dataclass
class Call:
    first: int            # pool position of the call's first chunk
    n_chunks: int
    result: object        # FleetResult
    due_s: float          # host clock, seconds since the window opened
    start_s: float
    end_s: float


@dataclasses.dataclass
class Window:
    calls: List[Call]
    seconds: float        # wall time of the window, host clock
    chunk_s: float        # video seconds per chunk

    @property
    def chunk_intervals(self) -> int:
        return sum(c.n_chunks for c in self.calls)

    def host_s(self) -> List[float]:
        return [h for c in self.calls for h in c.result.timing.host_s]


def _call(system, first, n_chunks):
    video = system.video(n_chunks, first)
    return system.engine.run(video, refs=system.refs(video))


def warm_up(system, traffic) -> None:
    """Compile and warm every program the window drives, on the window's
    own chunk shape: a closed-loop call over a full pipeline, or two
    open-loop calls."""
    if traffic["loop"] == "closed":
        _call(system, 0, system.engine.depth + 1)
    else:
        _call(system, 0, 1)
        _call(system, 1, 1)


def closed_loop(system, traffic, seconds: float) -> Window:
    cfg = system.cfg
    n = max(system.engine.depth + 1,
            int(round(seconds * traffic["intervals_per_s"])))
    t0 = time.perf_counter()
    res = _call(system, 0, n)
    t1 = time.perf_counter()
    chunk_s = cfg["chunk_size"] / cfg["fps"]
    return Window([Call(0, n, res, 0.0, 0.0, t1 - t0)], t1 - t0, chunk_s)


def open_loop(system, traffic, seconds: float) -> Window:
    cfg = system.cfg
    chunk_s = cfg["chunk_size"] / cfg["fps"]
    period = traffic["period_chunks"] * chunk_s
    n = max(1, int(math.floor(seconds / period)))
    calls = []
    t0 = time.perf_counter()
    for k in range(n):
        due = (k + 1) * period
        wait = due - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        start = time.perf_counter() - t0
        res = _call(system, k, 1)
        calls.append(Call(k, 1, res, due, start, time.perf_counter() - t0))
    return Window(calls, max(n * period, calls[-1].end_s), chunk_s)


DRIVERS = {"closed": closed_loop, "open": open_loop}


def drive(system, traffic, seconds: float) -> Window:
    try:
        driver = DRIVERS[traffic["loop"]]
    except KeyError:
        raise ValueError(f"unknown loop {traffic['loop']!r}; known: "
                         f"{sorted(DRIVERS)}") from None
    return driver(system, traffic, seconds)
