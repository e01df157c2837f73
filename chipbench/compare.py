"""The comparison that decides ``correct``.

Every answer the window's engine calls returned is compared with the plain
reference (``reference.py``) of the chunks it covers, once the window has
closed:

- with ``detail="chunks"`` an answer is one stream-chunk's ``ChunkResult``
  (wire bytes of its 10 frames, accuracy against D(H));
- with ``detail="windowed"`` an answer is one ``WindowStats`` of the
  aggregate (wire bytes and accuracy summed over every stream-chunk of its
  chunk intervals).

Four numbers are computed; a cell compares those its limits name
(``cells/<cell>.json``, with the readings each limit was set from):

``bytes_gap``       the largest relative gap of an answer's wire bytes;
``bytes_gap_mean``  the mean of that gap over the window's answers;
``acc_gap``         the largest gap of an answer's accuracy, per
                    stream-chunk;
``acc_gap_mean``    the mean of that gap over the window's answers.

An answer that is missing (fewer stream-chunks served than were due)
counts as failed.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple


from chipbench import reference

Unit = Tuple[int, int]  # (stream, pool chunk)


@dataclasses.dataclass
class Answer:
    units: List[Unit]
    nbytes: float
    acc_sum: float


def answers(window, pool_size: int, n_streams: int):
    """-> (answers, stream-chunks attempted, stream-chunks failed)."""
    out: List[Answer] = []
    attempted = served = 0
    for call in window.calls:
        res = call.result
        attempted += n_streams * call.n_chunks
        pos = lambda ci: (call.first + ci) % pool_size
        if res.aggregate is not None:
            agg = res.aggregate
            served += agg.n
            cis = list(agg.cis)
            for w in agg.windows:
                units = [(s, pos(ci)) for ci in cis
                         if ci // agg.window == w.wi for s in range(n_streams)]
                if len(units) != w.n:
                    raise AssertionError(f"window {w.wi} counts {w.n} "
                                         f"stream-chunks, expected "
                                         f"{len(units)}")
                out.append(Answer(units, w.sum_bytes, w.sum_acc))
        else:
            for s, stream in enumerate(res.streams):
                for ci, c in enumerate(stream.chunks):
                    served += 1
                    out.append(Answer([(s, pos(ci))], c.bytes, c.accuracy))
    return out, attempted, attempted - served


def reference_units(system, units, precision="highest") -> Dict[Unit, tuple]:
    """(bytes, accuracy) of the reference for every (stream, pool chunk),
    computed one stream-chunk at a time."""
    got = {}
    for s, p in sorted(set(units)):
        got[(s, p)] = reference.stream_chunk(
            system.acc_params, system.dnn_params, system.pool[p, s],
            system.cfg, precision)
    return got


def gaps(ans: List[Answer], ref: Dict[Unit, tuple]) -> Dict[str, float]:
    b, a = [], []
    for x in ans:
        rb = sum(ref[u][0] for u in x.units)
        ra = sum(ref[u][1] for u in x.units)
        b.append(abs(x.nbytes - rb) / rb)
        a.append(abs(x.acc_sum - ra) / len(x.units))
    return {"bytes_gap": max(b), "bytes_gap_mean": sum(b) / len(b),
            "acc_gap": max(a), "acc_gap_mean": sum(a) / len(a)}


def judge(numbers: Dict[str, float], limits: Dict[str, float],
          failed: int) -> bool:
    return failed == 0 and all(numbers[k] <= limits[k] for k in limits)


def report(numbers, limits, failed) -> List[str]:
    """One line per compared number, with its limit."""
    lines = [f"{k} {numbers[k]!r} limit {limits[k]!r}" for k in limits]
    return lines + [f"failed {failed} limit 0"]
