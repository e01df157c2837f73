"""The trace reduction, on hand-made events and on a small trace recorded
on a v5e chip (``testdata/det_96x160.xplane.pb.gz``, written by
``record_test_trace.py``)."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

RECORDED = Path(__file__).parent / "testdata" / "det_96x160.xplane.pb.gz"
E = tr.Event


def _trace():
    ops = {"/device:TPU:0": [E(10, 20, "fusion.1"), E(15, 30, "fusion.2"),
                             E(50, 60, "mbcodec_kernel"),
                             E(90, 200, "copy.3")],
           "/device:TPU:1": [E(0, 40, "fusion.1")]}
    modules = {"/device:TPU:0": [E(10, 30, "jit__step(1)"),
                                 E(50, 60, "jit__step(1)"),
                                 E(90, 200, "jit__server(2)")]}
    host = {"python": [E(0, 100, "bench.window"), E(32, 48, "scoring"),
                       E(1, 99, "bench.call")]}
    return tr.Trace(ops, modules, host)


def test_union_and_busy():
    t = _trace()
    assert tr.union(t.ops["/device:TPU:0"], 0, 100) == [(10, 30), (50, 60),
                                                        (90, 100)]
    # device 0 is busy 20 + 10 + 10 of [0, 100), device 1 is busy 40
    assert tr.busy_ns(t, 0, 100) == pytest.approx((40 + 40) / 2)
    assert t.span("bench.window") == (0, 100)


def test_programs_ops_and_gaps():
    t = _trace()
    assert tr.module_ns(t, r"^jit__step\b", 0, 100) == (30.0, 2)
    assert tr.module_ns(t, r"^jit__server\b", 0, 100) == (10.0, 1)
    assert tr.op_ns(t, "mbcodec", 0, 100) == (10.0, 1)
    top = dict(tr.top_ops(t, 0, 100))
    assert list(top)[:2] == ["/fusion.1", "jit__step/fusion.2"]
    assert top["jit__server/copy.3"] == pytest.approx(10e-9)
    gaps = tr.idle_gaps(t, 0, 100)
    assert gaps[0] == ("bench.call", pytest.approx(30e-9))
    assert gaps[1] == ("scoring", pytest.approx(20e-9))
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_window_and_gap_labels_without_host_events():
    t = _trace()
    t.host = {}
    assert tr.window(t) == (10, 200)  # first to last program
    gaps = tr.idle_gaps(t, 10, 100)
    assert gaps[0] == ("before jit__server", pytest.approx(30e-9))


def test_recorded_chip_trace():
    t = tr.load(str(RECORDED))
    lo, hi = tr.window(t)
    assert hi > lo
    assert t.devices() == ["/device:TPU:0"]
    busy = tr.busy_ns(t, lo, hi)
    assert 0 < busy <= hi - lo
    cam_ns, cam_n = tr.module_ns(t, r"^jit__step\b", lo, hi)
    srv_ns, srv_n = tr.module_ns(t, r"^jit__server\b", lo, hi)
    assert cam_n >= 1 and srv_n >= 1 and cam_ns > 0 and srv_ns > 0
    kern_ns, kern_n = tr.op_ns(t, r"mbcodec|_chunk_scores_kernel", lo, hi)
    assert kern_n >= 1 and 0 < kern_ns <= cam_ns
    top = tr.top_ops(t, lo, hi)
    assert len(top) == 10
    assert top[0][0].startswith("jit__server/")
    assert sum(s for _, s in top) <= busy * 1e-9
    gaps = tr.idle_gaps(t, lo, hi)
    assert all(isinstance(n, str) and s > 0 for n, s in gaps)
    assert sum(s for _, s in gaps) <= (hi - lo - busy) * 1e-9 + 1e-12
