"""The program's spans on the device trace's clock (``program_spans.py``)
on hand-made events and on recorded chip traces, and a CPU rehearsal of
``spans_run.py``."""
import time
import warnings
from pathlib import Path

import pytest

from chipbench import program_spans as ps
from chipbench import trace_reduce as tr

TESTDATA = Path(__file__).parent / "testdata"
E = tr.Event
DEV = "/device:TPU:0"


def _payload(anchor_wall, anchor_mono, events):
    return {"host": 0, "anchor_wall": anchor_wall, "anchor_mono": anchor_mono,
            "events": [{"name": n, "stage": lane, "ts": ts, "dur": dur,
                        "phase": ph, "args": None}
                       for n, lane, ts, dur, ph in events]}


def test_clock_mapping():
    """A span at monotonic ts lies at (ts - anchor_mono + anchor_wall) s
    of wall time, less the profile's start, in trace ns; instants stay
    out; each lane becomes one ``program/<lane>`` line."""
    start_ns = 1_792_177_931_859_468_182
    anchor_wall = start_ns * 1e-9 + 0.25  # the tracer starts 250 ms later
    payload = _payload(anchor_wall, 100.0, [
        ("ingest", "ingest", 100.5, 0.002, "X"),
        ("wait_camera", "camera", 101.0, 0.125, "X"),
        ("recompile", "warmup", 101.0, 0.0, "i")])
    lines = ps.program_lines(payload, start_ns)
    assert sorted(lines) == ["program/camera", "program/ingest"]
    (ing,) = lines["program/ingest"]
    (wait,) = lines["program/camera"]
    # anchor_wall holds the start to float precision (~0.2 us)
    assert ing.start == pytest.approx(750_000_000, abs=1_000)
    assert ing.end - ing.start == 2_000_000
    assert wait.start - ing.start == 500_000_000
    assert wait.end - wait.start == 125_000_000
    assert ing.name == "ingest" and wait.name == "wait_camera"


def _trace():
    """Device 0 busy [10, 30), [50, 60), [90, 100) of [0, 100); the loop's
    spans around the idle time."""
    ops = {DEV: [E(10, 30, "fusion.1"), E(50, 60, "copy.2"),
                 E(90, 100, "fusion.3")]}
    modules = {DEV: [E(10, 30, "jit__step(1)"), E(50, 60, "jit__server(2)"),
                     E(90, 100, "jit__step(1)")]}
    host = {"program/events": [E(0, 100, "run")],
            "program/ingest": [E(0, 4, "ingest")],
            "program/dispatch": [E(4, 8, "dispatch_camera"),
                                 E(44, 48, "dispatch_server")],
            "program/scoring": [E(30, 42, "scoring")],
            "program/camera": [E(8, 31, "wait_camera")],
            "program/fetch": [E(62, 80, "fetch")],
            "program/warmup": [E(80, 88, "warm")]}
    return tr.Trace(ops, modules, host)


def test_idle_shares_and_gap_labels():
    t = _trace()
    # idle: [0, 10) [30, 50) [60, 90) = 60 ns
    # busy spans cover [0, 8) [30, 42) [44, 48) of it: 24 ns
    # blocked spans cover [8, 10) and [30, 31) -- already busy from 30 --
    # and [62, 88): 2 + 26 = 28 ns; no span over [42, 44) [48, 50)
    # [60, 62) [88, 90): 8 ns
    busy, blocked = ps.idle_shares(t, 0, 100, (ps.HOST_BUSY,
                                               ps.HOST_BLOCKED))
    assert busy == pytest.approx(24 / 60)
    assert blocked == pytest.approx(28 / 60)
    n = ps.numbers(t, 0, 100)
    assert n["idle_host_busy_share"] == pytest.approx(100 * 24 / 60)
    assert n["idle_host_blocked_share"] == pytest.approx(100 * 28 / 60)
    assert n["idle_no_span_share"] == pytest.approx(100 * 8 / 60)
    assert n["ingest_ms"] == pytest.approx(4e-6)
    assert n["host_block_ms"] == pytest.approx((23 + 18) * 1e-6)
    assert n["in_call_warm_ms"] == pytest.approx(8e-6)
    # each gap is named by the innermost span at its middle: never
    # "before jit__..." while the program's spans cover the window
    gaps = dict(tr.idle_gaps(t, 0, 100))
    assert list(gaps) == ["fetch", "scoring", "dispatch_camera"]
    assert gaps["fetch"] == pytest.approx(30e-9)


def test_wait_offsets_pair_the_last_camera_steps():
    """Waits pair in order with the last camera steps (earlier steps ran
    in the warm-up), each offset its end less the step's end; a wait that
    began after its step had ended did not block and is left out."""
    t = _trace()
    t.host = {"program/camera": [E(92, 101, "wait_camera")]}
    assert ps.wait_offsets_ns(t) == [1]
    t.host["program/camera"].insert(0, E(8, 33, "wait_camera"))
    assert ps.wait_offsets_ns(t) == [3, 1]
    t.host["program/camera"][0] = E(31, 33, "wait_camera")
    assert ps.wait_offsets_ns(t) == [1]
    t.host = {}
    assert ps.wait_offsets_ns(t) == []


def test_lead_pairs_dispatches_with_their_server_steps():
    """The lead is the median of how far each server step starts before
    the dispatch that issued it; a step that starts after its dispatch
    means no lead; moving the spans by the lead moves only them."""
    t = _trace()  # dispatch_server [44, 48), jit__server from 50
    assert ps.lead_ns(t) == 0
    t.modules[DEV] += [E(140, 150, "jit__server(2)"),
                       E(241, 250, "jit__server(2)")]
    t.host["program/dispatch"] = [E(51, 53, "dispatch_server"),
                                  E(143, 145, "dispatch_server"),
                                  E(244, 246, "dispatch_server")]
    assert ps.lead_ns(t, reach_ns=5) == 3  # offsets -1, -3, -3
    moved = ps.shifted(t, 3)
    assert [e.start for e in moved.host["program/dispatch"]] == [48, 140,
                                                                  241]
    assert moved.modules == t.modules and moved.ops == t.ops
    assert ps.lead_ns(moved, reach_ns=5) == 0
    t.host = {}
    assert ps.lead_ns(t) is None


def test_shares_without_loop_spans():
    t = _trace()
    t.host = {"program/scoring": t.host["program/scoring"]}
    assert ps.numbers(t, 0, 100) is None  # a program without loop spans
    t.ops = {}
    assert ps.idle_shares(t, 0, 100, (ps.HOST_BUSY,)) == []


def test_profile_start_time_of_recorded_trace():
    pd = tr._profile(str(TESTDATA / "det_96x160.xplane.pb.gz"))
    assert ps.profile_start_ns(pd) == 1792177931859468182


def test_recorded_trace_with_program_spans():
    """Recorded on a v5e: ``det720_x6_sat`` at 96x160 with two pool
    chunks, a one-second traced window and ``spans_run.tracing`` around
    it. The program's spans land inside the traced window, split its idle
    time, name every idle gap, the waits that blocked end just after
    their camera steps, and the device leads the host by under 2 ms."""
    prefix = str(TESTDATA / "det_96x160_spans")
    t = ps.load(prefix + ".xplane.pb.gz", prefix + ".program.json")
    lo, hi = tr.window(t)
    assert t.devices() == [DEV]
    n = ps.numbers(t, lo, hi)
    assert n["ingest_ms"] > 0 and n["host_block_ms"] > 0
    assert n["in_call_warm_ms"] > 0
    busy, blocked = n["idle_host_busy_share"], n["idle_host_blocked_share"]
    assert busy > 50 and blocked > 0 and busy + blocked <= 100
    assert n["idle_no_span_share"] == pytest.approx(100 - busy - blocked)
    labels = [name for name, _ in tr.idle_gaps(t, lo, hi)]
    assert len(labels) == 10 and labels[0] == "scoring"
    assert set(labels) <= set(ps.HOST_BUSY + ps.HOST_BLOCKED)
    run = ps.spans(t, "run")
    assert len(run) == 1 and lo < run[0].end and run[0].start < hi
    offsets = ps.wait_offsets_ns(t)
    assert len(offsets) >= 2 and all(0 < o < 3_000_000 for o in offsets)
    assert 0 < ps.lead_ns(t) < 2_000_000


def test_attribution_of_recorded_trace(tmp_path):
    """``spans_run.attribution`` on the recorded trace, laid out as a
    run leaves it: the numbers, the split at each bound on the device's
    lead (low <= high), named gaps and the warm-ups' compiles."""
    from types import SimpleNamespace

    from chipbench import spans_run

    xplane = tmp_path / "host.xplane.pb.gz"
    xplane.write_bytes((TESTDATA / "det_96x160_spans.xplane.pb.gz")
                       .read_bytes())
    (tmp_path / spans_run.SPANS).write_bytes(
        (TESTDATA / "det_96x160_spans.program.json").read_bytes())
    warm = SimpleNamespace(name="warm", args={"compiled": []})
    out = spans_run.attribution(xplane, SimpleNamespace(events=[warm]))
    low, high = out["lead_corrected"]["low"], out["lead_corrected"]["high"]
    assert 0 < low["lead_ms"] <= high["lead_ms"] < 3
    for split in (out, low, high):
        assert split["idle_host_busy_share"] > 50
        assert (split["idle_host_busy_share"]
                + split["idle_host_blocked_share"]) <= 100
    assert len(out["idle_gaps"]) == 10 and out["ingest_ms"] > 0
    assert out["wait_camera_offset_ms"]["n"] == 2
    assert out["warm_compiled"] == [[]]


@pytest.mark.parametrize("name", ["det720_x6_sat", "seg720_x6_sat"])
def test_spans_run_rehearsal(name):
    """``spans_run.tracing`` at 48x64 on the CPU, profiler off: the window's
    engine call leaves one span of each loop boundary per interval, a
    warm-up inside the call that compiles nothing (the window's clip is
    longer than the warm-up call's), and the tracer is gone after it."""
    from chipbench import harness, load, spans_run
    from repro.obs import trace as obs_trace

    cell = harness.load_cell(name)
    cell["cfg"].update(height=48, width=64)
    cell["traffic_mix"]["pool_chunks"] = 2
    drive = load.drive
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # kernel fallback
        with spans_run.tracing() as tracer:
            result = harness.execute(
                cell, 2 ** 33 + 29, 1.2, False, time.perf_counter(),
                require_chip=False, log=lambda *a, **k: None)
    assert result["correct"], result["checks"]
    assert load.drive is drive and obs_trace.get_tracer() is None
    names = {}
    for e in tracer.events:
        names.setdefault(e.name, []).append(e)
    n = 4  # 1.2 s at 3.5 intervals a second
    for name_ in ps.HOST_BUSY + ("wait_camera", "fetch"):
        assert [e.args["ci"] for e in names[name_]] == list(range(n))
    (call,) = names["run"]
    assert call.args["intervals"] == n
    (warm,) = names["warm"]
    assert warm.args["compiled"] == []
