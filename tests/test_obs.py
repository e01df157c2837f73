"""Telemetry-plane contracts (``repro.obs``): the observability PR's
tentpole guarantees.

1. **Never perturb the data path** — a ``serve_loop`` schedule run with
   the plane on reports a bit-identical data-path digest (accuracy,
   bytes, delays under ``sim_encode_s``) to the same schedule with the
   plane off, while every serving interval gets a camera span and the
   ``stage_seconds_total`` counters reconcile with ``FleetTiming``.
2. **Span bookkeeping** — nesting/ordering of context-manager spans,
   monotone timestamps, caller-measured ``complete()`` passthrough.
3. **Cross-host merge** — ``merge_host_traces`` aligns per-host
   monotonic clocks onto one wall timeline, lays out one process lane
   per host and one thread lane per stage, and rejects duplicate host
   lanes; histogram merge is exact, associative, and commutative
   (property-tested) so the fleet view is gather-order independent.
4. **CompileCounter promotion** — the test-suite shim re-exports the
   production class, and ``publish()`` surfaces cache growth to the
   ambient registry/tracer.
"""
import json

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # dev-only dep; fall back to a fixed sample grid
    from _hypothesis_compat import given, settings, st

from repro import obs
from repro.obs.metrics import DEFAULT_BUCKETS, Histogram, MetricsRegistry
from repro.obs.trace import (STAGES, Tracer, merge_host_traces,
                             stage_summary)


@pytest.fixture(autouse=True)
def _plane_off():
    """Every test starts and ends with the ambient plane uninstalled —
    a leaked singleton would silently instrument unrelated suites."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------------
# tracer: spans, ordering, clocks
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering():
    tr = Tracer(host=3)
    with tr.span("outer", "camera", ci=0):
        with tr.span("inner", "server"):
            pass
    # completes append at block *exit*: inner closes first
    assert [e.name for e in tr.events] == ["inner", "outer"]
    inner, outer = tr.events
    assert outer.ts <= inner.ts  # outer opened first
    assert inner.ts + inner.dur <= outer.ts + outer.dur + 1e-9
    assert outer.args == {"ci": 0}
    assert inner.phase == outer.phase == "X"


def test_complete_records_caller_measured_times():
    tr = Tracer()
    tr.complete("camera", "camera", 1.5, 0.25, ci=7)
    (e,) = tr.events
    assert (e.ts, e.dur, e.stage, e.args) == (1.5, 0.25, "camera",
                                              {"ci": 7})


@settings(max_examples=10)
@given(st.integers(min_value=1, max_value=50))
def test_clock_monotonicity(n):
    """Sequential records carry non-decreasing timestamps, and each
    span's window never starts before the previous one ended."""
    tr = Tracer()
    for i in range(n):
        if i % 3 == 2:
            tr.instant("tick", "events", i=i)
        else:
            with tr.span("work", "camera"):
                pass
    ts = [e.ts for e in tr.events]
    assert ts == sorted(ts)
    spans = [e for e in tr.events if e.phase == "X"]
    for a, b in zip(spans, spans[1:]):
        assert a.ts + a.dur <= b.ts + 1e-9


def test_ambient_span_is_noop_when_disabled():
    # must not raise, must not create a tracer
    with obs.trace.span("x", "camera"):
        pass
    obs.trace.instant("y")
    assert obs.get_tracer() is None
    tr, _ = obs.enable(host=0)
    with obs.trace.span("x", "camera"):
        pass
    obs.trace.instant("y")
    assert [e.name for e in tr.events] == ["x", "y"]


# ---------------------------------------------------------------------------
# cross-host merge + summary
# ---------------------------------------------------------------------------

def _payload(host, anchor_wall, anchor_mono, events):
    return {"host": host, "anchor_wall": anchor_wall,
            "anchor_mono": anchor_mono,
            "events": [{"name": n, "stage": s, "ts": ts, "dur": dur,
                        "phase": "X" if dur else "i", "args": None}
                       for (n, s, ts, dur) in events]}


def test_merge_host_traces_lanes_and_alignment():
    # host 0 booted at wall=1000 with mono clock at 50; host 1 at
    # wall=1000.5 with a *different* mono origin. A span at the same
    # wall instant on both hosts must land at the same merged ts.
    p0 = _payload(0, 1000.0, 50.0, [("camera", "camera", 51.0, 0.5)])
    p1 = _payload(1, 1000.5, 7.0, [("camera", "camera", 7.5, 0.5)])
    trace = merge_host_traces([p1, p0])  # order must not matter
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in spans} == {0, 1}
    by_host = {e["pid"]: e for e in spans}
    # host0's span: wall 1001.0; host1's span: wall 1001.0 too
    assert by_host[0]["ts"] == pytest.approx(by_host[1]["ts"])
    assert min(e["ts"] for e in spans) == pytest.approx(0.0)  # origin
    assert by_host[0]["dur"] == pytest.approx(0.5e6)  # µs
    names = [e for e in trace["traceEvents"] if e["ph"] == "M"
             and e["name"] == "process_name"]
    assert sorted(m["args"]["name"] for m in names) == ["host0", "host1"]
    # stage lanes use the STAGES ordering as tid
    assert all(e["tid"] == STAGES.index("camera") for e in spans)


def test_merge_rejects_duplicate_host_lanes():
    p = _payload(2, 0.0, 0.0, [])
    with pytest.raises(ValueError, match="same host lane"):
        merge_host_traces([p, dict(p)])


def test_stage_summary_stats():
    p = _payload(0, 0.0, 0.0, [("camera", "camera", 0.0, 0.2),
                               ("camera", "camera", 0.3, 0.4),
                               ("tick", "events", 0.1, 0.0)])  # instant
    s = stage_summary([p])
    assert s[0]["camera"]["n"] == 2
    assert s[0]["camera"]["total_s"] == pytest.approx(0.6)
    assert s[0]["camera"]["mean_s"] == pytest.approx(0.3)
    assert s[0]["camera"]["max_s"] == pytest.approx(0.4)
    assert "events" not in s[0]  # instants carry no duration


def test_adopt_merges_peer_and_skips_self():
    tr = Tracer(host=0)
    tr.complete("camera", "camera", 0.0, 0.1)
    tr.adopt(tr.payload())  # own host: skipped
    peer = Tracer(host=1)
    peer.complete("server", "server", 0.0, 0.2)
    tr.adopt(peer.payload())
    trace = tr.chrome_trace()
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert sorted(e["pid"] for e in spans) == [0, 1]


# ---------------------------------------------------------------------------
# metrics: registry semantics + exporters
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_label_independence():
    reg = MetricsRegistry()
    c1 = reg.counter("x", stage="camera")
    assert reg.counter("x", stage="camera") is c1
    assert reg.counter("x", stage="server") is not c1
    assert reg.get("x", stage="camera") is c1
    assert reg.get("never_fired") is None
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x", stage="camera")
    with pytest.raises(ValueError, match="cannot decrease"):
        c1.inc(-1.0)


def test_exporters():
    reg = MetricsRegistry(host=5)
    reg.counter("served", stage="camera").inc(3)
    reg.gauge("lanes").set(4)
    reg.histogram("lat", boundaries=(0.1, 1.0)).observe_many(
        [0.05, 0.5, 2.0])
    lines = reg.to_jsonl().splitlines()
    assert len(lines) == 3
    recs = [json.loads(ln) for ln in lines]
    assert all(r["host"] == 5 for r in recs)
    assert [r["name"] for r in recs] == ["lanes", "lat", "served"]  # sorted
    prom = reg.to_prometheus()
    assert 'served_total{stage="camera"} 3' in prom
    assert "lanes 4" in prom
    assert 'lat_bucket{le="0.1"} 1' in prom
    assert 'lat_bucket{le="1"} 2' in prom        # cumulative
    assert 'lat_bucket{le="+Inf"} 3' in prom
    assert "lat_count 3" in prom


@settings(max_examples=10)
@given(st.integers(min_value=0, max_value=2 ** 30))
def test_histogram_merge_associative_commutative(seed):
    """Fixed-bucket merge is exact and gather-order independent: counts
    are bit-identical under commutation and association, and equal to
    one host having observed everything."""
    rng = np.random.default_rng(seed)
    obs_sets = [rng.lognormal(-3, 2, size=rng.integers(0, 40))
                for _ in range(3)]
    hs = []
    for vals in obs_sets:
        h = Histogram("lat")
        h.observe_many(vals)
        hs.append(h)
    a, b, c = hs
    ab, ba = a.merge(b), b.merge(a)
    assert np.array_equal(ab.counts, ba.counts) and ab.count == ba.count
    left, right = ab.merge(c), a.merge(b.merge(c))
    assert np.array_equal(left.counts, right.counts)
    everything = Histogram("lat")
    everything.observe_many(np.concatenate(obs_sets))
    assert np.array_equal(left.counts, everything.counts)
    assert left.count == everything.count == sum(map(len, obs_sets))
    assert left.sum == pytest.approx(everything.sum)


def test_histogram_boundary_mismatch_and_validation():
    with pytest.raises(ValueError, match="ascending"):
        Histogram("bad", boundaries=(1.0, 0.5))
    with pytest.raises(ValueError, match="different boundaries"):
        Histogram("a", boundaries=(1.0,)).merge(
            Histogram("b", boundaries=(1.0, 2.0)))


def test_histogram_observe_paths_agree():
    vals = [1e-5, 0.1, 0.10001, 3.0, 500.0]
    one, many = Histogram("h"), Histogram("h")
    for v in vals:
        one.observe(v)
    many.observe_many(vals)
    assert np.array_equal(one.counts, many.counts)
    assert one.count == many.count == len(vals)
    assert one.quantile(0.5) in DEFAULT_BUCKETS


# ---------------------------------------------------------------------------
# enable/disable plumbing
# ---------------------------------------------------------------------------

def test_enable_from_env(monkeypatch):
    monkeypatch.delenv(obs.ENV_OBS, raising=False)
    assert obs.enable_from_env(host=1) is False
    assert obs.get_tracer() is None
    monkeypatch.setenv(obs.ENV_OBS, "1")
    assert obs.enable_from_env(host=1) is True
    assert obs.get_tracer().host == 1
    assert obs.get_metrics().host == 1
    tr, reg = obs.disable()
    assert tr is not None and reg is not None  # still readable
    assert obs.enabled() is False


def test_compile_counter_shim_is_the_production_class():
    import _compile_counter

    from repro.obs.compile import CompileCounter

    assert _compile_counter.CompileCounter is CompileCounter


def test_compile_counter_publish():
    from repro.obs.compile import CompileCounter

    f = jax.jit(lambda x: x + 1)
    counter = CompileCounter(f=f)
    tr, reg = obs.enable(host=0)
    f(np.float32(1.0))  # first call compiles
    grown = counter.publish(context="warmup")
    assert grown == {"f": 1}
    assert reg.get("jit_cache_size", program="f").value == 1
    assert reg.get("jit_recompiles", program="f").value == 1
    assert [e.name for e in tr.stage_events("warmup")] == ["recompile"]
    f(np.float32(2.0))  # warm dispatch: no growth, publish re-baselined
    assert counter.publish() == {}
    assert reg.get("jit_recompiles", program="f").value == 1
    with pytest.raises(TypeError, match="not a jitted callable"):
        CompileCounter(g=lambda x: x)


# ---------------------------------------------------------------------------
# engine integration: bit-identity + reconciliation + decision instants
# ---------------------------------------------------------------------------

H, W = 48, 64
CS = 5


@pytest.fixture(scope="module")
def engine():
    from repro.core.accmodel import AccModel, accmodel_init
    from repro.core.pipeline import NetworkConfig
    from repro.engine import EngineConfig, MultiStreamEngine
    from repro.vision.dnn import FinalDNN, init_net

    dnn = FinalDNN("detection",
                   init_net("detection", jax.random.PRNGKey(0), width=8))
    am = AccModel(accmodel_init(jax.random.PRNGKey(1), 8))
    return MultiStreamEngine(dnn, am, config=EngineConfig(
        impl="fast", chunk_size=CS, net=NetworkConfig.shared(2.5e6, 3),
        sim_encode_s=0.05))


@pytest.fixture(scope="module")
def fleet():
    from repro.data.video import make_scene

    return np.stack([make_scene("dashcam", seed=70 + i, T=3 * CS, H=H,
                                W=W).frames for i in range(3)])


def _digest(res):
    return [[c.ci, c.accuracy, c.bytes, c.encode_s, c.stream_s,
             c.queue_s] for run in res.streams for c in run.chunks]


def test_serve_loop_bit_identical_with_plane_on(engine, fleet):
    """The acceptance criterion: telemetry on vs off, same schedule
    (with churn), bit-identical data path — and the plane saw every
    interval: one ``wait_camera`` span on the camera lane per
    ``FleetTiming`` entry, stage counters reconcile with the timing
    sums, churn left an instant."""
    from repro.control import ChurnEvent

    events = [ChurnEvent(1, leave=(2,)), ChurnEvent(2, join=(2,))]
    res_off = engine.serve_loop(fleet, events=events)
    tr, reg = obs.enable(host=0)
    res_on = engine.serve_loop(fleet, events=events)
    obs.disable()
    assert _digest(res_on) == _digest(res_off)

    cam_spans = tr.stage_events("camera")
    assert len(cam_spans) == len(res_on.timing.camera_s) == 3
    assert {e.name for e in cam_spans} == {"wait_camera"}
    assert [e.args["ci"] for e in cam_spans] == [0, 1, 2]
    # overlap mode charges the warm-up estimate to the server stage: it
    # reaches the counters, never the timeline
    assert tr.stage_events("server") == []
    assert tr.stage_events("uplink") == []
    # span durations are the host's real wait (in overlap mode the
    # FleetTiming entry is the steady-state accounting value instead);
    # exactness is pinned via the counters below, which carry the same
    # accounting values FleetTiming does
    for stage, series in (("camera", res_on.timing.camera_s),
                          ("server", res_on.timing.server_s),
                          ("host", res_on.timing.host_s)):
        c = reg.get("stage_seconds_total", stage=stage)
        assert c is not None
        assert c.value == pytest.approx(float(np.sum(series)), rel=1e-9)
    churn = [e for e in tr.stage_events("events") if e.name == "churn"]
    assert len(churn) == 2
    assert reg.get("churn_leaves_total").value == 1
    assert reg.get("churn_joins_total").value == 1
    # per-chunk scoring spans + admission counters also landed
    assert len(tr.stage_events("scoring")) == 3
    assert reg.get("admissions_total").value == 3
    assert reg.get("chunks_served_total").value == 3 + 2 + 3
    # and the whole story serializes: Chrome trace + both exporters
    trace = tr.chrome_trace()
    assert {e["pid"] for e in trace["traceEvents"]} == {0}
    assert reg.to_prometheus() and reg.to_jsonl()


#: the engine loop's spans, one of each per chunk interval, and their lanes
INTERVAL_SPANS = {"ingest": "ingest", "dispatch_camera": "dispatch",
                  "wait_camera": "camera", "dispatch_server": "dispatch",
                  "fetch": "fetch", "scoring": "scoring"}


def _serve(engine, fleet, entry):
    if entry == "run":
        return engine.run(fleet)
    return engine.serve_loop(fleet)


@pytest.mark.parametrize("entry", ["run", "serve_loop"])
def test_engine_spans_one_per_interval(engine, fleet, entry):
    """Every layer boundary of the loop leaves one span per interval,
    each carrying the interval id, in the order the host crosses them;
    one ``run`` span covers the call."""
    _serve(engine, fleet, entry)  # warm: the spans below are steady state
    tr = obs.trace.install()
    res = _serve(engine, fleet, entry)
    obs.disable()
    n = len(res.timing.camera_s)
    assert n == 3
    by_name = {}
    for e in tr.events:
        by_name.setdefault(e.name, []).append(e)
    assert set(by_name) == set(INTERVAL_SPANS) | {"run"}
    for name, lane in INTERVAL_SPANS.items():
        evs = by_name[name]
        assert [e.args["ci"] for e in evs] == list(range(n)), name
        assert {e.stage for e in evs} == {lane}
        assert all(e.dur >= 0 for e in evs)
    for ci in range(n):
        first = {name: by_name[name][ci].ts for name in INTERVAL_SPANS}
        assert first["ingest"] <= first["dispatch_camera"] \
            <= first["wait_camera"] <= first["dispatch_server"]
    # serve_loop pads its 3 streams to 4 lanes (a power of two)
    lanes = 3 if entry == "run" else 4
    lane_bytes = fleet[0, :CS].astype(np.float32).nbytes
    assert all(e.args["bytes"] == lanes * lane_bytes
               for e in by_name["ingest"])
    assert all(e.args["bytes"] > 0 for e in by_name["fetch"])
    # scoring is exactly the FleetTiming.host_s window
    assert [e.dur for e in by_name["scoring"]] == res.timing.host_s
    (call,) = by_name["run"]
    assert call.args == {"intervals": n, "streams": fleet.shape[0]}
    assert all(call.ts <= e.ts and e.ts + e.dur <= call.ts + call.dur
               for e in tr.events if e is not call)


def test_warm_span_names_what_compiled(engine, fleet):
    """``warm`` covers the whole in-call warm-up and names the programs
    whose jit cache grew: both steps for a new fleet shape, none for a
    new clip length at a known chunk shape (a warm-up inside the call
    that compiles nothing), and no span once the shape is cached."""
    tr = obs.trace.install()
    engine.run(fleet[:2])          # two streams: a new batch shape
    engine.run(fleet[:2, :2 * CS])  # known chunk shape, new clip length
    engine.run(fleet[:2, :2 * CS])  # cached: no warm-up at all
    obs.disable()
    warm = tr.stage_events("warmup")
    assert [e.name for e in warm] == ["warm", "warm"]
    assert warm[0].args["compiled"] == ["camera", "server"]
    assert warm[1].args["compiled"] == []
    assert warm[0].args["shape"] == [2, CS, H, W, 3]
    runs = [e for e in tr.events if e.name == "run"]
    assert len(runs) == 3
    # the warm-up runs inside its call's span
    for w, r in zip(warm, runs):
        assert r.ts <= w.ts and w.ts + w.dur <= r.ts + r.dur


@pytest.mark.parametrize("entry", ["run", "serve_loop"])
def test_plane_off_records_nothing(engine, fleet, monkeypatch, entry):
    """With the plane off the loop never builds its telemetry handles,
    so no span method runs and a tracer left uninstalled stays empty."""
    from repro.engine import multistream

    def refuse():
        raise AssertionError("telemetry handles built with the plane off")

    monkeypatch.setattr(multistream, "_EngineObs", refuse)
    tr = Tracer()
    res = _serve(engine, fleet, entry)
    assert len(res.timing.camera_s) == 3
    assert engine._obs is None and tr.events == []


def test_controller_records_level_transitions():
    from repro.control import RateController
    from repro.control.controller import ChunkObservation

    rc = RateController(delay_budget_s=0.5)
    tr, reg = obs.enable(host=0)
    rc.observe(ChunkObservation(n_bytes=1e5, stream_s=2.0))   # congested
    rc.observe(ChunkObservation(n_bytes=1e5, stream_s=0.1))   # headroom
    rc.observe(ChunkObservation(n_bytes=1e5, stream_s=0.45))  # hold
    obs.disable()
    instants = tr.stage_events("controller")
    assert [e.name for e in instants] == ["decrease", "increase"]
    assert instants[0].args["prev_level"] == 1.0
    assert instants[0].args["level"] < 1.0
    assert reg.get("controller_decisions_total", action="decrease").value == 1
    assert reg.get("controller_decisions_total", action="increase").value == 1
    assert reg.get("controller_decisions_total", action="hold").value == 1
    assert reg.get("controller_level").value == rc.level


def test_autoscaler_records_decisions_and_admissions():
    from repro.control import FleetAutoscaler
    from repro.core.pipeline import FleetTiming

    sc = FleetAutoscaler(pad_pow2=True)
    tr, reg = obs.enable(host=0)
    # camera-bound timing: decide scales out (width 1 -> 2)
    timing = FleetTiming(camera_s=[1.0], server_s=[0.1], host_s=[0.1])
    d = sc.decide(timing, n_streams=4, mesh_width=1, batch_depth=2,
                  n_devices=4)
    sc.admit(3, mesh_width=d.mesh_width)   # new shape: compile
    sc.admit(2, mesh_width=d.mesh_width)   # pads onto the same shape
    obs.disable()
    scale = tr.stage_events("autoscaler")
    if d.mesh_width != 1:  # decision changed => exactly one instant
        assert [e.name for e in scale] == ["scale"]
        assert scale[0].args["prev_width"] == 1
    assert reg.get("scale_decisions_total",
                   action="rescale" if d.mesh_width != 1
                   else "hold").value == 1
    assert reg.get("admissions_total").value == 2
    assert reg.get("admission_compiles_total").value == 1
    assert reg.get("admission_shape_reuse_total").value == 1
    admits = tr.stage_events("admission")
    assert [e.name for e in admits] == ["admit_new_shape"]
