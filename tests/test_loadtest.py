"""The load-harness serving path end to end: windowed aggregation on
the engine, the empty-interval controller guard, and the compact
cross-host wire format.

1. Engine parity — ``detail="windowed"`` (host scoring) reproduces the
   ``detail="legacy"`` per-lane loop's totals on a churny generated
   schedule: byte sums bit-equal, accuracy sums to summation order, p90
   exact; ``detail="chunks"`` (vectorized, full lists) is bit-identical
   to legacy chunk for chunk, on a segmentation fleet and on a
   detection fleet (vectorized host F1).
2. Regression — a drained pending chunk with an empty active set
   (``ids=()``) must not feed the controller a max() over nothing; the
   old per-lane path raised ValueError there.
3. Fleet wire — a 2-host local ``serve_fleet`` in windowed mode merges
   per-host aggregates exactly (global ids, disjointness enforced), and
   mixing windowed with per-chunk payloads is loud.
"""
import numpy as np
import pytest

from repro.control import FleetAutoscaler, RateController, make_workload
from repro.core.aggregate import AggregateConfig
from repro.core.pipeline import FleetTiming, NetworkConfig
from repro.engine import EngineConfig, MultiStreamEngine
from repro.serve.fleet import (FleetTopology, host_payload,
                               merge_host_results, serve_fleet)

CHUNK = 4
H, W = 32, 48
NET = NetworkConfig.shared(2e7, 4)


@pytest.fixture(scope="module")
def models():
    import jax

    from repro.core.accmodel import AccModel, accmodel_init
    from repro.vision.dnn import FinalDNN, init_net

    dnn = FinalDNN("segmentation",
                   init_net("segmentation", jax.random.PRNGKey(0),
                            width=8))
    am = AccModel(accmodel_init(jax.random.PRNGKey(1), 8))
    return dnn, am


@pytest.fixture(scope="module")
def workload():
    return make_workload(n_chunks=4, rate_per_chunk=1.5, seed=2,
                         mean_session_chunks=2.0, initial_streams=3,
                         max_concurrent=4, max_streams=4)


@pytest.fixture(scope="module")
def frames(workload):
    from repro.data.video import make_scene

    return np.stack([
        make_scene("dashcam", seed=40 + i, T=workload.n_chunks * CHUNK,
                   H=H, W=W).frames for i in range(workload.n_streams)])


def _engine(models, workload, detail, device_reduce=True):
    dnn, am = models
    return MultiStreamEngine(dnn, am, config=EngineConfig(
        net=NET, chunk_size=CHUNK, impl="fast",
        autoscaler=FleetAutoscaler(), sim_encode_s=0.01, detail=detail,
        aggregate=workload.aggregate_config(window=2),
        device_reduce=device_reduce))


def _serve(engine, workload, frames):
    return engine.serve_loop(frames, events=list(workload.events),
                             initial=list(workload.initial), net=NET)


# ---------------------------------------------------------------------------
# 1. engine parity: windowed vs the per-lane legacy loop
# ---------------------------------------------------------------------------
def test_windowed_matches_legacy_on_churned_schedule(models, workload,
                                                     frames):
    res_l = _serve(_engine(models, workload, "legacy"), workload, frames)
    res_c = _serve(_engine(models, workload, "chunks"), workload, frames)
    res_w = _serve(_engine(models, workload, "windowed",
                           device_reduce=False), workload, frames)
    # chunks-mode is the bit-identical vectorized rewrite of legacy
    assert res_c.stream_ids == res_l.stream_ids
    for rc, rl in zip(res_c.streams, res_l.streams):
        assert rc.chunks == rl.chunks
    # windowed carries no per-chunk lists, only the aggregate
    agg = res_w.aggregate
    assert agg is not None and res_w.streams == []
    chunks = [c for run in res_l.streams for c in run.chunks]
    assert agg.n == len(chunks) == workload.stream_chunks
    assert agg.sum_bytes == pytest.approx(
        sum(c.bytes for c in chunks), rel=1e-12)
    assert agg.sum_acc == pytest.approx(
        sum(c.accuracy for c in chunks), rel=1e-12)
    delays = [c.total_delay_s for c in chunks]
    assert agg.p90_delay == float(np.percentile(delays, 90.0))
    assert agg.max_delay == max(delays)
    assert agg.stream_ids == tuple(sorted(
        {sid for sid, run in zip(res_l.stream_ids, res_l.streams)
         if run.chunks}))
    # FleetResult falls back to the aggregate for headline metrics
    assert res_w.n_streams == agg.n_streams
    assert res_w.accuracy == agg.accuracy
    assert "slo_gold" in res_w.summary()


@pytest.fixture(scope="module")
def det_models():
    import jax

    from repro.core.accmodel import AccModel, accmodel_init
    from repro.vision.dnn import FinalDNN, init_net

    dnn = FinalDNN("detection",
                   init_net("detection", jax.random.PRNGKey(0), width=8))
    am = AccModel(accmodel_init(jax.random.PRNGKey(1), 8))
    return dnn, am


def test_chunks_match_legacy_on_detection_fleet(det_models, workload,
                                                frames):
    """Detection scores through the vectorized host F1 (no device
    reduction): ``chunks`` is ChunkResult-for-ChunkResult identical to
    the per-lane ``legacy`` loop, and ``windowed`` sums the same scores."""
    assert not det_models[0].supports_device_accuracy
    res_l = _serve(_engine(det_models, workload, "legacy"), workload,
                   frames)
    res_c = _serve(_engine(det_models, workload, "chunks"), workload,
                   frames)
    res_w = _serve(_engine(det_models, workload, "windowed"), workload,
                   frames)
    assert res_c.stream_ids == res_l.stream_ids
    for rc, rl in zip(res_c.streams, res_l.streams):
        assert rc.chunks == rl.chunks
    chunks = [c for run in res_l.streams for c in run.chunks]
    assert len(chunks) == workload.stream_chunks
    # the parity is not vacuous: the scores differ across chunks
    assert len({c.accuracy for c in chunks}) > 1
    agg = res_w.aggregate
    assert agg.n == len(chunks)
    assert agg.sum_acc == pytest.approx(
        sum(c.accuracy for c in chunks), rel=1e-12)


def test_device_reduce_stays_on_device_and_close(models, workload,
                                                 frames):
    dnn, _ = models
    assert dnn.supports_device_accuracy
    res_w = _serve(_engine(models, workload, "windowed"), workload,
                   frames)
    res_l = _serve(_engine(models, workload, "legacy"), workload, frames)
    chunks = [c for run in res_l.streams for c in run.chunks]
    agg = res_w.aggregate
    assert agg.sum_bytes == pytest.approx(
        sum(c.bytes for c in chunks), rel=1e-12)
    # f32 device reduction vs f64 host scoring: close, not bit-equal
    assert agg.sum_acc == pytest.approx(
        sum(c.accuracy for c in chunks), abs=1e-5 * max(agg.n, 1))


def test_detail_knob_validated(models):
    dnn, am = models
    with pytest.raises(ValueError, match="detail"):
        MultiStreamEngine(dnn, am,
                          config=EngineConfig(detail="everything"))


# ---------------------------------------------------------------------------
# 2. the empty-interval controller guard
# ---------------------------------------------------------------------------
def test_finish_with_empty_active_set_skips_controller(models):
    """Regression: a drained pending chunk after every stream left
    (``ids=()``) used to raise ``ValueError: max() arg is an empty
    sequence`` while building the controller observation."""
    dnn, am = models
    engine = MultiStreamEngine(dnn, am, config=EngineConfig(
        net=NET, chunk_size=CHUNK, controller=RateController(),
        sim_encode_s=0.01))
    per_stream = {0: []}
    timing = FleetTiming()
    p = {"ci": 3, "ids": (), "pbytes": np.zeros((2, CHUNK)),
         "cam_dt": 0.01, "outs": {"seg": np.zeros((2, CHUNK, 4, 6, 3))},
         "ref_outs": {"seg": np.zeros((2, CHUNK, 4, 6, 3))},
         "server_steady_s": 0.0, "knobs": None}
    engine._finish(p, per_stream, NET, None, timing, overlap=False)
    assert per_stream[0] == []          # nothing scored
    assert len(timing.host_s) == 1      # accounting still ticked
    assert engine.controller.history == []  # and no phantom observation


# ---------------------------------------------------------------------------
# 3. the compact fleet wire format
# ---------------------------------------------------------------------------
def test_two_host_fleet_merges_windowed_aggregates(models, workload,
                                                   frames):
    topo = FleetTopology.contiguous(workload.n_streams, 2)
    res = serve_fleet(
        lambda h: _engine(models, workload, "windowed"), frames, topo,
        events=workload.events, initial=workload.initial, net=NET)
    agg = res.aggregate
    assert agg is not None and res.streams == []
    assert agg.n == workload.stream_chunks
    # global ids, each attributed to its ingestion host
    assert list(agg.stream_ids) == res.stream_ids
    for sid, host in zip(res.stream_ids, res.hosts):
        assert sid in topo.ownership[host]
    assert set(agg.attainment()) == {t.name for t in workload.tiers}
    # per-host totals add up to the fleet totals
    solo = serve_fleet(
        lambda h: _engine(models, workload, "windowed"), frames,
        FleetTopology.contiguous(workload.n_streams, 1),
        events=workload.events, initial=workload.initial, net=NET)
    assert agg.n == solo.aggregate.n
    assert agg.sum_bytes == pytest.approx(solo.aggregate.sum_bytes,
                                          rel=1e-12)


def test_mixed_wire_formats_are_loud(models, workload, frames):
    res_w = _serve(_engine(models, workload, "windowed"), workload,
                   frames)
    res_c = _serve(_engine(models, workload, "chunks"), workload, frames)
    own = list(range(workload.n_streams))
    pw = host_payload(0, own, res_w)
    pc = host_payload(1, own, res_c)
    assert pw["aggregate"] is not None and pw["streams"] == []
    assert pc["aggregate"] is None and pc["streams"]
    with pytest.raises(ValueError, match="detail"):
        merge_host_results([pw, pc])
