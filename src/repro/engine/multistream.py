"""Sharded, pipelined multi-stream serving: one fused camera step per chunk
interval serves N independent camera streams sharing one uplink, the server
DNN is batched across streams, and the two are double-buffered.

The single-stream engine loops Python-side per camera — fine for one
stream, but a fleet pays N jit dispatches, 2N device syncs, and N small
convolutions per chunk interval. Here the whole camera side (AccModel
scoring + QP assignment + RoI encode) is one XLA program with the stream
axis leading (``serve.steps.make_camera_fleet_step``), optionally lowered
over a 1-D ``"stream"`` device mesh via shard_map (``mesh=``), and the
uplink uses processor-sharing accounting
(``core.pipeline.shared_stream_delays``) instead of a fixed equal split.

Pipelining (``overlap=True``): per chunk interval the loop runs three
stages — fused camera step (device), batched server DNN
(``serve.steps.make_server_fleet_step``, device), host-side accuracy
decode + delay accounting. The server step is dispatched asynchronously
right after its chunk's camera step completes, and two chunks stay in
flight (depth-2 double buffer): the host stage of chunk i runs while the
device queue still holds chunk i+1's server step and chunk i+2's camera
step, so server inference overlaps camera encode and the host never
stalls on the server step. Detection NMS is folded into the batched
server program (``vision.dnn.detection_keep_heat``) so the host stage is
numpy-only and never enqueues device work behind the next camera step.
``FleetResult.timing`` (``core.pipeline.FleetTiming``) records the measured
makespan vs the serialized stage sum.

Accounting notes relative to the sequential engine:
- ``encode_s``/``overhead_s`` per stream report the *fused batch* step's
  wall clock (every camera's chunk completes when the batch completes);
  fleet throughput is the per-chunk step time, not the per-stream sum.
  With ``overlap=True`` the camera wall clock can include the tail of the
  previous chunk's (asynchronously dispatched) server step sharing the
  device queue; serving-tier throughput then lives in ``timing.wall_s``.
- accuracy/bytes match N sequential single-stream runs (exact codec:
  bit-stable; fast codec: within the deviation documented on
  ``codec.encode_chunk_fast``), sharded or not — the stream mesh changes
  the lowering, never the math.
- server inference stays excluded from per-stream delay (as in the paper);
  ``timing.server_s`` tracks it for serving-tier capacity planning only.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.aggregate import AggregateConfig, AggregateResult
from repro.core.pipeline import (ChunkResult, FleetTiming, NetworkConfig,
                                 RunResult, UplinkClock,
                                 shared_stream_delays)
from repro.core.quality import QualityConfig
from repro.engine.config import EngineConfig
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.compile import CompileCounter
from repro.serve.steps import (make_accuracy_reduce_step,
                               make_camera_fleet_step, make_server_fleet_step,
                               make_tenant_accuracy_reduce_step,
                               make_tenant_camera_fleet_step,
                               make_tenant_server_fleet_step,
                               stream_sharding)

#: sentinel distinguishing "caller passed this legacy kwarg" from the
#: default — the deprecation shim only fires on kwargs actually given
_LEGACY = object()


class _EngineObs:
    """Per-run telemetry handles, resolved once so the per-interval cost
    is one attribute load + ``is not None`` branch when the plane is off
    (the <2%-enabled / ~0-disabled budget ``benchmarks/obs_overhead.py``
    pins). ``None`` fields mean that half of the plane is disabled.

    All recording uses values the engine already computed for its own
    accounting, or clock reads taken only while the plane is on — no
    extra device syncs, no RNG — so telemetry can never perturb the data
    path (``tests/test_obs.py`` pins bit-identity). Metric recording in
    the host stage happens *after* ``timing.host_s.append``, so the
    measured host window stays clean.

    The loop's spans, one of each per chunk interval ``ci``, mark its
    layer boundaries on the host: ``ingest`` (host slice + device put),
    ``dispatch_camera``, ``wait_camera`` (the host blocked on the camera
    step), ``dispatch_server`` (server, reference and accuracy steps;
    args ``server``, ``frames`` and ``precision``, and the
    ``server_frames_total`` counter by server),
    ``fetch`` (device-to-host copy of the interval's outputs or accuracy
    vector) and ``scoring`` (exactly ``FleetTiming.host_s``, which holds
    the wire bytes' copy). Each ``span`` call reads
    the clock for the end of the span.
    """

    __slots__ = ("tracer", "reg", "cam_c", "srv_c", "host_c",
                 "stage_h", "delay_h", "queue_h")

    def __init__(self):
        self.tracer = obs_trace.get_tracer()
        self.reg = reg = obs_metrics.get_metrics()
        if reg is not None:
            self.cam_c = reg.counter("stage_seconds_total", stage="camera")
            self.srv_c = reg.counter("stage_seconds_total", stage="server")
            self.host_c = reg.counter("stage_seconds_total", stage="host")
            self.stage_h = {
                s: reg.histogram("stage_seconds", stage=s)
                for s in ("camera", "server", "host")}
            self.delay_h = reg.histogram("chunk_delay_s")
            self.queue_h = reg.histogram("uplink_queue_s")

    def span(self, name: str, lane: str, t0: float, **args) -> None:
        """A span from ``t0`` to now on ``lane``."""
        if self.tracer is not None:
            self.tracer.complete(name, lane, t0, time.perf_counter() - t0,
                                 **args)

    def dispatch_server(self, ci: int, t0: float, dnn, frames: int) -> None:
        """The server steps of interval ``ci`` are dispatched: a span
        from ``t0`` naming the server (its DNN's ``name``), the
        ``frames`` its passes take and its precision; the frames are
        counted by server."""
        if self.tracer is not None:
            self.tracer.complete("dispatch_server", "dispatch", t0,
                                 time.perf_counter() - t0, ci=ci,
                                 server=dnn.name, frames=frames,
                                 precision=dnn.precision_name)
        if self.reg is not None:
            self.reg.counter("server_frames_total",
                             server=dnn.name).inc(frames)

    def camera(self, ci: int, t0: float, t_wait: float, acct: float,
               n_lanes: int, n_active: int) -> None:
        """The camera step is ready: ``wait_camera`` spans the host's
        block from ``t_wait``; the counters take the accounting charge
        and the dispatch-to-ready wall time from ``t0``."""
        t1 = time.perf_counter()
        if self.tracer is not None:
            self.tracer.complete("wait_camera", "camera", t_wait,
                                 t1 - t_wait, ci=ci)
        if self.reg is not None:
            self.cam_c.inc(acct)
            self.stage_h["camera"].observe(t1 - t0)
            self.reg.gauge("lanes_active").set(n_active)
            self.reg.gauge("lanes_padded").set(n_lanes - n_active)

    def server(self, dur: float, ci: Optional[int] = None,
               t0: Optional[float] = None) -> None:
        """Server-step seconds for the counters, and a ``server`` span
        where ``t0`` is given: serialized mode measures the step, while
        overlap mode charges the warm-up estimate, which has no place on
        the timeline."""
        if self.tracer is not None and t0 is not None:
            self.tracer.complete("server", "server", t0, dur, ci=ci)
        if self.reg is not None:
            self.srv_c.inc(dur)
            self.stage_h["server"].observe(dur)

    def finish(self, ci: int, t0: float, host_dur: float, n_active: int,
               lane_bytes, delays, queue_s: float, cam_dt: float) -> None:
        """Host-scoring + uplink accounting for one finished interval.
        Called after ``timing.host_s.append`` so none of this work lands
        inside the measured host window."""
        tr = self.tracer
        if tr is not None:
            tr.complete("scoring", "scoring", t0, host_dur, ci=ci,
                        active=n_active)
        if self.reg is not None:
            self.host_c.inc(host_dur)
            self.stage_h["host"].observe(host_dur)
            self.reg.counter("chunks_served_total").inc(n_active)
            if n_active:
                self.reg.counter("wire_bytes_total").inc(
                    float(sum(lane_bytes[:n_active])))
                self.reg.gauge("uplink_backlog_s").set(queue_s)
                self.queue_h.observe(queue_s)
                self.delay_h.observe_many(
                    [d + cam_dt + queue_s for d in delays[:n_active]])

    def churn(self, ci: int, event) -> None:
        if self.tracer is not None:
            self.tracer.instant("churn", stage="events", ci=ci,
                                join=list(event.join),
                                leave=list(event.leave))
        if self.reg is not None:
            self.reg.counter("churn_joins_total").inc(len(event.join))
            self.reg.counter("churn_leaves_total").inc(len(event.leave))

    def slo_attainment(self, aggregate, tenants=None) -> None:
        """Windowed runs: export the aggregator's per-tier SLO
        attainment as gauges at run end — and, on tenanted fleets, the
        per-tenant attainment/volume split (labelled by tenant name)."""
        if self.reg is not None and aggregate is not None:
            for tier, frac in aggregate.attainment().items():
                if frac == frac:  # skip empty tiers (NaN)
                    self.reg.gauge("slo_attainment", tier=tier).set(frac)
            if tenants is not None and aggregate.tenanted:
                for t, atts in enumerate(aggregate.attainment_by_tenant()):
                    name = tenants[t].name
                    self.reg.gauge("tenant_chunks_served",
                                   tenant=name).set(int(aggregate.t_n[t]))
                    for tier, frac in atts.items():
                        if frac == frac:
                            self.reg.gauge("tenant_slo_attainment",
                                           tenant=name,
                                           tier=tier).set(frac)

    def tenant_lanes(self, tenants, counts) -> None:
        """Per-interval active-lane split across tenants (the occupancy
        the capacity split divides by)."""
        if self.reg is not None:
            for t, spec in enumerate(tenants):
                self.reg.gauge("tenant_lanes_active",
                               tenant=spec.name).set(int(counts[t]))


@functools.lru_cache()
def _jit_nms():
    """Process-wide jitted detection NMS (one compile across engine runs)."""
    from repro.vision.dnn import detection_keep_heat

    return jax.jit(detection_keep_heat)


@dataclasses.dataclass
class FleetResult:
    """Per-stream results plus fleet-level camera timing.

    Closed-loop runs (``serve_loop``) additionally carry the control
    plane's trajectory: ``stream_ids`` maps each entry of ``streams``
    back to its fleet lane id (churn means not every stream serves every
    chunk), ``decisions`` is the per-interval ``ScaleDecision`` record,
    and ``shapes`` the padded fleet shapes admission ever compiled —
    the O(log N) churn guarantee, in data."""

    streams: List[RunResult]
    camera_s: List[float]     # fused camera-step wall clock per chunk
    timing: Optional[FleetTiming] = None  # full pipeline accounting
    stream_ids: Optional[List[int]] = None   # serve_loop: lane ids
    decisions: Optional[List] = None         # serve_loop: ScaleDecisions
    shapes: Optional[List[int]] = None       # serve_loop: padded shapes
    hosts: Optional[List[int]] = None  # multi-host (serve_fleet): the
    # ingestion host that served each entry of ``streams``
    aggregate: Optional[AggregateResult] = None  # detail="windowed":
    # O(window) summaries replace ``streams`` at fleet scale
    served_cis: Optional[List[int]] = None  # absolute chunk interval of
    # each ``camera_s`` entry (all-quiet intervals append neither) — the
    # explicit record the cross-host camera_s merge aligns on, and what
    # failure-time re-serve dedup keys by
    tenant_ids: Optional[List[int]] = None  # multi-tenant fleets: the
    # tenant index each entry of ``streams`` belongs to (windowed runs
    # carry the split inside ``aggregate`` instead)

    @property
    def n_streams(self):
        if not self.streams and self.aggregate is not None:
            return self.aggregate.n_streams
        return len(self.streams)

    @property
    def accuracy(self):
        if not self.streams and self.aggregate is not None:
            return self.aggregate.accuracy
        return float(np.mean([r.accuracy for r in self.streams]))

    @property
    def mean_camera_s(self):
        return float(np.mean(self.camera_s))

    @property
    def chunks_per_s(self):
        """Fleet camera throughput: stream-chunks processed per second."""
        return self.n_streams / max(self.mean_camera_s, 1e-12)

    def accuracy_by_tenant(self):
        """Per-tenant mean accuracy (tuple indexed by tenant id) — the
        number the 2-tenant acceptance test pins against dedicated
        single-tenant engines. Windowed runs read the aggregate's exact
        per-tenant sums; per-chunk runs group ``streams`` by
        ``tenant_ids`` and mean the per-stream accuracies (matching the
        dedicated engines' ``FleetResult.accuracy``)."""
        if not self.streams and self.aggregate is not None \
                and self.aggregate.tenanted:
            return self.aggregate.accuracy_by_tenant()
        if self.tenant_ids is None:
            raise ValueError("untenanted result has no per-tenant "
                             "accuracy; serve with EngineConfig(tenants"
                             "=...)")
        by_t: dict = {}
        for t, r in zip(self.tenant_ids, self.streams):
            by_t.setdefault(int(t), []).append(r.accuracy)
        n = max(by_t) + 1 if by_t else 0
        return tuple(float(np.mean(by_t[t])) if t in by_t
                     else float("nan") for t in range(n))

    def _delay_percentile(self, q: float) -> float:
        if not self.streams and self.aggregate is not None:
            return self.aggregate.delay_percentile(q)
        delays = [c.total_delay_s for r in self.streams for c in r.chunks]
        # a serve_loop schedule where no stream ever served is legal
        # (admit(0) idles every interval) — report nan, not a crash
        return float(np.percentile(delays, q)) if delays else float("nan")

    @property
    def p90_delay(self):
        """Tail end-to-end chunk delay pooled over every served
        stream-chunk — the fleet-level SLO closed-loop scaling targets."""
        return self._delay_percentile(90)

    def summary(self):
        s = {
            "n_streams": self.n_streams,
            "accuracy": self.accuracy,
            "camera_s_per_chunk": self.mean_camera_s,
            "chunks_per_s": self.chunks_per_s,
            "p95_delay_s": self._delay_percentile(95),
        }
        if self.timing is not None:
            s.update(wall_s=self.timing.wall_s,
                     serialized_s=self.timing.serialized_s,
                     overlap_speedup=self.timing.overlap_speedup)
        if self.shapes is not None:
            s.update(n_compiled_shapes=len(self.shapes),
                     p90_delay_s=self.p90_delay)
        if self.decisions is not None:
            s["n_rescales"] = sum(
                1 for a, b in zip(self.decisions, self.decisions[1:])
                if (a.mesh_width, a.batch_depth)
                != (b.mesh_width, b.batch_depth))
        if self.aggregate is not None:
            s.update(self.aggregate.summary())
        return s


class MultiStreamEngine:
    """Batched AccMPEG serving for N cameras sharing one uplink.

    ``impl``   chunk-encoder backend from the ``codec.CHUNK_ENCODERS``
               registry ("fast" | "exact" | "fast_exact" | "pallas" |
               "fused" | "fused_exact" — the fused pair takes the
               scores fast-path in ``serve.steps``, skipping the
               materialized QP map).
    ``mesh``   None (single-device vmap), a 1-D ``"stream"`` Mesh, or
               "auto" (widest stream mesh dividing N on the available
               devices — ``distributed.mesh.stream_mesh_for``).
    ``overlap`` double-buffer the batched server DNN + host accounting
               against the next chunk's camera step (False = serialized
               camera -> server -> host loop, the pre-pipeline shape).
    ``depth``  chunks in flight when overlapped (2 = the classic double
               buffer; deeper buffers let slow server steps hide behind
               several camera steps — the autoscaler's batch-depth knob).
    ``trace``  time-varying shared-uplink bandwidth trace
               (``control.traces.NetworkTrace``): per-chunk uploads
               processor-share the trace at their actual send time and
               queue behind the previous chunk's upload
               (``core.pipeline.UplinkClock.send_shared``); replaces the
               constant ``net`` accounting.
    ``controller`` fleet-wide ``control.controller.RateController``: the
               camera step is built knob-taking (``make_camera_fleet_step
               (knobs=True)``), the controller's traced knob array rides
               along each dispatch (no recompiles), and each finished
               chunk's tail delay feeds back. With ``overlap=True`` the
               feedback lags by the pipeline depth, exactly like a real
               double-buffered deployment.
    ``autoscaler`` ``control.autoscaler.FleetAutoscaler``: after each run
               the measured ``FleetTiming`` is turned into a
               ``ScaleDecision`` (``self.last_scale``); ``apply_scale()``
               adopts it for the next run.

    ``sim_encode_s`` replaces the *accounted* per-chunk camera time (the
               ``ChunkResult.encode_s`` charge and the uplink clock's
               ready time) with a fixed constant, making trace-driven
               delay accounting fully deterministic — multi-host parity
               tests and simulation replays depend on it. ``FleetTiming``
               keeps the measured wall clocks either way, so autoscaler
               occupancy still sees real hardware.

    ``run()`` serves a fixed fleet; :meth:`serve_loop` is the closed-loop
    variant — stream membership churns via ``control.ChurnEvent``s,
    admission re-pads the fleet shape mid-stream, and ``ScaleDecision``s
    apply between chunks without tearing the engine down.
    """

    def __init__(self, final_dnn=None, accmodel=None,
                 qcfg=_LEGACY, net=_LEGACY, *,
                 config: Optional[EngineConfig] = None,
                 chunk_size=_LEGACY, impl=_LEGACY, mesh=_LEGACY,
                 overlap=_LEGACY, depth=_LEGACY, trace=_LEGACY,
                 controller=_LEGACY, autoscaler=_LEGACY, fps=_LEGACY,
                 sim_encode_s=_LEGACY, detail=_LEGACY, aggregate=_LEGACY,
                 device_reduce=_LEGACY):
        # -- typed-config surface + legacy-kwarg shim ----------------------
        # the supported construction is MultiStreamEngine(dnn, accmodel,
        # config=EngineConfig(...)); loose serving kwargs still work but
        # assemble the same EngineConfig under a DeprecationWarning (and
        # are parity-tested bit-exact against the config path)
        given = {k: v for k, v in (
            ("qcfg", qcfg), ("net", net), ("chunk_size", chunk_size),
            ("impl", impl), ("mesh", mesh), ("overlap", overlap),
            ("depth", depth), ("trace", trace), ("controller", controller),
            ("autoscaler", autoscaler), ("fps", fps),
            ("sim_encode_s", sim_encode_s), ("detail", detail),
            ("aggregate", aggregate), ("device_reduce", device_reduce),
        ) if v is not _LEGACY}
        if config is not None and given:
            raise ValueError(
                f"pass serving options through config=EngineConfig(...) "
                f"OR as legacy kwargs, not both (got config plus "
                f"{sorted(given)})")
        if config is None:
            if given:
                warnings.warn(
                    "MultiStreamEngine's loose serving kwargs are "
                    "deprecated; pass config=EngineConfig(...) (see "
                    "engine/README.md for the kwarg -> field table)",
                    DeprecationWarning, stacklevel=2)
            config = EngineConfig(**given)
        self.config = config
        # -- tenancy -------------------------------------------------------
        # one tenant folds into the classic single-DNN engine (adopting
        # the tenant's DNN/AccModel/QualityConfig — bit-identical path);
        # two or more light up the tenant-routed fleet steps
        self.tenants = config.tenants
        self._tenanted = config.tenanted
        self._tenant_of = dict(config.tenant_of or {})
        if self.tenants is not None:
            if final_dnn is not None or accmodel is not None:
                raise ValueError(
                    "EngineConfig(tenants=...) declares the served "
                    "DNN/AccModel per tenant; do not also pass "
                    "final_dnn/accmodel")
            if self._tenanted and config.controller is not None:
                raise ValueError(
                    "multi-tenant fleets do not support the rate "
                    "controller yet: its knob array is fleet-wide while "
                    "tenants carry per-tenant quality configs")
            final_dnn = self.tenants[0].dnn
            accmodel = self.tenants[0].accmodel
        self.final_dnn = final_dnn
        self.accmodel = accmodel
        # a single tenant's qcfg IS the engine's qcfg; multi-tenant
        # engines keep per-lane configs inside the tenant camera step and
        # never read self.qcfg on the data path
        self.qcfg = self.tenants[0].qcfg if self.tenants is not None \
            and not self._tenanted else config.qcfg
        # mutable runtime attributes seeded from the frozen config —
        # apply_scale and serve_loop legitimately move mesh/overlap/depth
        # at run time, so the instance owns them from here on
        self.net = config.net
        self.chunk_size = config.chunk_size
        self.impl = config.impl
        self.mesh = config.mesh
        self.overlap = config.overlap
        self.depth = config.depth
        self.trace = config.trace
        self.controller = config.controller
        self.autoscaler = config.autoscaler
        self.fps = config.fps
        self.sim_encode_s = config.sim_encode_s
        # host accounting mode: "chunks" keeps full per-chunk ChunkResult
        # lists but scores all lanes in one vectorized pass (bit-identical
        # to "legacy", the preserved per-lane loop / parity oracle);
        # "windowed" streams chunk batches into a FleetAggregator so the
        # result carries O(window) summaries — the fleet-scale mode
        self.detail = config.detail
        self.aggregate = config.aggregate  # for detail="windowed"
        # with detail="windowed" and no precomputed refs, reduce per-lane
        # accuracy on device (segmentation/keypoint) so dense output trees
        # never cross to host — only (N,) scalars do
        self.device_reduce = config.device_reduce
        self.last_scale = None  # autoscaler's most recent ScaleDecision
        self.last_serve_state = None  # serve_loop's exported resume state
        self._steps = {}  # resolved mesh (or None) -> (camera, server)
        self._acc_steps = {}  # resolved mesh -> device accuracy reduce
        self._warm = {}   # (shape, mesh, refs is None) -> steady-state times
        self._refs_prepared = None  # (refs object, prepared copy)
        self._agg = None  # live FleetAggregator during a windowed run
        self._obs = None  # per-run telemetry handles (None = plane off)

    # -- tenancy helpers ------------------------------------------------------
    def _tenant_idx(self, sid: int) -> int:
        return self._tenant_of.get(sid, 0)

    def _dnn_for_sid(self, sid: int):
        """The server DNN that scores stream ``sid`` (per-tenant on
        tenanted fleets; the engine's single DNN otherwise)."""
        if self._tenanted:
            return self.tenants[self._tenant_idx(sid)].dnn
        return self.final_dnn

    def _tenant_lane_ids(self, sids, n_lanes: int) -> np.ndarray:
        """Dense (n_lanes,) int32 tenant-id lane for a fleet batch whose
        active prefix serves ``sids``; padded lanes route to tenant 0
        (their outputs are masked downstream like every padding lane)."""
        lane = np.zeros(n_lanes, np.int32)
        for i, sid in enumerate(sids):
            lane[i] = self._tenant_idx(sid)
        return lane

    def _tenant_counts(self, sids) -> List[int]:
        """Per-tenant active stream counts — the occupancy the
        autoscaler's capacity split divides by."""
        counts = [0] * len(self.tenants)
        for sid in sids:
            counts[self._tenant_idx(sid)] += 1
        return counts

    def _build_agg(self):
        """The windowed run's aggregator; tenanted fleets thread the
        stream -> tenant map and per-tenant SLO ladders through so the
        result carries per-tenant attainment."""
        cfg = self.aggregate or AggregateConfig()
        if not self._tenanted:
            return cfg.build()
        return cfg.build(tenant_of=dict(self._tenant_of),
                         tenant_tiers=tuple(t.tiers for t in self.tenants))

    # -- step construction ---------------------------------------------------
    def _resolve_mesh(self, n_streams: int) -> Optional[Mesh]:
        if self.mesh == "auto":
            from repro.distributed.mesh import stream_mesh_for

            return stream_mesh_for(n_streams)
        return self.mesh

    def _steps_for(self, n_streams: int, masked: bool = False):
        mesh = self._resolve_mesh(n_streams)
        # the camera step's arity depends on controller presence (and on
        # whether it takes an admission lane mask), so the cache key must
        # too (toggling controller between runs would otherwise dispatch
        # into a step of the wrong arity)
        key = (mesh, self.controller is not None, masked)
        if key not in self._steps:
            if self._tenanted:
                # tenant-routed steps: per-lane tenant ids ride as traced
                # data, so tenant-mix churn at a fixed padded shape costs
                # zero recompiles (same guarantee as the lane mask)
                self._steps[key] = (
                    make_tenant_camera_fleet_step(self.tenants,
                                                  impl=self.impl,
                                                  mesh=mesh, mask=masked),
                    make_tenant_server_fleet_step(self.tenants, mesh=mesh),
                )
            else:
                self._steps[key] = (
                    make_camera_fleet_step(self.accmodel, self.qcfg,
                                           impl=self.impl, mesh=mesh,
                                           knobs=self.controller is not None,
                                           mask=masked),
                    make_server_fleet_step(self.final_dnn, mesh=mesh),
                )
        return self._steps[key] + (mesh,)

    def _use_device_reduce(self, refs) -> bool:
        """Device accuracy reduction applies only when the run is windowed
        (no per-chunk results wanted), references are computed in-loop
        (precomputed refs live on host), and the task has a jnp-reducible
        metric (on tenanted fleets: every tenant's task)."""
        if self._tenanted:
            reducible = all(t.dnn.supports_device_accuracy
                            for t in self.tenants)
        else:
            reducible = self.final_dnn.supports_device_accuracy
        return (self.detail == "windowed" and self.device_reduce
                and refs is None and reducible)

    def _acc_step_for(self, mesh):
        if mesh not in self._acc_steps:
            if self._tenanted:
                self._acc_steps[mesh] = make_tenant_accuracy_reduce_step(
                    self.tenants, mesh=mesh)
            else:
                self._acc_steps[mesh] = make_accuracy_reduce_step(
                    self.final_dnn, mesh=mesh)
        return self._acc_steps[mesh]

    def _mesh_width(self) -> int:
        """Current stream-mesh width (1 = single-device vmap)."""
        return int(self.mesh.devices.size) \
            if isinstance(self.mesh, Mesh) else 1

    @staticmethod
    def _put(x, sharding):
        x = jnp.asarray(x)
        return jax.device_put(x, sharding) if sharding is not None else x

    def _steady_times(self, camera, server_step, warm, refs_none: bool,
                      overlap: bool, key, acc_step=None, programs=None):
        """Compile the camera + server programs for this batch shape
        outside the timed loop, then (overlap mode) time one hot step of
        each — the steady-state estimates per-stream ``encode_s`` and
        ``timing.server_s`` report while the pipelined loop's
        dispatch->ready spans absorb overlapped work. Cached per
        (shape, mesh, refs mode, ...) so repeat visits to a fleet shape
        skip the warm-up device work entirely.

        With the tracer on, one ``warm`` span covers all of it; its
        ``compiled`` arg names the ``programs`` (name -> jitted callable)
        whose jit cache grew, so a compile inside a measured window shows
        on the timeline."""
        if key in self._warm:
            return self._warm[key]
        ob = self._obs
        if ob is not None and ob.tracer is not None:
            counter = CompileCounter(**(programs or {}))
        t_warm = time.perf_counter()
        d0, _, _ = camera(warm)
        jax.block_until_ready(d0)
        so = server_step(d0)
        jax.block_until_ready(jax.tree_util.tree_leaves(so))
        if acc_step is not None:  # compile the device accuracy reduce too
            jax.block_until_ready(acc_step(so, so))
        reg = obs_metrics.get_metrics()
        if reg is not None:
            reg.counter("warm_compiles_total").inc()
            reg.histogram("warmup_seconds").observe(
                time.perf_counter() - t_warm)
        cam_steady_s = server_steady_s = 0.0
        if overlap:  # serialized mode measures stages per chunk instead
            t0 = time.perf_counter()
            jax.block_until_ready(camera(warm)[0])
            cam_steady_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            jax.block_until_ready(
                jax.tree_util.tree_leaves(server_step(d0)))
            if refs_none:  # refs=None: second server pass per chunk
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(server_step(warm)))
            server_steady_s = time.perf_counter() - t0
        if ob is not None and ob.tracer is not None:
            ob.span("warm", "warmup", t_warm, shape=list(warm.shape),
                    compiled=sorted(n for n, d in counter.growth().items()
                                    if d > 0))
        self._warm[key] = (cam_steady_s, server_steady_s)
        return self._warm[key]

    def apply_scale(self, decision=None) -> "MultiStreamEngine":
        """Adopt a ``ScaleDecision`` (default: the last one) for the next
        ``run``: stream-mesh width, buffer depth, and overlap on/off.
        Compiled steps for previously used meshes stay cached."""
        d = decision or self.last_scale
        if d is None:
            raise ValueError("no ScaleDecision to apply (run first, or "
                             "pass one)")
        if d.mesh_width > 1:
            from repro.distributed.mesh import make_stream_mesh

            self.mesh = make_stream_mesh(d.mesh_width)
        else:
            self.mesh = None
        self.overlap = d.overlap
        self.depth = d.batch_depth
        return self

    def _prepare_refs(self, refs):
        """Normalize references and precompute their device half once, up
        front: raw high-quality frames (chunk_accuracy's legacy fallback)
        become server-DNN outputs, and detection refs get their NMS
        (``"keep"``) — the per-chunk host stage then touches numpy only,
        so it never enqueues device work behind the next camera step.

        The prepared copy is cached by the identity of ``refs``: references
        are treated as immutable once passed (pass a fresh list after
        recomputing D(H); in-place mutation would be served stale)."""
        if refs is None:
            return None
        if self._refs_prepared is not None and self._refs_prepared[0] is refs:
            return self._refs_prepared[1]  # same refs across runs: once
        prepared = []
        for sid, stream_refs in enumerate(refs):
            # refs index by stream id, so each stream's references run
            # through its *own* tenant's DNN on tenanted fleets
            dnn = self._dnn_for_sid(sid)
            detection = dnn.task == "detection"
            row = []
            for r in stream_refs:
                if not isinstance(r, dict):  # raw frames -> D(ref)
                    r = dnn.predict(jnp.asarray(r))
                if detection and "keep" not in r:
                    r = dict(r, keep=np.asarray(_jit_nms()(r)))
                row.append(r)
            prepared.append(row)
        self._refs_prepared = (refs, prepared)
        return prepared

    # -- chunk post-processing (host side) ------------------------------------
    def _finish(self, p, per_stream, net, refs, timing, overlap: bool,
                clock=None):
        """Server-output scoring + uplink accounting for one chunk; in
        overlapped mode this host work runs while the device executes the
        next chunk's camera step.

        ``p["ids"]`` (closed-loop ``serve_loop`` chunks) maps active lanes
        to fleet stream ids; lanes past ``len(ids)`` are admission padding
        whose wire bytes the masked camera step already zeroed — they ride
        through the shared-uplink solvers at zero cost and are never
        scored, so padding contributes exactly nothing to accuracy, bytes,
        or delay aggregates.

        Scoring dispatches on ``self.detail``: "chunks" (default) scores
        every active lane in one vectorized numpy pass and still builds
        the full ChunkResult lists, bit-identical to "legacy" — the
        original per-lane Python loop, preserved as the parity oracle and
        as the bench's O(streams x chunks) baseline; "windowed" folds the
        lane batch into the run's FleetAggregator (O(window) state) and
        appends nothing. When the chunk carries a device-reduced accuracy
        vector (``p["acc_dev"]``) the dense output trees were never
        fetched at all."""
        ob = self._obs
        if ob is not None:
            t_fetch = time.perf_counter()
        ci = p["ci"]
        acc_dev = p.get("acc_dev")
        outs = ref_outs = None
        if acc_dev is None:
            # bulk-fetch device results to host once, then keep the
            # scoring in numpy — per-stream device slicing would enqueue
            # tiny computations behind the (already dispatched) next
            # camera step
            outs = {k: np.asarray(v) for k, v in p["outs"].items()}
            ref_outs = None if p["ref_outs"] is None else {
                k: np.asarray(v) for k, v in p["ref_outs"].items()}
        else:
            # materialize the device-reduced (N,) accuracies up front,
            # beside the bulk fetch above: blocking on the device here
            # would charge server compute to the host_s accounting
            acc_dev = np.asarray(acc_dev)
        if ob is not None:  # the wire bytes' copy stays in host_s
            fetched = [acc_dev] if outs is None else \
                list(outs.values()) + list((ref_outs or {}).values())
            ob.span("fetch", "fetch", t_fetch, ci=ci,
                    bytes=int(sum(a.nbytes for a in fetched)))
        if overlap:
            timing.server_s.append(p["server_steady_s"])
        t0 = time.perf_counter()
        ids = p.get("ids")  # serve_loop: active lane i -> stream ids[i]
        pbytes = np.asarray(p["pbytes"])
        n_lanes = pbytes.shape[0]
        n_active = n_lanes if ids is None else len(ids)
        # one vectorized row-sum; .tolist() keeps the downstream delay
        # solvers / controller sums fed with the same Python floats the
        # old per-lane loop produced
        lane_bytes = pbytes.reshape(n_lanes, -1).sum(axis=1).tolist()
        if clock is None:
            # price the uplink over *active* lanes only: the constant-net
            # fallback sizes the shared uplink as bandwidth_bps * N when
            # the config carries no uplink_bps, and padding lanes are not
            # cameras — counting them would grant the fleet phantom
            # capacity (active lanes occupy the leading rows, so this is
            # a prefix slice)
            delays = shared_stream_delays(lane_bytes[:n_active], net)
            delays += [0.0] * (n_lanes - len(delays))
            queue_s = 0.0
        else:
            # the trace's capacity is absolute (bw(t)), so zero-byte
            # padded lanes already ride along at zero cost
            delays, queue_s = clock.send_shared(ci, lane_bytes,
                                                p["cam_dt"])
        if n_active and self.detail == "legacy":
            for i in range(n_active):
                sid = i if ids is None else ids[i]
                out_i = {k: v[i] for k, v in outs.items()}
                if refs is not None:
                    ref = refs[sid][ci]
                else:
                    ref = {k: v[i] for k, v in ref_outs.items()}
                acc = self._dnn_for_sid(sid).accuracy(out_i, ref)
                per_stream[sid].append(ChunkResult(
                    acc, lane_bytes[i], encode_s=p["cam_dt"],
                    overhead_s=0.0, stream_s=delays[i], queue_s=queue_s,
                    ci=ci))
        elif n_active:
            sids = list(range(n_active)) if ids is None else list(ids)
            if acc_dev is not None:
                accs = np.asarray(acc_dev, np.float64)[:n_active]
            elif not self._tenanted:
                outs_a = {k: v[:n_active] for k, v in outs.items()}
                if refs is not None:
                    keys = refs[sids[0]][ci].keys()
                    ref_a = {k: np.stack([np.asarray(refs[sid][ci][k])
                                          for sid in sids]) for k in keys}
                else:
                    ref_a = {k: v[:n_active] for k, v in ref_outs.items()}
                accs = self.final_dnn.accuracy_batched(outs_a, ref_a)
            else:
                # tenant-grouped host scoring: each tenant's DNN scores
                # its own lanes in one batched call (the union output
                # tree carries every task's keys, and each metric reads
                # only its task's — foreign-lane garbage never surfaces)
                accs = np.zeros(n_active, np.float64)
                lane_t = np.asarray([self._tenant_idx(sid)
                                     for sid in sids])
                for t in np.unique(lane_t):
                    rows = np.flatnonzero(lane_t == t)
                    dnn = self.tenants[int(t)].dnn
                    o_t = {k: v[rows] for k, v in outs.items()}
                    if refs is not None:
                        keys = refs[sids[int(rows[0])]][ci].keys()
                        ref_t = {k: np.stack(
                            [np.asarray(refs[sids[int(i)]][ci][k])
                             for i in rows]) for k in keys}
                    else:
                        ref_t = {k: v[rows] for k, v in ref_outs.items()}
                    accs[rows] = np.asarray(
                        dnn.accuracy_batched(o_t, ref_t), np.float64)
            if self.detail == "windowed":
                total = (np.asarray(delays[:n_active], np.float64)
                         + p["cam_dt"] + queue_s)
                self._agg.observe(ci, sids, accs,
                                  np.asarray(lane_bytes[:n_active],
                                             np.float64), total)
            else:
                for i in range(n_active):
                    per_stream[sids[i]].append(ChunkResult(
                        float(accs[i]), lane_bytes[i],
                        encode_s=p["cam_dt"], overhead_s=0.0,
                        stream_s=delays[i], queue_s=queue_s, ci=ci))
        if self.controller is not None and n_active:
            from repro.control.controller import ChunkObservation

            # the fleet shares one uplink, so the controller tracks the
            # batch tail: the slowest *active* stream's completion is what
            # a fade turns into backlog for the next chunk interval;
            # used_knobs is what this chunk was dispatched with (under
            # overlap the level has moved since). An all-quiet interval
            # (n_active == 0) that still reaches scoring — a drained
            # pending chunk after everyone left — yields no observation:
            # there is no batch tail to measure, and the old
            # ``max(delays[i] for i in rows)`` raised on it
            self.controller.observe(ChunkObservation(
                n_bytes=float(sum(lane_bytes[:n_active])),
                stream_s=max(delays[:n_active]),
                queue_s=queue_s, compute_s=p["cam_dt"],
                n_streams=n_active),
                used_knobs=p.get("knobs"))
        host_dur = time.perf_counter() - t0
        timing.host_s.append(host_dur)
        if ob is not None:  # after host_s.append: outside the host window
            if overlap:
                ob.server(p["server_steady_s"])
            ob.finish(ci, t0, host_dur, n_active, lane_bytes, delays,
                      queue_s, p["cam_dt"])

    # -- the pipelined fleet loop ---------------------------------------------
    def run(self, frames, refs: Optional[Sequence[Sequence]] = None,
            net: Optional[NetworkConfig] = None) -> FleetResult:
        """frames (N, T, H, W, C); refs[i][ci]: per-stream per-chunk D(H)
        references (optional; without them the reference outputs are the
        server DNN on the raw chunk, batched like everything else)."""
        N, T = frames.shape[:2]
        cs = self.chunk_size
        net = net or self.net or NetworkConfig.shared(2.5e6, N)
        cam_step, server_step, mesh = self._steps_for(N)
        sharding = stream_sharding(mesh) if mesh is not None else None
        per_stream: List[List[ChunkResult]] = [[] for _ in range(N)]
        timing = FleetTiming()
        starts = list(range(0, T - T % cs, cs))
        refs = self._prepare_refs(refs)
        windowed = self.detail == "windowed"
        if windowed:
            self._agg = self._build_agg()
        use_dev = self._use_device_reduce(refs)
        acc_step = self._acc_step_for(mesh) if use_dev else None
        controlled = self.controller is not None
        if controlled:
            self.controller.reset()
        clock = None if self.trace is None else \
            UplinkClock(self.trace, cs, self.fps)
        self._obs = ob = _EngineObs() \
            if (obs_trace.enabled() or obs_metrics.enabled()) else None
        if ob is not None:
            t_call = time.perf_counter()
        programs = {"camera": cam_step, "server": server_step}
        if use_dev:
            programs["acc"] = acc_step
        tids_dev = None
        if self._tenanted:
            # the per-lane tenant-id lane: stream i IS lane i in run(),
            # and the tenant steps take it as a trailing traced argument
            tids_dev = self._put(
                self._tenant_lane_ids(range(N), N), sharding)
            server_step = (lambda d, _s=server_step, _t=tids_dev:
                           _s(d, _t))
            if use_dev:
                acc_step = (lambda o, r, _a=acc_step, _t=tids_dev:
                            _a(o, r, _t))

        def camera(batch):
            if tids_dev is not None:  # tenant-routed step
                return cam_step(batch, tids_dev)
            if controlled:  # traced knob array: fresh values, same program
                return cam_step(batch, self.controller.knob_array())
            return cam_step(batch)

        def put(x):
            return self._put(x, sharding)

        # steady-state timing: compile camera + server outside the clock,
        # then time one hot step of each — wall_s stays the measured
        # ground truth for the whole loop (see _steady_times).
        warm_key = (frames.shape, mesh, refs is None, self.overlap,
                    controlled, use_dev)
        if warm_key in self._warm:  # repeat run: skip the warm put
            cam_steady_s, server_steady_s = self._warm[warm_key]
        else:
            cam_steady_s, server_steady_s = self._steady_times(
                camera, server_step, put(frames[:, : cs]), refs is None,
                self.overlap, warm_key, acc_step=acc_step,
                programs=programs)

        # ``depth`` chunks stay in flight (2 = the classic double buffer):
        # at iteration ci the host scores chunk ci-depth, whose server
        # outputs are long since ready, while the device queue still holds
        # the later chunks' server and camera steps — so host accounting
        # overlaps the device stages and the host never stalls waiting for
        # the server step
        pending: List[dict] = []
        depth = self.depth
        t_run = time.perf_counter()
        for ci, s in enumerate(starts):
            if ob is not None:
                t_in = time.perf_counter()
            batch = put(frames[:, s : s + cs])
            if ob is not None:
                ob.span("ingest", "ingest", t_in, ci=ci, bytes=batch.nbytes)
            knobs_used = self.controller.knobs() if controlled else None
            t0 = time.perf_counter()
            decoded, pbytes, _ = camera(batch)    # async dispatch
            if ob is not None:
                ob.span("dispatch_camera", "dispatch", t0, ci=ci)
            if self.overlap and len(pending) >= depth:
                self._finish(pending.pop(0), per_stream, net, refs,
                             timing, True, clock)
            if ob is not None:
                t_wait = time.perf_counter()
            jax.block_until_ready(decoded)
            cam_dt = cam_steady_s if self.overlap \
                else time.perf_counter() - t0
            timing.camera_s.append(cam_dt)
            # accounting charge: the measured step time, or the fixed
            # simulation constant (deterministic delay replay / parity)
            acct_dt = cam_dt if self.sim_encode_s is None \
                else self.sim_encode_s
            if ob is not None:
                ob.camera(ci, t0, t_wait, cam_dt, N, N)
            t1 = time.perf_counter()
            outs = server_step(decoded)           # batched server DNN
            ref_outs = server_step(batch) if refs is None else None
            if use_dev:
                # reduce accuracy on device and let the dense output
                # trees die in the device queue — only (N,) scalars and
                # the byte matrix ever reach the host
                acc_dev = acc_step(outs, ref_outs)
                entry = dict(ci=ci, outs=None, ref_outs=None,
                             acc_dev=acc_dev)
            else:
                acc_dev = None
                entry = dict(ci=ci, outs=outs, ref_outs=ref_outs)
            if ob is not None:
                # D(H) in the loop is a second pass over the frames
                passes = 1 if refs is not None else 2
                ob.dispatch_server(ci, t1, self.final_dnn,
                                   passes * decoded.shape[0] * cs)
            entry.update(pbytes=pbytes, cam_dt=acct_dt,
                         server_steady_s=server_steady_s,
                         knobs=knobs_used)
            pending.append(entry)
            if not self.overlap:
                if use_dev:
                    jax.block_until_ready(acc_dev)
                else:
                    jax.block_until_ready(jax.tree_util.tree_leaves(outs))
                    if ref_outs is not None:  # ref pass bills to server
                        jax.block_until_ready(
                            jax.tree_util.tree_leaves(ref_outs))
                srv_dt = time.perf_counter() - t1
                timing.server_s.append(srv_dt)
                if ob is not None:
                    ob.server(srv_dt, ci, t1)
                self._finish(pending.pop(0), per_stream, net, refs,
                             timing, False, clock)
        while pending:
            self._finish(pending.pop(0), per_stream, net, refs, timing,
                         self.overlap, clock)
        timing.wall_s = time.perf_counter() - t_run
        if ob is not None:
            ob.span("run", "events", t_call, intervals=len(starts),
                    streams=N)
        if self.autoscaler is not None:
            width = mesh.devices.size if mesh is not None else 1
            # tenant_streams only rides when tenanted: autoscaler
            # subclasses predating the kwarg keep working untouched
            tkw = ({"tenant_streams": self._tenant_counts(range(N))}
                   if self._tenanted else {})
            self.last_scale = self.autoscaler.decide(
                timing, N, mesh_width=width,
                batch_depth=self.depth if self.overlap else 1, **tkw)
        served_cis = list(range(len(starts)))  # run(): ci == position
        tenant_ids = [self._tenant_idx(i) for i in range(N)] \
            if self._tenanted else None
        if windowed:
            agg, self._agg = self._agg.result(), None
            if ob is not None:
                ob.slo_attainment(agg, self.tenants
                                  if self._tenanted else None)
            return FleetResult([], timing.camera_s, timing=timing,
                               aggregate=agg, served_cis=served_cis)
        streams = [RunResult(f"accmpeg_fleet[{i}]", per_stream[i])
                   for i in range(N)]
        return FleetResult(streams, timing.camera_s, timing=timing,
                           served_cis=served_cis, tenant_ids=tenant_ids)

    # -- the closed-loop churn serving loop ------------------------------------
    def serve_loop(self, frames, events=(), refs=None, initial=None,
                   net: Optional[NetworkConfig] = None, rescale: bool = True,
                   decide_every: int = 1,
                   owned: Optional[Sequence[int]] = None,
                   start_chunk: int = 0,
                   stop_chunk: Optional[int] = None,
                   state: Optional[dict] = None) -> FleetResult:
        """Closed-loop fleet serving under stream churn: scaling happens
        *inside* the loop, not between runs.

        ``frames`` is the (N_total, T, H, W, C) union of every camera
        that ever serves; its leading index is the stream id. ``initial``
        names the ids active at chunk 0 (default: all), and ``events``
        (``control.autoscaler.ChurnEvent``) join/leave streams at chunk
        boundaries. Per interval the loop:

        1. folds the interval's churn events into the active set,
        2. re-admits it through ``FleetAutoscaler.admit`` — active
           streams pad up to a power-of-two multiple of the mesh width,
           so the set of fleet programs ever compiled stays logarithmic
           in N_max while the lane mask (traced, never a constant)
           carries membership,
        3. dispatches the masked camera fleet step on the padded batch
           (padded lanes repeat the last real stream so every lane runs
           the identical program, but their wire bytes are zeroed
           in-program),
        4. scores + prices only active lanes: padding contributes
           exactly zero to accuracy, bytes, and delay aggregates, and
           the shared ``UplinkClock`` — which survives churn, backlog
           and all — sees zero-byte uploads for idle lanes,
        5. hands the interval's ``FleetTiming`` window to
           ``FleetAutoscaler.decide`` and adopts the ``ScaleDecision``
           (mesh width / buffer depth) between chunks via
           ``apply_scale`` — no engine teardown, no recompile for
           already-admitted shapes.

        ``admit(0)`` (everyone left) idles the interval: in-flight chunks
        drain, the uplink clock keeps ticking, and a later join resumes
        with the backlog the lull left behind. ``rescale=False`` pins the
        entered width/depth (admission still adapts the padded shape).
        ``decide_every`` spaces out scale decisions (1 = every interval,
        AIMD-style one notch each).

        ``owned`` declares this engine's stream ownership (multi-host
        serving: the host's shard of the fleet,
        ``repro.serve.fleet.FleetTopology``). Whenever the admitted
        active set reaches past it the loop raises a loud ``ValueError``
        instead of silently serving — and mis-accounting — another
        host's streams.

        Suspend/resume (elastic hosts, ``repro.serve.fleet``):
        ``start_chunk``/``stop_chunk`` bound the served interval range
        ``[start_chunk, stop_chunk)`` on the *global* chunk timeline —
        ``ci`` stays absolute, so the uplink clock's capture times and
        the churn schedule line up across a suspension. ``state`` imports
        a previous call's exported resume state; after every call the
        engine leaves its export in ``self.last_serve_state``: the uplink
        clock's backlog (``free_at_s``), the controller level, the
        windowed aggregator's accumulators, and the last decoded chunk of
        the active lanes (the adopting host's warm reference, restored
        against *its* mesh by the re-homing path). ``initial`` must
        already reflect the churn up to ``start_chunk`` — events at
        chunks before ``start_chunk`` are never re-applied.

        Returns a :class:`FleetResult` whose ``streams`` hold one
        ``RunResult`` per stream id that ever served (``stream_ids`` maps
        them back), plus the ``decisions`` and compiled-``shapes``
        trajectories."""
        from repro.control.autoscaler import (FleetAutoscaler, apply_churn,
                                              pad_streams)

        frames = np.asarray(frames)
        N_total, T = frames.shape[:2]
        cs = self.chunk_size
        starts = list(range(0, T - T % cs, cs))
        n_int = len(starts)
        stop = n_int if stop_chunk is None else int(stop_chunk)
        if not 0 <= start_chunk <= stop <= n_int:
            raise ValueError(
                f"serve window [{start_chunk}, {stop}) does not fit the "
                f"schedule's {n_int} intervals")
        events = tuple(events)
        for ev in events:
            if ev.chunk >= n_int:
                raise ValueError(f"churn event at chunk {ev.chunk} never "
                                 f"fires; schedule has {n_int} "
                                 f"intervals")
            for sid in ev.join + ev.leave:
                if not 0 <= sid < N_total:
                    raise ValueError(f"churn event names stream {sid}; "
                                     f"fleet has {N_total}")
        if self.autoscaler is None:
            self.autoscaler = FleetAutoscaler()
        scaler = self.autoscaler
        if self.mesh == "auto":
            # resolve once up front: under churn there is no fixed N to
            # divide, so take the widest power-of-two mesh (pow2 widths
            # compose with admit's pow2 lane buckets: any padded shape
            # stays divisible)
            from repro.distributed.mesh import make_stream_mesh
            from repro.distributed.sharding import host_local_devices

            n_dev = len(host_local_devices())
            width = 1 << (n_dev.bit_length() - 1)
            self.mesh = make_stream_mesh(width) if width > 1 else None
        active_ids = list(range(N_total)) if initial is None \
            else list(initial)
        if len(set(active_ids)) != len(active_ids):
            raise ValueError(f"duplicate stream ids in initial: "
                             f"{active_ids}")
        for sid in active_ids:
            if not 0 <= sid < N_total:
                raise ValueError(f"initial names stream {sid}; fleet "
                                 f"has {N_total}")
        owned_set = None if owned is None else frozenset(owned)
        net = net or self.net or NetworkConfig.shared(2.5e6,
                                                      max(N_total, 1))
        controlled = self.controller is not None
        if controlled:
            self.controller.reset()
        clock = None if self.trace is None else \
            UplinkClock(self.trace, cs, self.fps)
        refs = self._prepare_refs(refs)
        windowed = self.detail == "windowed"
        if windowed:
            self._agg = self._build_agg()
        # resume: the suspended run's serving state picks up where it
        # left off — clock backlog, controller level, aggregate window
        if state is not None:
            if clock is not None and state.get("clock_free_at_s") \
                    is not None:
                clock.free_at_s = float(state["clock_free_at_s"])
            if controlled and state.get("controller_level") is not None:
                self.controller.level = float(state["controller_level"])
            if windowed and state.get("agg") is not None:
                self._agg.import_state(state["agg"])
        use_dev = self._use_device_reduce(refs)
        per_stream: dict = {sid: [] for sid in range(N_total)}
        timing = FleetTiming()
        served_cis: List[int] = []
        last_dec = None  # (device decoded batch, n_active) of the last
        # served interval — exported as the resume state's warm reference
        self._obs = ob = _EngineObs() \
            if (obs_trace.enabled() or obs_metrics.enabled()) else None
        if ob is not None:
            t_call = time.perf_counter()
        decisions: List = []
        pending: List[dict] = []
        warm_s = 0.0  # per-shape compiles land mid-loop under churn;
        # excluded from wall_s so it stays comparable to run()'s
        t_run = time.perf_counter()
        for ci in range(start_chunk, stop):
            s = starts[ci]
            active_ids = apply_churn(active_ids, events, ci)
            if ob is not None:
                for ev in events:
                    if ev.chunk == ci and (ev.join or ev.leave):
                        ob.churn(ci, ev)
            if owned_set is not None:
                stray = sorted(sid for sid in active_ids
                               if sid not in owned_set)
                if stray:
                    raise ValueError(
                        f"admitted active set at chunk {ci} includes "
                        f"streams {stray} outside this engine's declared "
                        f"ownership {sorted(owned_set)}; route the "
                        f"schedule through repro.serve.fleet (or fix the "
                        f"FleetTopology) instead of silently mis-"
                        f"sharding another host's streams")
            plan = scaler.admit(len(active_ids),
                                mesh_width=self._mesh_width())
            if plan.n_padded == 0:
                # all-quiet interval: drain in-flight work; the uplink
                # clock keeps its backlog, ready for the next join
                while pending:
                    self._finish(pending.pop(0), per_stream, net, refs,
                                 timing, self.overlap, clock)
                continue
            depth = self.depth if self.overlap else 1
            cam_step, server_step, mesh = self._steps_for(plan.n_padded,
                                                          masked=True)
            programs = {"camera": cam_step, "server": server_step}
            sharding = stream_sharding(mesh) if mesh is not None else None
            mask_dev = self._put(plan.active, sharding)
            ids = list(active_ids)
            tids_dev = None
            t_counts = None
            if self._tenanted:
                # tenant ids ride as traced data beside the lane mask:
                # padded lanes route to tenant 0 and are masked exactly
                # like untenanted padding, so tenant-mix churn at a fixed
                # padded shape reuses the one compiled program
                tids_dev = self._put(
                    self._tenant_lane_ids(ids, plan.n_padded), sharding)
                server_step = (lambda d, _s=server_step, _t=tids_dev:
                               _s(d, _t))
                t_counts = self._tenant_counts(ids)
                if ob is not None:
                    ob.tenant_lanes(self.tenants, t_counts)

            def camera(batch, _cam=cam_step, _mask=mask_dev,
                       _tids=tids_dev):
                if _tids is not None:  # tenant-routed masked step
                    return _cam(batch, _tids, _mask)
                if controlled:  # traced knobs: fresh values, same program
                    return _cam(batch, _mask,
                                self.controller.knob_array())
                return _cam(batch, _mask)

            acc_step = self._acc_step_for(mesh) if use_dev else None
            if use_dev:
                programs["acc"] = acc_step
            if use_dev and self._tenanted:
                acc_step = (lambda o, r, _a=acc_step, _t=tids_dev:
                            _a(o, r, _t))
            if ob is not None:
                t_in = time.perf_counter()
            # advanced index + slice in one step: copies one chunk's
            # worth of frames, not each active stream's whole timeline
            batch_np = pad_streams(frames[ids, s : s + cs], plan.n_padded)
            warm_key = (batch_np.shape, mesh, refs is None, self.overlap,
                        controlled, use_dev, "masked")
            if warm_key in self._warm:  # hot shape: skip the warm put
                cam_steady_s, server_steady_s = self._warm[warm_key]
            else:
                t_warm = time.perf_counter()
                cam_steady_s, server_steady_s = self._steady_times(
                    camera, server_step, self._put(batch_np, sharding),
                    refs is None, self.overlap, warm_key,
                    acc_step=acc_step, programs=programs)
                # a cold shape's ingest span covers the put alone
                t_in = time.perf_counter()
                warm_s += t_in - t_warm

            host_before = len(timing.host_s)
            t_int = time.perf_counter()
            batch = self._put(batch_np, sharding)
            if ob is not None:
                ob.span("ingest", "ingest", t_in, ci=ci, bytes=batch.nbytes)
            knobs_used = self.controller.knobs() if controlled else None
            t0 = time.perf_counter()
            decoded, pbytes, _ = camera(batch)    # async dispatch
            if ob is not None:
                ob.span("dispatch_camera", "dispatch", t0, ci=ci)
            if self.overlap and len(pending) >= depth:
                self._finish(pending.pop(0), per_stream, net, refs,
                             timing, True, clock)
            if ob is not None:
                t_wait = time.perf_counter()
            jax.block_until_ready(decoded)
            cam_dt = cam_steady_s if self.overlap \
                else time.perf_counter() - t0
            timing.camera_s.append(cam_dt)
            served_cis.append(ci)
            last_dec = (decoded, len(ids))
            acct_dt = cam_dt if self.sim_encode_s is None \
                else self.sim_encode_s
            if ob is not None:
                ob.camera(ci, t0, t_wait, cam_dt, plan.n_padded, len(ids))
            t1 = time.perf_counter()
            outs = server_step(decoded)           # batched server DNN
            ref_outs = server_step(batch) if refs is None else None
            if use_dev:
                acc_dev = acc_step(outs, ref_outs)
                entry = dict(ci=ci, ids=ids, outs=None, ref_outs=None,
                             acc_dev=acc_dev)
            else:
                acc_dev = None
                entry = dict(ci=ci, ids=ids, outs=outs,
                             ref_outs=ref_outs)
            if ob is not None:
                # D(H) in the loop is a second pass over the frames
                passes = 1 if refs is not None else 2
                ob.dispatch_server(ci, t1, self.final_dnn,
                                   passes * decoded.shape[0] * cs)
            entry.update(pbytes=pbytes, cam_dt=acct_dt,
                         server_steady_s=server_steady_s,
                         knobs=knobs_used)
            pending.append(entry)
            if not self.overlap:
                if use_dev:
                    jax.block_until_ready(acc_dev)
                else:
                    jax.block_until_ready(jax.tree_util.tree_leaves(outs))
                    if ref_outs is not None:
                        jax.block_until_ready(
                            jax.tree_util.tree_leaves(ref_outs))
                srv_dt = time.perf_counter() - t1
                timing.server_s.append(srv_dt)
                if ob is not None:
                    ob.server(srv_dt, ci, t1)
                self._finish(pending.pop(0), per_stream, net, refs,
                             timing, False, clock)
            if rescale and (ci + 1) % max(decide_every, 1) == 0:
                # decide on the freshest interval window only — stale
                # occupancies from a different fleet shape would fight
                # the one-notch damping
                srv_est = server_steady_s if self.overlap \
                    else timing.server_s[-1]
                window = FleetTiming(
                    camera_s=[cam_dt], server_s=[srv_est],
                    host_s=list(timing.host_s[host_before:]),
                    wall_s=time.perf_counter() - t_int)
                tkw = ({"tenant_streams": t_counts}
                       if t_counts is not None else {})
                d = scaler.decide(window, plan.n_padded,
                                  mesh_width=self._mesh_width(),
                                  batch_depth=depth, **tkw)
                decisions.append(d)
                self.last_scale = d
                if (d.mesh_width, d.batch_depth) != (self._mesh_width(),
                                                     depth):
                    # adopt between chunks: drain what the new depth
                    # cannot keep in flight, then re-shape — compiled
                    # steps for already-seen (mesh, shape) pairs stay
                    while len(pending) >= max(d.batch_depth, 1):
                        self._finish(pending.pop(0), per_stream, net,
                                     refs, timing, self.overlap, clock)
                    self.apply_scale(d)
        while pending:
            self._finish(pending.pop(0), per_stream, net, refs, timing,
                         self.overlap, clock)
        timing.wall_s = time.perf_counter() - t_run - warm_s
        if ob is not None:
            ob.span("run", "events", t_call, intervals=len(served_cis),
                    streams=N_total)
        # export the resume state (see the docstring): whatever a
        # draining host must carry for its adopter to continue this run
        # bit-exactly from ``stop``
        if last_dec is not None:
            dec, n_act = last_dec
            last_decoded = np.asarray(dec)[:n_act]
        else:
            last_decoded = None
        agg_state = self._agg.export_state() if windowed else None
        self.last_serve_state = {
            "next_chunk": int(stop),
            "clock_free_at_s": None if clock is None
            else float(clock.free_at_s),
            "controller_level": None if not controlled
            else float(self.controller.level),
            "agg": agg_state,
            "last_decoded": last_decoded,
        }
        if windowed:
            agg, self._agg = self._agg.result(), None
            if ob is not None:
                ob.slo_attainment(agg, self.tenants
                                  if self._tenanted else None)
            return FleetResult([], timing.camera_s, timing=timing,
                               stream_ids=list(agg.stream_ids),
                               decisions=decisions,
                               shapes=list(scaler.compiled_shapes),
                               aggregate=agg, served_cis=served_cis)
        served = [sid for sid in sorted(per_stream) if per_stream[sid]]
        streams = [RunResult(f"accmpeg_churn[{sid}]", per_stream[sid])
                   for sid in served]
        return FleetResult(streams, timing.camera_s, timing=timing,
                           stream_ids=served, decisions=decisions,
                           shapes=list(scaler.compiled_shapes),
                           served_cis=served_cis,
                           tenant_ids=[self._tenant_idx(sid)
                                       for sid in served]
                           if self._tenanted else None)
