"""Multi-process fleet launcher: N worker processes joined over
``jax.distributed`` with a CPU coordinator on ``127.0.0.1``.

This is a CPU check of the multi-host control plane, not a chip path:
the launcher pins ``JAX_PLATFORMS=cpu`` before its first JAX import, so
the parent (which serves the in-process reference) and every worker it
starts compute on the CPU, and none of them claims an accelerator that
one process must own. ``chip_smoke.py`` is the chip path.

The ``tests/_subproc.py`` pattern (fresh interpreters so the JAX backend
view is per-process), promoted into the package so CI's multihost-smoke
job, the test suite, and local experiments share one launcher. Each
worker gets the ``repro.distributed.multihost`` env contract
(``FLEET_COORD`` / ``FLEET_NPROCS`` / ``FLEET_PROC_ID``) and a prelude
that joins the distributed service *before* the first backend touch —
exactly what a real per-host deployment (k8s pod, systemd unit) would
do, minus the machines.

CLI::

    PYTHONPATH=src python -m repro.launch.fleet --smoke

runs the 2-process parity check end to end on CPU: a churned two-host
fleet served once in-process (single-process local fallback) and once as
two ``jax.distributed`` workers, asserting the global ``FleetResult``s
are bit-identical — accuracy, wire bytes, and (under the deterministic
``sim_encode_s`` accounting) every delay component.

The smoke serves the workers with the telemetry plane on (``REPRO_OBS=1``
exported to the gang) and the in-process reference *both* off and on —
so one run pins the parity check *and* the telemetry-on-vs-off
bit-identity guarantee. Worker 0 writes the cross-host merged Chrome
trace (``--trace-out``, Perfetto-loadable, one process lane per host)
and the gathered per-host metrics JSONL (``--metrics-out``); the driver
prints each host's per-stage time summary and reconciles the
``stage_seconds_total`` counters against ``FleetTiming``.
``--profile DIR`` additionally captures a ``jax.profiler`` device trace
per worker under ``DIR/host<k>``.
"""
from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # before any JAX import: see above

import argparse
import json
import socket
import subprocess
import sys
import textwrap
import time
from pathlib import Path
from typing import List, Optional

SRC = str(Path(__file__).resolve().parents[2])

#: worker-env contract for the smoke's telemetry outputs (set by the
#: driver, read by worker 0 in ``_smoke_obs_outputs``)
ENV_TRACE_OUT = "REPRO_OBS_TRACE_OUT"
ENV_METRICS_OUT = "REPRO_OBS_METRICS_OUT"
ENV_PROFILE_DIR = "REPRO_PROFILE_DIR"


def find_free_port(host: str = "127.0.0.1") -> int:
    s = socket.socket()
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


def worker_prelude(devices_per_proc: int = 1) -> str:
    """Python source every worker runs first: the CPU platform, optional
    host-forced device fan-out, src on sys.path, and the
    ``jax.distributed`` join from the launcher env."""
    force = ""
    if devices_per_proc > 1:
        force = (f'os.environ["XLA_FLAGS"] = '
                 f'"--xla_force_host_platform_device_count='
                 f'{devices_per_proc}"\n        ')
    return textwrap.dedent(f"""
        import os
        {force}os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, {SRC!r})
        import jax, jax.numpy as jnp, numpy as np
        jax.config.update("jax_enable_x64", False)
        from repro.distributed import multihost
        assert multihost.init_from_env(), "launcher env missing"
    """)


def launch_fleet(body: str, num_processes: int = 2,
                 devices_per_proc: int = 1, timeout: int = 900,
                 env: Optional[dict] = None,
                 stagger_s: Optional[dict] = None) -> List[str]:
    """Run ``body`` (dedented python source, after the prelude) in
    ``num_processes`` workers joined via ``jax.distributed``; returns
    each worker's stdout in process order.

    ``stagger_s`` maps process index -> spawn delay in seconds (elastic
    joiners arriving late: ``jax.distributed.initialize`` blocks the
    early arrivals until the whole gang connects, exactly like a real
    staggered rollout).

    Failure is loud and collective: any nonzero exit (or a hang past
    ``timeout`` — e.g. a worker waiting at a barrier its dead sibling
    never reaches) kills the whole gang and raises with the offending
    worker's output. A worker that *exits cleanly* early (rc 0 — the
    injected-kill fault in the elastic smoke uses ``os._exit(0)``) is
    not a failure."""
    import threading

    from repro.distributed.multihost import (ENV_COORD, ENV_NPROCS,
                                             ENV_PROC_ID)

    port = find_free_port()
    script = worker_prelude(devices_per_proc) + textwrap.dedent(body)
    procs = []
    for i in range(num_processes):
        if stagger_s and stagger_s.get(i):
            time.sleep(float(stagger_s[i]))
        e = dict(os.environ)
        e.update(env or {})
        e[ENV_COORD] = f"127.0.0.1:{port}"
        e[ENV_NPROCS] = str(num_processes)
        e[ENV_PROC_ID] = str(i)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script], env=e,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    # drain every worker's pipes concurrently: a sequential communicate()
    # on worker 0 would leave a chatty sibling blocked on a full OS pipe
    # buffer, unable to reach its next barrier — deadlocking the gang
    results = [None] * num_processes

    def _drain(i, p):
        results[i] = p.communicate()

    threads = [threading.Thread(target=_drain, args=(i, p), daemon=True)
               for i, p in enumerate(procs)]
    deadline = time.monotonic() + timeout
    for t in threads:
        t.start()
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    if any(t.is_alive() for t in threads):
        for p in procs:
            p.kill()
        for t in threads:
            t.join(10.0)
        raise RuntimeError(
            f"fleet worker hung past {timeout}s (a dead sibling leaves "
            f"survivors blocked at the next allgather); gang killed")
    outs, failures = [], []
    for i, (p, res) in enumerate(zip(procs, results)):
        out, err = res
        outs.append(out)
        if p.returncode != 0:
            failures.append(f"worker {i} rc={p.returncode}\n"
                            f"stdout:\n{out}\nstderr:\n{err[-4000:]}")
    if failures:
        raise RuntimeError("fleet launch failed:\n" + "\n".join(failures))
    return outs


# ---------------------------------------------------------------------------
# the 2-process parity smoke (CI: multihost-smoke job)
# ---------------------------------------------------------------------------
def _smoke_result():
    """Serve a small churned two-host fleet; returns the global
    :class:`repro.serve.fleet.FleetResult`.

    Deterministic by construction — seeded scenes, seeded model inits,
    ``sim_encode_s`` accounting, per-host constant traces — so the same
    data-path digest must come out of the single-process fallback and of
    every ``jax.distributed`` worker, bit for bit. Workers import and
    call this very function: one source of truth for what "the same
    run" means."""
    import jax
    import numpy as np

    from repro.control import ChurnEvent, FleetAutoscaler
    from repro.control.traces import constant_trace
    from repro.core.accmodel import AccModel, accmodel_init
    from repro.data.video import make_scene
    from repro.engine import EngineConfig, MultiStreamEngine
    from repro.serve.fleet import FleetTopology, serve_fleet
    from repro.vision.dnn import FinalDNN, init_net

    h, w, cs = 48, 64, 10
    dnn = FinalDNN("detection",
                   init_net("detection", jax.random.PRNGKey(0), width=8))
    am = AccModel(accmodel_init(jax.random.PRNGKey(1), 8))
    frames = np.stack([
        make_scene("dashcam", seed=40 + i, T=3 * cs, H=h, W=w).frames
        for i in range(4)])
    topology = FleetTopology(((0, 1), (2, 3)))

    def make_engine(host):
        # per-host uplink: each ingestion host carries its own trace
        return MultiStreamEngine(dnn, am, config=EngineConfig(
            impl="fast", chunk_size=cs,
            trace=constant_trace(1.5e5 * (host + 1), rtt_s=0.02),
            autoscaler=FleetAutoscaler(), sim_encode_s=0.05))

    return serve_fleet(
        make_engine, frames, topology,
        events=[ChurnEvent(1, leave=(1,)), ChurnEvent(2, join=(1,),
                                                      leave=(3,))])


def _smoke_digest(res=None) -> dict:
    """The data-path digest the parity assertions compare: everything a
    ``FleetResult`` carries except wall clocks (which can never be
    bit-identical across runs)."""
    if res is None:
        res = _smoke_result()
    return {
        "stream_ids": res.stream_ids,
        "hosts": res.hosts,
        "shapes": res.shapes,
        "chunks": [[c.ci, c.accuracy, c.bytes, c.encode_s, c.stream_s,
                    c.queue_s]
                   for run in res.streams for c in run.chunks],
    }


def _smoke_obs_outputs() -> Optional[dict]:
    """After a telemetry-enabled smoke serve: worker 0 writes the merged
    Chrome trace + gathered per-host metrics JSONL (paths from the
    driver's env contract), and every worker returns the per-host
    per-stage span summary. None when the telemetry plane was off."""
    from repro import obs
    from repro.distributed import multihost
    from repro.serve import fleet as fleet_mod

    gather = fleet_mod.LAST_OBS_GATHER
    if gather is None:
        return None
    span_payloads = [p["spans"] for p in gather
                     if p.get("spans") is not None]
    summary = obs.stage_summary(span_payloads)
    if multihost.exchange().host == 0:
        trace_out = os.environ.get(ENV_TRACE_OUT, "fleet_trace.json")
        metrics_out = os.environ.get(ENV_METRICS_OUT,
                                     "fleet_metrics.jsonl")
        with open(trace_out, "w") as f:
            json.dump(obs.merge_host_traces(span_payloads), f)
        ts = time.time()
        lines = [json.dumps({"host": p["host"], "unix_time": ts, **s},
                            sort_keys=True)
                 for p in gather for s in (p.get("metrics") or [])]
        with open(metrics_out, "w") as f:
            f.write("\n".join(lines) + ("\n" if lines else ""))
    return summary


_SMOKE_BODY = """
    import json, os
    from repro import obs
    obs.enable_from_env(host=jax.process_index())  # no-op sans REPRO_OBS
    from repro.launch.fleet import (ENV_PROFILE_DIR, _smoke_digest,
                                    _smoke_obs_outputs, _smoke_result)
    with obs.profile_region(os.environ.get(ENV_PROFILE_DIR),
                            host=jax.process_index()):
        res = _smoke_result()
    print("DIGEST " + json.dumps(_smoke_digest(res), sort_keys=True))
    summary = _smoke_obs_outputs()
    if summary is not None:
        print("OBSSUM " + json.dumps(summary, sort_keys=True))
"""


def _print_stage_table(summary: dict) -> None:
    """Per-host per-stage span-time table from ``obs.stage_summary``
    output (hosts/stages keyed by strings after the JSON round trip)."""
    print(f"{'host':>4} {'stage':<12} {'spans':>6} {'total_s':>9} "
          f"{'mean_s':>9} {'max_s':>9}")
    for host in sorted(summary, key=int):
        for stage, row in sorted(summary[host].items()):
            print(f"{host:>4} {stage:<12} {row['n']:>6} "
                  f"{row['total_s']:>9.4f} {row['mean_s']:>9.4f} "
                  f"{row['max_s']:>9.4f}")


def _reconcile_counters(res, registry) -> None:
    """The tentpole's books-balance check: the per-interval
    ``stage_seconds_total`` counters the engine hooks increment must sum
    to the same stage totals ``FleetTiming`` measures (float association
    aside — the counter adds across hosts in gather order)."""
    import numpy as np

    for stage, measured in (("camera", res.timing.camera_s),
                            ("server", res.timing.server_s),
                            ("host", res.timing.host_s)):
        c = registry.get("stage_seconds_total", stage=stage)
        assert c is not None, f"stage_seconds_total{{{stage}}} never fired"
        total = float(np.sum(measured))
        assert np.isclose(c.value, total, rtol=1e-9, atol=1e-12), (
            f"telemetry books don't balance: stage_seconds_total"
            f"{{stage={stage}}}={c.value} vs FleetTiming sum {total}")


# ---------------------------------------------------------------------------
# the elastic-membership smoke (drain-and-rehome + kill-one-host)
# ---------------------------------------------------------------------------
#: worker-env contract for the elastic smoke scenarios
ENV_ELASTIC_MODE = "REPRO_ELASTIC_MODE"
ENV_ELASTIC_CKPT = "REPRO_ELASTIC_CKPT"


def _elastic_smoke_result(mode: str, ckpt_dir: Optional[str]):
    """Serve one elastic scenario (or its uninterrupted reference).

    ``drain``: one host owns every stream, a second host joins mid-run
    (staggered spawn in the 2-process form) and adopts the whole shard
    when the first host drains at chunk 2 — planned handoff through a
    checkpoint, nothing re-served. ``fail``: the two-host churned fleet
    of ``_smoke_result``, with host 1 killed at chunk 2 *after*
    publishing its last segment but *before* checkpointing; host 0
    detects the death by exchange timeout and re-serves host 1's unit
    forward from the chunk-1 checkpoint (dedup by absolute ``ci``).
    ``<mode>_ref`` serves the identical schedule with a fixed host set —
    the bit-exactness reference. Adopted units keep their origin host's
    engine config (``make_engine(unit)``), which is what makes the
    post-rehome accounting bit-identical to the reference."""
    import jax
    import numpy as np

    from repro.control import ChurnEvent, FleetAutoscaler
    from repro.control.traces import constant_trace
    from repro.core.accmodel import AccModel, accmodel_init
    from repro.data.video import make_scene
    from repro.engine import EngineConfig, MultiStreamEngine
    from repro.serve.fleet import FleetTopology, HostEvent, serve_fleet
    from repro.vision.dnn import FinalDNN, init_net

    base = mode[: -len("_ref")] if mode.endswith("_ref") else mode
    if base not in ("drain", "fail"):
        raise ValueError(f"unknown elastic smoke mode {mode!r}")
    h, w, cs = 48, 64, 10
    dnn = FinalDNN("detection",
                   init_net("detection", jax.random.PRNGKey(0), width=8))
    am = AccModel(accmodel_init(jax.random.PRNGKey(1), 8))
    if base == "drain":
        T = 4 * cs
        topology = FleetTopology(((0, 1, 2, 3), ()))
        events = []
        host_events = [HostEvent(1, host=1, kind="join"),
                       HostEvent(2, host=0, kind="drain", adopter=1)]
        segment_every = None
    else:
        T = 3 * cs
        topology = FleetTopology(((0, 1), (2, 3)))
        events = [ChurnEvent(1, leave=(1,)),
                  ChurnEvent(2, join=(1,), leave=(3,))]
        host_events = [HostEvent(2, host=1, kind="fail", adopter=0)]
        segment_every = 1
    frames = np.stack([
        make_scene("dashcam", seed=40 + i, T=T, H=h, W=w).frames
        for i in range(4)])

    def make_engine(host):
        return MultiStreamEngine(dnn, am, config=EngineConfig(
            impl="fast", chunk_size=cs,
            trace=constant_trace(1.5e5 * (host + 1), rtt_s=0.02),
            autoscaler=FleetAutoscaler(), sim_encode_s=0.05))

    if mode.endswith("_ref"):
        return serve_fleet(make_engine, frames, topology, events=events)
    return serve_fleet(make_engine, frames, topology, events=events,
                       host_events=host_events, checkpoint_dir=ckpt_dir,
                       segment_every=segment_every, fail_timeout_s=10.0)


def _elastic_digest(res) -> dict:
    """Per-(stream, interval) accounting rows, sorted — the elastic
    parity digest. ``hosts``/``shapes`` are excluded on purpose: a
    re-homed stream legitimately reports its adopter, but its *chunk
    accounting* must be bit-identical to the uninterrupted reference.
    ``served_cis`` pins the no-lost-interval guarantee."""
    rows = []
    for sid, run in zip(res.stream_ids, res.streams):
        for c in run.chunks:
            rows.append([int(sid), int(c.ci), c.accuracy, c.bytes,
                         c.encode_s, c.stream_s, c.queue_s])
    rows.sort(key=lambda r: (r[0], r[1]))
    return {"stream_ids": list(res.stream_ids),
            "served_cis": list(res.served_cis or []),
            "chunks": rows}


_ELASTIC_BODY = """
    import json, os, sys
    from repro import obs
    obs.enable_from_env(host=jax.process_index())  # no-op sans REPRO_OBS
    from repro.launch.fleet import (ENV_ELASTIC_CKPT, ENV_ELASTIC_MODE,
                                    _elastic_digest, _elastic_smoke_result,
                                    _smoke_obs_outputs)
    mode = os.environ[ENV_ELASTIC_MODE]
    res = _elastic_smoke_result(mode, os.environ[ENV_ELASTIC_CKPT])
    print("DIGEST " + json.dumps(_elastic_digest(res), sort_keys=True))
    _smoke_obs_outputs()
    if mode == "fail":
        # the coordinator already lost a member; skip jax.distributed's
        # full-gang shutdown handshake, which would wait on the corpse
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)
"""


def elastic_smoke(kill_trace_out: str = "fleet_trace_kill.json") -> None:
    """The elastic-membership smoke: both scenarios must reproduce the
    uninterrupted reference's per-(stream, interval) accounting bit for
    bit, in the local fallback *and* as a real 2-process gang (staggered
    joiner for the drain, an injected ``os._exit`` kill for the fail).
    Worker 0 of the kill run leaves its merged Chrome trace behind for
    the CI artifact upload."""
    import tempfile

    from repro import obs

    for mode in ("drain", "fail"):
        ref = json.loads(json.dumps(
            _elastic_digest(_elastic_smoke_result(mode + "_ref", None)),
            sort_keys=True))
        with tempfile.TemporaryDirectory() as d:
            local = json.loads(json.dumps(
                _elastic_digest(_elastic_smoke_result(mode, d)),
                sort_keys=True))
        assert local == ref, (
            f"local {mode} scenario diverged from the uninterrupted "
            f"reference:\n{local}\n!=\n{ref}")
        env = {ENV_ELASTIC_MODE: mode}
        stagger = None
        if mode == "drain":
            stagger = {1: 1.0}  # the joiner arrives late
        else:
            env[obs.ENV_OBS] = "1"  # kill run leaves the trace artifact
            env[ENV_TRACE_OUT] = kill_trace_out
            env[ENV_METRICS_OUT] = kill_trace_out + ".metrics.jsonl"
        with tempfile.TemporaryDirectory() as d:
            env[ENV_ELASTIC_CKPT] = d
            outs = launch_fleet(_ELASTIC_BODY, num_processes=2,
                                timeout=600, env=env, stagger_s=stagger)
        for i, out in enumerate(outs):
            lines = [ln for ln in out.splitlines()
                     if ln.startswith("DIGEST ")]
            if mode == "fail" and i == 1:
                assert not lines, (
                    f"the killed worker should die before returning a "
                    f"merged result:\n{out}")
                continue
            assert lines, f"worker {i} printed no digest:\n{out}"
            d = json.loads(lines[-1][len("DIGEST "):])
            assert d == ref, (
                f"{mode}: worker {i} diverged from the uninterrupted "
                f"reference:\n{d}\n!=\n{ref}")
        n = len(ref["chunks"])
        verb = "drain-and-rehome handoff" if mode == "drain" \
            else "kill-one-host recovery"
        print(f"elastic-smoke OK [{mode}]: {verb} == uninterrupted "
              f"reference, bit-exact ({n} stream-chunks, served "
              f"intervals {ref['served_cis']})")
    assert os.path.exists(kill_trace_out), (
        f"kill-scenario worker 0 left no {kill_trace_out}")
    print(f"kill-scenario merged Chrome trace -> {kill_trace_out}")


def smoke(trace_out: str = "fleet_trace.json",
          metrics_out: str = "fleet_metrics.jsonl",
          profile: Optional[str] = None,
          kill_trace_out: str = "fleet_trace_kill.json") -> None:
    """The CI multihost-smoke: the 2-process ``jax.distributed`` serve
    (telemetry on) must match the single-process fallback bit-exactly —
    run both with the plane off and with it on, so the same assertion
    also pins telemetry-on-vs-off bit-identity. Worker 0 leaves the
    merged Chrome trace and metrics JSONL behind for the CI artifact
    upload."""
    from repro import obs

    reference = json.loads(json.dumps(_smoke_digest(), sort_keys=True))
    # same run again under the telemetry plane: identical digest, and
    # the counters the hooks kept must reconcile with FleetTiming
    obs.enable(host=0)
    try:
        res_on = _smoke_result()
        on_digest = json.loads(json.dumps(_smoke_digest(res_on),
                                          sort_keys=True))
        assert on_digest == reference, (
            "telemetry-on single-process run diverged from telemetry-off:"
            f"\n{on_digest}\n!=\n{reference}")
        _reconcile_counters(res_on, obs.get_metrics())
    finally:
        obs.disable()
    env = {obs.ENV_OBS: "1", ENV_TRACE_OUT: trace_out,
           ENV_METRICS_OUT: metrics_out}
    if profile:
        env[ENV_PROFILE_DIR] = profile
    outs = launch_fleet(_SMOKE_BODY, num_processes=2, timeout=600,
                        env=env)
    digests, summaries = [], []
    for i, out in enumerate(outs):
        lines = [ln for ln in out.splitlines() if ln.startswith("DIGEST ")]
        assert lines, f"worker {i} printed no digest:\n{out}"
        digests.append(json.loads(lines[-1][len("DIGEST "):]))
        obs_lines = [ln for ln in out.splitlines()
                     if ln.startswith("OBSSUM ")]
        assert obs_lines, f"worker {i} printed no span summary:\n{out}"
        summaries.append(json.loads(obs_lines[-1][len("OBSSUM "):]))
    for i, d in enumerate(digests):
        assert d == reference, (
            f"worker {i} global FleetResult diverged from the "
            f"single-process run:\n{d}\n!=\n{reference}")
    assert summaries[0] == summaries[1], (
        "workers disagree on the gathered span summary — the fleet_obs "
        f"allgather is not lockstep:\n{summaries[0]}\n!=\n{summaries[1]}")
    hosts_seen = sorted(summaries[0], key=int)
    assert hosts_seen == ["0", "1"], (
        f"merged telemetry covers hosts {hosts_seen}, expected both "
        f"workers' lanes")
    assert os.path.exists(trace_out), f"worker 0 left no {trace_out}"
    assert os.path.exists(metrics_out), f"worker 0 left no {metrics_out}"
    n_chunks = len(reference["chunks"])
    print(f"multihost-smoke OK: 2-process jax.distributed serve == "
          f"single-process fallback (telemetry off AND on), bit-exact "
          f"({n_chunks} stream-chunks, streams={reference['stream_ids']}, "
          f"hosts={reference['hosts']}, shapes={reference['shapes']})")
    print(f"merged Chrome trace -> {trace_out}; per-host metrics -> "
          f"{metrics_out}" + (f"; device profiles -> {profile}/host<k>"
                              if profile else ""))
    _print_stage_table(summaries[0])
    elastic_smoke(kill_trace_out=kill_trace_out)


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.fleet",
        description="multi-process fleet launcher / parity smoke")
    ap.add_argument("--smoke", action="store_true",
                    help="run the 2-process parity + telemetry smoke")
    ap.add_argument("--trace-out", default="fleet_trace.json",
                    help="merged Chrome trace path (smoke; worker 0 "
                         "writes it)")
    ap.add_argument("--metrics-out", default="fleet_metrics.jsonl",
                    help="gathered per-host metrics JSONL path (smoke)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture jax.profiler device traces per worker "
                         "under DIR/host<k>")
    ap.add_argument("--kill-trace-out", default="fleet_trace_kill.json",
                    help="merged Chrome trace path for the elastic "
                         "kill-one-host scenario (smoke)")
    args = ap.parse_args(sys.argv[1:] if argv is None else argv)
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        smoke(trace_out=args.trace_out, metrics_out=args.metrics_out,
              profile=args.profile, kill_trace_out=args.kill_trace_out)
        return
    ap.error("nothing to do (pass --smoke)")


if __name__ == "__main__":
    main()
