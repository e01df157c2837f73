"""Runs one cell once: set-up, the measured window, the comparison, and the
result line. Everything specific to a configuration, a traffic mix, a cell
or a metric is found by name in files of its own:

- ``BENCHMARK.json`` (the repository root): the cells and the metrics;
- ``configs/<config>.json``: the deployment's sizes and serving options;
- ``traffic/<traffic>.json``: the mix the generator (``load.py``) drives;
- ``cells/<cell>.json``: the cell's limits for the comparison;
- ``metrics/<metric>.py``: one reader per metric, ``read(ctx)`` returning
  the value or None where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import warnings
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry of BENCHMARK.json with its configuration, traffic
    and limits, and the metrics it reports."""
    spec = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    cell["cfg"] = read_json(HERE / "configs" / f"{cell['config']}.json")
    cell["traffic_mix"] = read_json(HERE / "traffic"
                                    / f"{cell['traffic']}.json")
    cell["limits"] = read_json(HERE / "cells" / f"{name}.json")["limits"]

    def mine(m):
        return name in m.get("workloads", [name])
    cell["end_to_end"] = [m for m in spec["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in spec["per_layer"] if mine(m)]
    return cell


def reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    cfg: dict
    traffic: dict
    chips: int
    n_streams: int
    window: object                  # load.Window
    setup_s: float
    memory_peak_bytes: int
    peak: object                    # peaks.Peak
    trace: Optional[object] = None  # trace_reduce.Trace
    lo: int = 0                     # traced window, trace ns
    hi: int = 0

    @property
    def stream_chunks(self) -> int:
        return self.n_streams * self.window.chunk_intervals


def configure_jax(cfg: dict, chips: int, require_chip: bool):
    """The configuration's precision and, on the chip, the compile cache
    inside the checkout and the chip check. Returns the devices."""
    import jax

    jax.config.update("jax_default_matmul_precision", cfg["precision"])
    devices = jax.devices()
    if require_chip:
        cache = ROOT / ".jax_cache"  # a fixed path: it is in the cache key
        cache.mkdir(exist_ok=True)  # JAX writes into it but does not make it
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
        jax.config.update("jax_compilation_cache_dir", str(cache))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # no eviction: its access-time files fail to write on some hosts,
        # and every later entry then fails to write with them
        jax.config.update("jax_compilation_cache_max_size", -1)
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: jax.devices()[0].platform is "
                         f"{devices[0].platform!r}")
        if len(devices) < chips:
            raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devices)}")
        # a kernel substituted off-TPU would hide the device
        warnings.filterwarnings("error", category=RuntimeWarning,
                                message=r"CHUNK_ENCODERS\[")
    return devices


def _memory_peak(devices) -> int:
    """Peak HBM of the fullest chip: the buffers (``peak_bytes_in_use``)
    plus the region the TPU runtime reserves for the programs' temporaries
    (``peak_bytes_reserved``), which ``peak_bytes_in_use`` leaves out."""
    def peak(d):
        stats = d.memory_stats() or {}
        return (stats.get("peak_bytes_in_use", 0)
                + stats.get("peak_bytes_reserved", 0))
    return int(max(peak(d) for d in devices))


def execute(cell: dict, seed: int, seconds: float, trace: bool,
            t_start: float, require_chip: bool = True, log=print) -> dict:
    """One run of ``cell``; returns the result object (its last key,
    ``checks``, holds every compared number beside its limit)."""
    import jax

    from chipbench import compare, load, peaks, system, trace_reduce

    cfg, mix, chips = cell["cfg"], cell["traffic_mix"], cell["chips"]
    devices = configure_jax(cfg, chips, require_chip)
    dev = devices[0]
    log(f"chipbench: {cell['name']} on {dev.platform} {dev.device_kind!r} "
        f"x{len(devices)}", file=sys.stderr)
    peak = peaks.peak_for(dev.device_kind) if require_chip else None
    n_streams = cfg["streams_per_chip"] * chips
    sysm = system.build(cfg, seed, n_streams, mix["pool_chunks"])
    load.warm_up(sysm, mix)
    window_seconds = min(seconds, mix["trace_seconds"]) if trace else seconds
    out_dir = ROOT / ".bench_out" / "trace" / cell["name"]
    if trace:
        shutil.rmtree(out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        # no host tracer: it records every block of the host's input
        # linearization (over a million events per 720p chunk), which
        # slows the window and fills the host's memory
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    try:
        window = load.drive(sysm, mix, window_seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    memory_peak = _memory_peak(devices[:max(chips, 1)])
    ctx = Context(cfg, mix, chips, n_streams, window, setup_s, memory_peak,
                  peak)
    if trace:
        paths = sorted(out_dir.glob("plugins/profile/*/*.xplane.pb"))
        tr = trace_reduce.load(str(paths[-1]))
        span = trace_reduce.window(tr)
        if span is not None:  # None: no program ran on a device
            ctx.trace, (ctx.lo, ctx.hi) = tr, span
    # the program's state goes before the reference runs
    sysm.engine = None
    gc.collect()

    ans, attempted, failed = compare.answers(window, mix["pool_chunks"],
                                             n_streams)
    ref = compare.reference_units(sysm, [u for a in ans for u in a.units])
    numbers = compare.gaps(ans, ref)
    limits = cell["limits"]
    correct = compare.judge(numbers, limits, failed)

    metrics = {}
    for m in (cell["per_layer"] if trace else cell["end_to_end"]):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if ctx.trace is not None:
        lo, hi, tr = ctx.lo, ctx.hi, ctx.trace
        device["busy_s"] = trace_reduce.busy_ns(tr, lo, hi) * 1e-9
        device["window_s"] = window.seconds
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace_reduce.top_ops(tr, lo,
                                                                   hi)],
            "idle_gaps": [[n, s] for n, s in trace_reduce.idle_gaps(tr, lo,
                                                                    hi)]}
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in limits}
    result["checks"]["failed"] = {"value": failed, "limit": 0}
    late = max(c.start_s - c.due_s for c in window.calls)
    log(f"generator: latest call start {late!r} s after its due time",
        file=sys.stderr)
    for line in compare.report(numbers, limits, failed):
        log(line, file=sys.stderr)
    return result
