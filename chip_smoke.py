#!/usr/bin/env python3
"""Chip smoke: drive the camera-to-server serving path once on a TPU at
1280x720 and check it against the same path on the host CPU.

    python chip_smoke.py             # one chip: N=4 720p dashcam streams
    python chip_smoke.py --chips 4   # the stream mesh across four chips

One chip: ``MultiStreamEngine`` with ``EngineConfig(impl="fused",
mesh="auto")`` serves N streams of three 10-frame chunks: AccModel
scoring and the fused Pallas codec kernel (camera fleet step), the
shared-uplink accounting, the batched width-32 detection DNN (server
fleet step) and the accuracy scoring against D(H). Weights are random
from fixed seeds; nothing is read from disk. The first chunk is then
recomputed on the host CPU backend with the ``fast`` encoder (the same
coefficient-space recursion as ``fused``) and compared.

``--chips 4`` runs only what spans chips: N=8 streams sharded over a
4-wide ``"stream"`` mesh, against the same streams on a 1-device mesh.

Everything runs in this one process, which holds the chip. The earlier
lines report versions, compile seconds, device memory and per-chunk
seconds; those seconds are smoke timings, not benchmark numbers. The
last line is one JSON object naming the device. Without an accelerator,
or without the repository beside it, the script exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Chip-vs-CPU tolerances. The chip's f32 matmuls (Precision.HIGHEST)
# and transcendental functions differ from the CPU's in the last bits,
# which can flip a coefficient that sits on a rounding tie by one
# quantization step. A few such flips per frame move its bytes by far
# less than 1%, the decoded pixels by well under 1e-3 on average, and a
# stream's detection F1 against D(H) by less than 0.01.
BYTES_RTOL = 0.01         # per-frame bytes, relative
DECODED_MEAN_ATOL = 1e-3  # mean |decoded_chip - decoded_cpu| per stream
ACC_ATOL = 0.01           # per-stream chunk accuracy vs D(H)


def _log(*args):
    print(*args, flush=True)


def _models(device=None):
    """The served DNN (detection, full default width 32) and the camera
    AccModel (width 16), from fixed seeds, on ``device``."""
    import jax

    from repro.core.accmodel import AccModel, accmodel_init
    from repro.vision.dnn import FinalDNN, init_net

    dnn_p = init_net("detection", jax.random.PRNGKey(0))
    am_p = accmodel_init(jax.random.PRNGKey(1))
    if device is not None:
        dnn_p, am_p = jax.device_put((dnn_p, am_p), device)
    return FinalDNN("detection", dnn_p), AccModel(am_p)


def _frames(n, T, H, W, seed0=0):
    import numpy as np

    from repro.data.video import make_scene

    return np.stack([make_scene("dashcam", seed=seed0 + i, T=T, H=H,
                                W=W).frames for i in range(n)])


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"argument": m.argument_size_in_bytes,
            "output": m.output_size_in_bytes,
            "temp": m.temp_size_in_bytes}


def _compile_steps(am, dnn, qcfg, impl, n, cs, H, W):
    """AOT-compile the camera and server fleet steps for an n-stream
    chunk on the engine's ``mesh="auto"``; returns them with their
    compile seconds and memory analyses."""
    import jax
    import jax.numpy as jnp

    from repro.distributed.mesh import stream_mesh_for
    from repro.serve.steps import (make_camera_fleet_step,
                                   make_server_fleet_step, stream_sharding)

    mesh = stream_mesh_for(n)
    spec = jax.ShapeDtypeStruct((n, cs, H, W, 3), jnp.float32,
                                sharding=stream_sharding(mesh))
    out = {"mesh": mesh}
    for name, step in (
            ("camera", make_camera_fleet_step(am, qcfg, impl=impl,
                                              mesh=mesh)),
            ("server", make_server_fleet_step(dnn, mesh=mesh))):
        t0 = time.perf_counter()
        out[name] = step.lower(spec).compile()
        out[name + "_compile_s"] = time.perf_counter() - t0
        out[name + "_memory"] = _memory(out[name])
    return out


def _fits(steps, depth) -> bool:
    """Whether the steps' compiled footprint fits the device: the larger
    step's temporaries plus ``depth + 1`` chunks and decoded outputs in
    flight, under the device's byte limit (True where the backend does
    not report one)."""
    import jax

    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return True
    cam, srv = steps["camera_memory"], steps["server_memory"]
    need = (max(cam["temp"], srv["temp"])
            + (depth + 1) * (cam["argument"] + cam["output"]))
    return need <= stats["bytes_limit"]


def _gaps(pb_chip, pb_ref, dec_chip, dec_ref, acc_chip, acc_ref) -> dict:
    import numpy as np

    pb_chip, pb_ref = np.asarray(pb_chip), np.asarray(pb_ref)
    dec = np.abs(np.asarray(dec_chip) - np.asarray(dec_ref))
    return {
        "bytes_rel_max": float(np.max(np.abs(pb_chip - pb_ref)
                                      / np.maximum(pb_ref, 1e-9))),
        "decoded_mean_abs_max": float(
            dec.reshape(dec.shape[0], -1).mean(axis=1).max()),
        "decoded_abs_max": float(dec.max()),
        "accuracy_abs_max": float(np.max(np.abs(np.asarray(acc_chip)
                                                - np.asarray(acc_ref)))),
    }


def _check(gaps: dict, what: str) -> None:
    for key, tol in (("bytes_rel_max", BYTES_RTOL),
                     ("decoded_mean_abs_max", DECODED_MEAN_ATOL),
                     ("accuracy_abs_max", ACC_ATOL)):
        if not gaps[key] <= tol:
            raise AssertionError(f"{what}: {key}={gaps[key]!r} exceeds "
                                 f"its tolerance {tol}")


def _cpu_reference(frames0, cs):
    """Chunk-0 reference on the host CPU backend: the ``fast`` camera
    step's decoded frames and per-frame bytes, and the engine's
    per-stream accuracy against D(H) computed there too."""
    import jax
    import jax.numpy as jnp

    from repro.core.pipeline import make_reference
    from repro.core.quality import QualityConfig
    from repro.engine import EngineConfig, MultiStreamEngine
    from repro.serve.steps import make_camera_fleet_step

    cpu = jax.devices("cpu")[0]
    dnn, am = _models(cpu)
    with jax.default_device(cpu):
        dec, pb, _ = make_camera_fleet_step(am, QualityConfig(),
                                            impl="fast")(
            jnp.asarray(frames0))
        refs = [make_reference(f, dnn, chunk_size=cs) for f in frames0]
        res = MultiStreamEngine(dnn, am, config=EngineConfig(
            chunk_size=cs, impl="fast")).run(frames0, refs=refs)
        acc = [s.chunks[0].accuracy for s in res.streams]
        return jax.device_get((dec, pb)) + (acc,)


def run_smoke(H: int = 720, W: int = 1280, n_streams: int = 4,
              chunks: int = 3, chunk_size: int = 10,
              impl: str = "fused", log=_log) -> dict:
    """Serve ``n_streams`` dashcam streams through ``MultiStreamEngine``
    on the default device, compare the first chunk with the host CPU,
    and return what the script prints. Raises when a tolerance fails."""
    import jax
    import numpy as np

    from repro.core.pipeline import make_reference
    from repro.core.quality import QualityConfig
    from repro.engine import EngineConfig, MultiStreamEngine
    from repro.serve.steps import stream_sharding

    dev = jax.devices()[0]
    cs, qcfg = chunk_size, QualityConfig()
    config = EngineConfig(qcfg=qcfg, chunk_size=cs, impl=impl, mesh="auto")
    dnn, am = _models()
    log(f"weights: detection DNN width 32 (seed 0), AccModel width 16 "
        f"(seed 1); frames {H}x{W}, chunk {cs}, {chunks} chunks, "
        f"impl={impl}, mesh=auto, overlap depth {config.depth}, "
        f"device_reduce={config.device_reduce} (it applies to windowed "
        f"segmentation/keypoint runs; detection F1 is scored on the host)")

    n = n_streams
    while True:  # the most streams, up to n_streams, whose steps fit
        steps = _compile_steps(am, dnn, qcfg, impl, n, cs, H, W)
        log(f"N={n}: camera step compile {steps['camera_compile_s']:.2f} "
            f"s, memory {steps['camera_memory']}; server step compile "
            f"{steps['server_compile_s']:.2f} s, memory "
            f"{steps['server_memory']}")
        if _fits(steps, config.depth) or n == 1:
            break
        n //= 2
    log(f"N={n} streams fit")

    frames = _frames(n, chunks * cs, H, W)
    t0 = time.perf_counter()
    refs = [make_reference(f, dnn, chunk_size=cs) for f in frames]
    log(f"D(H) references: {time.perf_counter() - t0:.2f} s")
    eng = MultiStreamEngine(dnn, am, config=config)
    t0 = time.perf_counter()
    res = eng.run(frames, refs=refs)
    run_s = time.perf_counter() - t0
    timing = res.timing
    log(f"smoke timings, not benchmark numbers: run {run_s:.2f} s "
        f"(warm-up compiles included); steady camera step "
        f"{res.camera_s[0]:.4f} s/chunk, steady server step "
        f"{timing.server_s[0]:.4f} s/chunk, host "
        f"{[round(h, 4) for h in timing.host_s]} s, loop wall "
        f"{timing.wall_s:.3f} s for {len(res.camera_s)} chunks")
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"device peak_bytes_in_use: {peak}")

    # chunk 0 on the chip through the compiled camera step, then on CPU
    frames0 = frames[:, :cs]
    dec, pb, _ = steps["camera"](
        jax.device_put(frames0, stream_sharding(steps["mesh"])))
    dec, pb = jax.device_get((dec, pb))
    acc = [s.chunks[0].accuracy for s in res.streams]
    dec_ref, pb_ref, acc_ref = _cpu_reference(frames0, cs)
    gaps = _gaps(pb, pb_ref, dec, dec_ref, acc, acc_ref)
    log(f"chip vs CPU (chunk 0): {gaps}; tolerances bytes_rel "
        f"{BYTES_RTOL}, decoded_mean_abs {DECODED_MEAN_ATOL}, accuracy "
        f"{ACC_ATOL}")
    log(f"accuracy vs D(H), chunk 0: chip {np.round(acc, 4).tolist()} "
        f"cpu {np.round(acc_ref, 4).tolist()}")
    _check(gaps, "chip vs CPU")
    return {
        "n_streams": n,
        "frames": [H, W],
        "chunks": len(res.camera_s),
        "compile_s": {"camera": steps["camera_compile_s"],
                      "server": steps["server_compile_s"]},
        "memory": {"camera": steps["camera_memory"],
                   "server": steps["server_memory"]},
        "peak_bytes_in_use": peak,
        "camera_s": list(res.camera_s),
        "server_s": list(timing.server_s),
        "wall_s": timing.wall_s,
        "accuracy": acc,
        "gaps": gaps,
    }


def run_mesh_check(H: int = 720, W: int = 1280, n_streams: int = 8,
                   chunk_size: int = 10, group: int = 4,
                   impl: str = "fused", log=_log) -> dict:
    """The stream mesh across every local device against a 1-device
    mesh. The 1-device side serves the streams ``group`` at a time (what
    one chip holds at 720p); streams are independent, so bytes, decoded
    frames and accuracy per stream are the same run's."""
    import jax
    import numpy as np

    from repro.core.pipeline import make_reference
    from repro.core.quality import QualityConfig
    from repro.distributed.mesh import make_local_stream_mesh, stream_mesh_for
    from repro.engine import EngineConfig, MultiStreamEngine
    from repro.serve.steps import make_camera_fleet_step, stream_sharding

    cs, qcfg = chunk_size, QualityConfig()
    dnn, am = _models()
    frames = _frames(n_streams, cs, H, W)
    refs = [make_reference(f, dnn, chunk_size=cs) for f in frames]

    def server(mesh):
        camera = make_camera_fleet_step(am, qcfg, impl=impl, mesh=mesh)
        eng = MultiStreamEngine(dnn, am, config=EngineConfig(
            qcfg=qcfg, chunk_size=cs, impl=impl, mesh=mesh))

        def serve(sl):
            f = frames[sl]
            dec, pb, _ = camera(jax.device_put(f, stream_sharding(mesh)))
            res = eng.run(f, refs=refs[sl])
            return dec, pb, [s.chunks[0].accuracy for s in res.streams]
        return serve

    wide = stream_mesh_for(n_streams)
    t0 = time.perf_counter()
    dec_w, pb_w, acc_w = server(wide)(slice(None))
    devices = len(dec_w.sharding.device_set)
    log(f"{wide.devices.size}-wide stream mesh: decoded sharding spans "
        f"{devices} devices ({time.perf_counter() - t0:.2f} s smoke "
        f"timing, compiles included)")
    dec_w, pb_w = jax.device_get((dec_w, pb_w))
    serve_one = server(make_local_stream_mesh())
    parts = [serve_one(slice(g, g + group))
             for g in range(0, n_streams, group)]
    dec_1 = np.concatenate([jax.device_get(p[0]) for p in parts])
    pb_1 = np.concatenate([jax.device_get(p[1]) for p in parts])
    acc_1 = [a for p in parts for a in p[2]]
    gaps = _gaps(pb_w, pb_1, dec_w, dec_1, acc_w, acc_1)
    log(f"mesh vs 1-device mesh: {gaps}")
    if devices != len(jax.devices()):
        raise AssertionError(f"decoded output spans {devices} devices, "
                             f"expected {len(jax.devices())}")
    _check(gaps, "stream mesh vs 1-device mesh")
    return {"n_streams": n_streams, "devices": devices, "gaps": gaps,
            "accuracy": acc_w}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path on one chip; 4: only the "
                         "stream mesh across four chips")
    args = ap.parse_args(argv)
    # the CPU reference needs the host backend beside the accelerator
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform="
              f"{dev.platform!r})", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.codec.codec import CHUNK_ENCODERS
    from repro.utils import enable_compile_cache

    _log(f"jax {jax.__version__}, device_kind {dev.device_kind!r}, "
         f"{len(jax.devices())} device(s), compile cache "
         f"{enable_compile_cache()}")
    # a substituted encoder would hide the device: make it an error
    warnings.filterwarnings("error", category=RuntimeWarning,
                            message=r"CHUNK_ENCODERS\[")
    if not CHUNK_ENCODERS.describe("fused")["native"]:
        raise AssertionError("the fused encoder is not native here")
    # hold the chip to the CPU's f32 arithmetic in every matmul and
    # convolution, so the comparison sees only rounding ties
    jax.config.update("jax_default_matmul_precision", "highest")
    if args.chips == 4:
        run_mesh_check()
    else:
        run_smoke()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
