"""Multi-pod dry-run driver.

For every (architecture x input-shape x mesh) cell: lower + compile the
step on the production mesh (single pod 16x16 = 256 chips, multi-pod
2x16x16 = 512 chips) with ShapeDtypeStruct inputs (no allocation), then
record memory_analysis / cost_analysis / loop-aware roofline terms.

Usage:
  python -m repro.launch.dryrun                      # full sweep (subprocess per cell)
  python -m repro.launch.dryrun --arch yi_34b --shape train_4k --mesh single
  python -m repro.launch.dryrun --skip-existing      # resume an interrupted sweep
"""
# The VERY FIRST lines — before ANY other import — force 512 host devices
# on the CPU backend; jax locks the device count on first backend init.
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

DEFAULT_OUT = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             variant: str = "base") -> dict:
    import jax

    from repro.configs.base import SHAPES, cell_applicable, get_config
    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_cell, model_flops

    cfg = get_config(arch)
    if variant == "kvint8":  # beyond-paper: quantized KV cache (§Perf)
        import dataclasses

        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "variant": variant, "status": "skipped", "skip_reason": why,
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{arch}__{shape_name}__{mesh_kind}"
    if variant != "base":
        name += f"__{variant}"
    if not ok:
        (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=2))
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh, cfg=cfg)
    donate = ()
    if shape.kind == "decode":
        donate = (1,)  # cache buffers alias in/out (halves decode peak)
    elif shape.kind == "train":
        donate = (0,)  # train state
    lowered = jax.jit(
        cell.fn, in_shardings=cell.in_shardings,
        out_shardings=cell.out_shardings, donate_argnums=donate,
    ).lower(*cell.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    print(f"[{arch}/{shape_name}/{mesh_kind}] memory_analysis: {mem}",
          flush=True)  # proves it fits
    print(f"[{arch}/{shape_name}/{mesh_kind}] cost_analysis: "
          f"flops={cost.get('flops')} bytes={cost.get('bytes accessed')} "
          f"(loop bodies counted once — loop-aware totals in the JSON)",
          flush=True)
    text = compiled.as_text()
    ana = hlo_analysis.analyze(text)
    terms = hlo_analysis.roofline_terms(ana)
    mf = model_flops(cfg, shape)
    n_dev = mesh.devices.size
    flops_global = ana["dot_flops"] * n_dev
    # grad-accum reshapes mean per-step tokens == shape.tokens regardless
    rec.update({
        "status": "ok",
        "n_devices": n_dev,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_bytes": (
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes
            ),
        },
        "cost_analysis": {
            "flops_loopbody_once": cost.get("flops"),
            "bytes_accessed_loopbody_once": cost.get("bytes accessed"),
        },
        "analysis": ana,
        "roofline": terms,
        "model_flops_global": mf,
        "hlo_flops_global": flops_global,
        "model_to_hlo_flops": (mf / flops_global) if flops_global else None,
        "meta": cell.meta,
    })
    (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=2))
    return rec


def enumerate_cells(archs, shapes, meshes):
    from repro.configs.base import all_arch_ids

    archs = all_arch_ids() if archs == ["all"] else archs
    shapes = ["train_4k", "prefill_32k", "decode_32k", "long_500k"] \
        if shapes == ["all"] else shapes
    meshes = ["single", "multi"] if meshes == ["all"] else meshes
    for a in archs:
        for s in shapes:
            for m in meshes:
                yield a, s, m


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="+", default=["all"])
    ap.add_argument("--shape", nargs="+", default=["all"])
    ap.add_argument("--mesh", nargs="+", default=["all"])
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--variant", default="base")
    ap.add_argument("--in-process", action="store_true",
                    help="run cells in this process (default: subprocess per "
                         "cell for isolation — a compiler crash must not kill "
                         "the sweep)")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args()
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    out_dir = Path(args.out)

    cells = list(enumerate_cells(args.arch, args.shape, args.mesh))
    single = len(cells) == 1
    failures = 0
    for arch, shape, mesh in cells:
        name = f"{arch}__{shape}__{mesh}"
        if args.variant != "base":
            name += f"__{args.variant}"
        path = out_dir / f"{name}.json"
        if args.skip_existing and path.exists():
            st = json.loads(path.read_text()).get("status")
            if st in ("ok", "skipped"):
                print(f"[skip-existing] {name}: {st}", flush=True)
                continue
        if args.in_process or single:
            try:
                rec = run_cell(arch, shape, mesh, out_dir, args.variant)
            except Exception as e:  # record the failure, keep sweeping
                rec = {"arch": arch, "shape": shape, "mesh": mesh,
                       "variant": args.variant, "status": "error",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
                out_dir.mkdir(parents=True, exist_ok=True)
                path.write_text(json.dumps(rec, indent=2))
        else:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh,
                   "--out", str(out_dir), "--variant", args.variant]
            t0 = time.time()
            try:
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=args.timeout)
                if r.returncode != 0 and not path.exists():
                    rec = {"arch": arch, "shape": shape, "mesh": mesh,
                           "variant": args.variant, "status": "error",
                           "error": r.stderr[-4000:]}
                    out_dir.mkdir(parents=True, exist_ok=True)
                    path.write_text(json.dumps(rec, indent=2))
            except subprocess.TimeoutExpired:
                rec = {"arch": arch, "shape": shape, "mesh": mesh,
                       "variant": args.variant, "status": "timeout",
                       "timeout_s": args.timeout}
                out_dir.mkdir(parents=True, exist_ok=True)
                path.write_text(json.dumps(rec, indent=2))
            rec = json.loads(path.read_text()) if path.exists() else rec
        st = rec.get("status")
        if st == "ok":
            rl = rec["roofline"]
            print(f"[{st}] {name}: compile={rec['compile_s']}s "
                  f"peak={rec['memory']['peak_per_device_bytes']/2**30:.2f}GiB "
                  f"bottleneck={rl['bottleneck']} "
                  f"(c={rl['compute_s']:.4f}s m={rl['memory_s']:.4f}s "
                  f"coll={rl['collective_s']:.4f}s)", flush=True)
        else:
            failures += st in ("error", "timeout")
            print(f"[{st}] {name}: {rec.get('skip_reason') or rec.get('error', '')[:300]}",
                  flush=True)
    if failures:
        print(f"{failures} cell(s) failed", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
