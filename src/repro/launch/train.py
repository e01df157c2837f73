"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm_360m \
        --reduced --steps 200 --mesh local

Fault tolerance in the loop (not just the library):
- auto-resume from the newest checkpoint (``--resume auto``)
- async atomic checkpoint every ``--ckpt-every`` steps + on SIGTERM/SIGINT
  (preemption-style shutdown saves before exiting)
- NaN/inf skip-step guard inside the jitted step (metrics report ``skipped``)
- per-step wall-time watchdog: steps slower than ``watchdog_factor`` x the
  trailing median are logged as straggler events (at fleet scale this feeds
  the scheduler; here it exercises the same code path)
- deterministic data: batch(step) is pure, so restart needs no replay
"""
from __future__ import annotations

import argparse
import json
import signal
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the arch's reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="local", choices=["local", "single", "multi"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--watchdog-factor", type=float, default=3.0)
    ap.add_argument("--compression", default=None,
                    choices=[None, "int8", "bf16"])
    args = ap.parse_args(argv)
    from repro.utils import enable_compile_cache

    enable_compile_cache()

    from repro.checkpoint.manager import CheckpointManager
    from repro.configs.base import get_config, get_reduced_config
    from repro.data.tokens import DataConfig, PrefetchingLoader
    from repro.distributed.sharding import Rules, named_tree
    from repro.launch.mesh import make_local_mesh, make_production_mesh
    from repro.models.transformer import build_model
    from repro.optim.adamw import AdamW, warmup_cosine
    from repro.train.steps import (batch_specs, init_train_state,
                                   make_train_step, train_state_specs)

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_local_mesh() if args.mesh == "local" else \
        make_production_mesh(multi_pod=(args.mesh == "multi"))
    rules = Rules(mesh, fsdp=cfg.fsdp,
                  manual_pod=bool(args.compression and "pod" in mesh.shape))
    model = build_model(cfg, rules,
                        compute_dtype=jnp.bfloat16 if args.mesh != "local"
                        else jnp.float32,
                        param_dtype=jnp.float32)
    opt = AdamW(schedule=warmup_cosine(args.lr, 20, args.steps),
                moment_dtype=jnp.dtype(cfg.opt_moment_dtype))

    ckpt_dir = args.ckpt_dir or f"experiments/ckpt/{args.arch}"
    mgr = CheckpointManager(ckpt_dir, keep=3)

    state_spec = train_state_specs(model, opt, rules)
    state_shardings = named_tree(rules, state_spec)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    start_step = 0
    if args.resume == "auto" and mgr.latest_step() is not None:
        state = mgr.restore(state, shardings=state_shardings)
        start_step = int(jax.device_get(state["step"]))
        print(f"[resume] restored step {start_step} from {ckpt_dir}",
              flush=True)

    step_fn = jax.jit(
        make_train_step(model, cfg, opt, rules, grad_accum=1,
                        compression=args.compression),
        in_shardings=(state_shardings, None),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )

    dcfg = DataConfig(cfg.vocab_size, args.seq, args.batch)
    loader = PrefetchingLoader(dcfg, start_step=start_step)

    stop = {"now": False}

    def on_signal(sig, frame):
        print(f"[signal] {sig}: checkpoint + exit", flush=True)
        stop["now"] = True

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    times = []
    metrics = {}
    for step, batch in loader:
        if step >= args.steps or stop["now"]:
            break
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        times.append(dt)
        if len(times) > 20:
            med = statistics.median(times[-20:])
            if dt > args.watchdog_factor * med and len(times) > 5:
                print(f"[straggler] step {step}: {dt:.3f}s vs median "
                      f"{med:.3f}s", flush=True)
        if step % args.log_every == 0:
            m = {k: float(jax.device_get(v)) for k, v in metrics.items()}
            print(f"step {step}: loss={m['nll']:.4f} gnorm={m['grad_norm']:.3f} "
                  f"lr={m['lr']:.2e} {dt*1000:.0f}ms", flush=True)
        if args.ckpt_every and step > 0 and step % args.ckpt_every == 0:
            mgr.save(step + 1, state)
    loader.close()
    final_step = int(jax.device_get(state["step"]))
    mgr.save(final_step, state)
    mgr.wait()
    if metrics:
        m = {k: float(jax.device_get(v)) for k, v in metrics.items()}
        print(f"[done] step {final_step} loss={m.get('nll', float('nan')):.4f} "
              f"ckpt={ckpt_dir}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
