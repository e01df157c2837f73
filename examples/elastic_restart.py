"""Elastic fault tolerance demo: train, SIGTERM mid-run (simulated
preemption), then resume the same checkpoint on a DIFFERENT mesh topology.

    PYTHONPATH=src python examples/elastic_restart.py

Phase 1 trains on a single device and checkpoints. Phase 2 re-launches in a
subprocess with 8 forced host devices, restores the same checkpoint onto a
(2, 4) mesh (the CheckpointManager re-shards arrays with jax.device_put
against the new NamedShardings), and continues training — the loss picks up
where it left off. Both phases run on the CPU backend (phase 2's eight
devices are host-forced), so the demo never holds an accelerator that its
child process would then need.
"""
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

CKPT = ROOT / "experiments" / "ckpt" / "elastic_demo"

PHASE2 = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import get_reduced_config
from repro.data.tokens import DataConfig, batch_at
from repro.distributed.mesh import make_mesh
from repro.distributed.sharding import Rules, named_tree
from repro.models.transformer import build_model
from repro.optim.adamw import AdamW, warmup_cosine
from repro.train.steps import (init_train_state, make_train_step,
                               train_state_specs)

cfg = get_reduced_config("smollm_360m")
mesh = make_mesh((2, 4), ("data", "model"))   # DIFFERENT topology
rules = Rules(mesh)
model = build_model(cfg, rules, compute_dtype=jnp.float32,
                    param_dtype=jnp.float32)
opt = AdamW(schedule=warmup_cosine(1e-3, 10, 60))
mgr = CheckpointManager({ckpt!r})
state = init_train_state(model, opt, jax.random.PRNGKey(0))
shardings = named_tree(rules, train_state_specs(model, opt, rules))
state = mgr.restore(state, shardings=shardings)
start = int(jax.device_get(state["step"]))
print(f"[phase2] resumed step {{start}} on mesh {{dict(mesh.shape)}}")
step_fn = jax.jit(make_train_step(model, cfg, opt, rules),
                  in_shardings=(shardings, None),
                  out_shardings=(shardings, None))
dcfg = DataConfig(cfg.vocab_size, 64, 8)
for s in range(start, start + 10):
    batch = {{k: jnp.asarray(v) for k, v in batch_at(dcfg, s).items()}}
    state, metrics = step_fn(state, batch)
print(f"[phase2] step {{int(jax.device_get(state['step']))}} "
      f"loss={{float(jax.device_get(metrics['nll'])):.4f}} — elastic resume OK")
"""


def main():
    import jax
    import jax.numpy as jnp

    from repro.checkpoint.manager import CheckpointManager
    from repro.configs.base import get_reduced_config
    from repro.data.tokens import DataConfig, batch_at
    from repro.distributed.sharding import local_rules
    from repro.models.transformer import build_model
    from repro.optim.adamw import AdamW, warmup_cosine
    from repro.train.steps import init_train_state, make_train_step

    shutil.rmtree(CKPT, ignore_errors=True)
    cfg = get_reduced_config("smollm_360m")
    rules = local_rules()
    model = build_model(cfg, rules, compute_dtype=jnp.float32,
                        param_dtype=jnp.float32)
    opt = AdamW(schedule=warmup_cosine(1e-3, 10, 60))
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(model, cfg, opt, rules))
    dcfg = DataConfig(cfg.vocab_size, 64, 8)
    mgr = CheckpointManager(CKPT, async_save=True)
    print("[phase1] training on 1 device…")
    for s in range(12):
        batch = {k: jnp.asarray(v) for k, v in batch_at(dcfg, s).items()}
        state, metrics = step_fn(state, batch)
    print(f"[phase1] step 12 loss="
          f"{float(jax.device_get(metrics['nll'])):.4f}; checkpoint + 'preempt'")
    mgr.save(12, state)
    mgr.wait()

    script = PHASE2.format(src=str(ROOT / "src"), ckpt=str(CKPT))
    r = subprocess.run([sys.executable, "-c", script], text=True)
    sys.exit(r.returncode)


if __name__ == "__main__":
    main()
