"""Gradient compression for the slow cross-pod links.

The paper's core insight — spend bits where a gradient says they matter —
reappears at fleet scale: cross-pod gradient all-reduce is the slowest
collective tier, so gradients crossing pods are quantized (int8 absmax per
tensor-block) before the reduction; a fp32 residual (error feedback) carries
the quantization error into the next step when enabled at the call site.

Used inside ``shard_map`` regions where the "pod" axis is manual.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import tree_map

BLOCK = 1024


def _quant(x):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, BLOCK)
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1, keepdims=True), 1e-12) / 127.0
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q, scale, shape):
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    return flat[: int(np.prod(shape))].reshape(shape)


def quantize_roundtrip(x):
    """Quantize/dequantize (error characterization in tests/benchmarks)."""
    q, s = _quant(x)
    return _dequant(q, s, x.shape)


def compressed_psum(grads, axis_name: str, method: str = "int8"):
    """All-reduce ``grads`` over a *manual* mesh axis with compression.

    int8: quantize -> psum int32 -> dequantize with summed scales (uses a
          shared max-scale so the sum stays exact in int32 range)
    bf16: cast to bf16 before the reduction (2x bytes saving)
    none: plain psum
    """
    n = jax.lax.axis_size(axis_name)

    if method == "none" or n == 1:
        return tree_map(lambda g: jax.lax.psum(g, axis_name), grads)

    if method == "bf16":
        return tree_map(
            lambda g: jax.lax.psum(g.astype(jnp.bfloat16), axis_name)
            .astype(jnp.float32),
            grads,
        )

    if method != "int8":
        raise ValueError(method)

    def one_clean(g):
        flat = g.astype(jnp.float32).reshape(-1)
        pad = (-flat.shape[0]) % BLOCK
        blocks = jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)
        scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1, keepdims=True),
                            1e-12) / 127.0
        scale = jax.lax.pmax(scale, axis_name)  # shared across pods
        q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int32)
        qsum = jax.lax.psum(q, axis_name)
        out = (qsum.astype(jnp.float32) * scale).reshape(-1)
        return out[: int(np.prod(g.shape))].reshape(g.shape)

    return tree_map(one_clean, grads)


def ef_compressed_psum(grads, residual, axis_name: str):
    """int8 compressed reduction with error feedback.

    Returns (reduced, new_residual): the local quantization error is carried
    into the next step's gradient, which provably preserves convergence for
    SGD-family optimizers.
    """
    def one(g, r):
        g = g.astype(jnp.float32) + r
        flat = g.reshape(-1)
        pad = (-flat.shape[0]) % BLOCK
        blocks = jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)
        scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1, keepdims=True),
                            1e-12) / 127.0
        scale = jax.lax.pmax(scale, axis_name)
        q = jnp.clip(jnp.round(blocks / scale), -127, 127)
        sent = (q * scale).reshape(-1)[: flat.shape[0]].reshape(g.shape)
        new_r = g - sent
        qsum = jax.lax.psum(q.astype(jnp.int32), axis_name)
        out = (qsum.astype(jnp.float32) * scale).reshape(-1)
        return out[: flat.shape[0]].reshape(g.shape), new_r

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = treedef.flatten_up_to(residual)
    outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    reduced = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    new_res = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    return reduced, new_res
