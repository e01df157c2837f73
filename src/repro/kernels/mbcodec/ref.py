"""Pure-jnp oracle for the fused macroblock codec kernel.

It computes the kernel's arithmetic in the kernel's order: the same
Kronecker transform at the same precision, the same quantization step
formula, and division by the step (not a reciprocal multiply), so that
interpret-mode Pallas and this oracle round every coefficient the same
way, ties included. It is jitted like the kernel, so XLA folds the
step formula's constants the same way in both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.codec.codec import BITS_PER_MAG, BLOCK_OVERHEAD, RUN_BITS
from repro.codec.dct import MB, dct_kron, freq_weight

_HI = jax.lax.Precision.HIGHEST


@jax.jit
def mbcodec_ref(blocks: jnp.ndarray, qp: jnp.ndarray):
    """blocks: (N, 16, 16) f32; qp: (N,) f32.

    Returns (reconstructed (N, 16, 16), bits (N,)).
    """
    n = blocks.shape[0]
    kf = jnp.asarray(dct_kron())
    w = jnp.asarray(freq_weight().reshape(MB * MB, 1))
    x = blocks.reshape(n, MB * MB).T                       # (256, N)
    c = jax.lax.dot_general(kf, x, (((1,), (0,)), ((), ())), precision=_HI)
    step = (0.625 * jnp.exp2((qp[None, :] - 4.0) / 6.0) / 255.0) * w
    q = jnp.round(c / step)
    aq = jnp.abs(q)
    bits = (BITS_PER_MAG * jnp.log2(1.0 + aq)
            + RUN_BITS * (aq > 0.5).astype(jnp.float32)).sum(axis=0) \
        + BLOCK_OVERHEAD
    rec = jax.lax.dot_general(kf, q * step, (((0,), (0,)), ((), ())),
                              precision=_HI)
    return rec.T.reshape(n, MB, MB), bits
