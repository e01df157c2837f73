"""16x16 macroblock DCT transform, TPU-adapted.

H.264 applies 4x4/8x8 integer transforms inside 16x16 macroblocks — shapes
hostile to a 128x128 MXU. We lift the transform to a single 16x16 DCT-II
per macroblock expressed as two dense matmuls ``D @ X @ D.T`` and batch
macroblocks along the leading dim so the MXU sees large GEMMs (the Pallas
codec kernel runs the same transform as one Kronecker matmul, see
:func:`dct_kron`). The codec is therefore H.264-*shaped* (QP semantics,
macroblock RoI, I/P frames), not bit-exact H.264.

Transforms run at ``Precision.HIGHEST``: on the TPU an f32 matmul
otherwise takes a single bf16 pass, and the coefficients are rounded to
integers right after, so the chip must compute the CPU's f32 transform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MB = 16  # macroblock size (pixels)


@functools.lru_cache()
def dct_matrix(n: int = MB) -> np.ndarray:
    """Orthonormal DCT-II matrix (n x n)."""
    k = np.arange(n)[:, None]
    i = np.arange(n)[None, :]
    d = np.cos(np.pi * (2 * i + 1) * k / (2 * n)) * np.sqrt(2.0 / n)
    d[0] /= np.sqrt(2.0)
    return d.astype(np.float32)


@functools.lru_cache()
def dct_kron(n: int = MB) -> np.ndarray:
    """``kron(D, D)`` (n*n x n*n): the 2-D DCT of a row-major flattened
    block, ``dct_kron() @ x.reshape(-1) == (D @ x @ D.T).reshape(-1)``;
    its transpose is the inverse transform."""
    d = dct_matrix(n)
    return np.kron(d, d).astype(np.float32)


@functools.lru_cache()
def freq_weight(n: int = MB) -> np.ndarray:
    """Mild high-frequency quantization ramp (JPEG-flavoured)."""
    k = np.arange(n, dtype=np.float32)
    w = 1.0 + (k[:, None] + k[None, :]) / (2.0 * (n - 1))  # 1 .. 2
    return w.astype(np.float32)


def blockify(img: jnp.ndarray) -> jnp.ndarray:
    """(H, W, C) -> (H/16 * W/16, C, 16, 16)."""
    H, W, C = img.shape
    x = img.reshape(H // MB, MB, W // MB, MB, C)
    return x.transpose(0, 2, 4, 1, 3).reshape(-1, C, MB, MB)


def unblockify(blocks: jnp.ndarray, H: int, W: int) -> jnp.ndarray:
    """inverse of blockify."""
    C = blocks.shape[1]
    x = blocks.reshape(H // MB, W // MB, C, MB, MB)
    return x.transpose(0, 3, 1, 4, 2).reshape(H, W, C)


def dct2(blocks: jnp.ndarray) -> jnp.ndarray:
    """blocks (..., 16, 16) -> coefficients."""
    d = jnp.asarray(dct_matrix())
    return jnp.einsum("ij,...jk,lk->...il", d, blocks, d,
                      precision=jax.lax.Precision.HIGHEST)


def idct2(coefs: jnp.ndarray) -> jnp.ndarray:
    d = jnp.asarray(dct_matrix())
    return jnp.einsum("ji,...jk,kl->...il", d, coefs, d,
                      precision=jax.lax.Precision.HIGHEST)


def qstep(qp) -> jnp.ndarray:
    """H.264 quantization step for pixel range [0, 1]:
    Qstep(QP) = 0.625 * 2^((QP-4)/6) on the 8-bit scale, /255 here."""
    qp = jnp.asarray(qp, jnp.float32)
    return 0.625 * jnp.exp2((qp - 4.0) / 6.0) / 255.0
