"""Fused macroblock codec Pallas kernels (TPU target).

Two kernel generations live here, each with its own layout. Matmuls run at
``Precision.HIGHEST`` so the chip computes the f32 transform the CPU does.

* ``mbcodec_pallas`` — the per-frame tile, on blockified macroblocks:
  a tile is ``(256, TILE)``, each column one 16x16 macroblock channel
  flattened row-major, and the 16x16 DCT ``D @ X @ D.T`` is one Kronecker
  matmul ``kron(D, D) @ x``. Transform, quantize, entropy bits,
  dequantize and inverse transform in one VMEM round-trip, with the
  per-macroblock QP fetched alongside the tile as a ``(1, TILE)`` row.

* ``mbcodec_chunk_pallas`` / ``mbcodec_chunk_scores_pallas`` — the fused
  camera fast-path (the registry's ``fused`` / ``fused_exact`` backends),
  on planar frames: ``(T, C, H, lanes)``, which is how a ``(T, H, W, C)``
  frame batch already lies in HBM on the TPU (W on lanes), so the kernel
  reads and writes the frames in place and forms macroblocks in VMEM. A
  tile is R frame rows (``stripe_rows``: 48 at 720p) of one channel,
  every lane. The transform is separable as ``D X D^T`` reads: rows by
  the block-diagonal ``kron(I, D)`` (R, R), then each 128-lane slice by
  ``kron(I_8, D^T)``; coefficient (u, v) of a macroblock sits on its
  pixel (u, v), so the frequency weight is a (16, lanes) pattern and the
  per-macroblock QP a per-stripe row broadcast over the stripe's 16 rows.
  Grid ``(C, H/R, T)`` with the frame axis innermost and sequential: each
  tile's P-frame reference lives in VMEM scratch across the whole chunk
  scan, so frame t+1 codes against frame t's reference without an HBM
  round-trip, while Pallas double-buffers the next frame's tile DMA
  against compute. ``fused`` carries the reference as unclipped DCT
  coefficients (``codec.encode_chunk_fast``'s recursion), ``fused_exact``
  as clipped pixels (``codec.encode_chunk``'s); both write the decoded
  tile clipped to [0, 1]. Bits leave as per-lane sums over the tile's
  rows. The ``scores`` variant takes the dilated AccModel score rows plus
  the (alpha, qp_hi, qp_lo) knob triple (in SMEM) and assigns the
  two-level QP inside the kernel, so no QP map ever materializes in HBM
  between scoring and encode.

Validated against ref.mbcodec_ref / codec.encode_chunk in interpret mode
(tests/test_kernels.py) and compiled for a described v5e in
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.codec.codec import BITS_PER_MAG, BLOCK_OVERHEAD, RUN_BITS
from repro.codec.dct import MB, dct_kron, dct_matrix, freq_weight

TILE = 256  # macroblock channels per tile (lanes): 256 KiB per buffer
COEFS = 256  # 16 x 16 coefficients per macroblock channel (sublanes)
LANES = 128  # lane width: planar frame rows are padded to a multiple
MAX_ROWS = 64  # frame rows per chunk-kernel tile, at most
_HI = jax.lax.Precision.HIGHEST

_SQ = pl.Squeezed()


def _qstep(qp, w):
    """(1, n) QP row x weight ((256, 1) column, or (16, n) pattern) ->
    steps (``codec.dct.qstep`` times ``freq_weight``)."""
    return (0.625 * jnp.exp2((qp - 4.0) / 6.0) / 255.0) * w


def _bits(q):
    """(256, TILE) quantized coefficients -> (1, TILE) entropy bits
    (``codec.block_bits`` per channel column)."""
    return _lane_bits(q) + BLOCK_OVERHEAD


def _dot(a, b, ca, cb):
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _encode_tile_step(x, ref, qp, kf, w):
    """One pixel-space encode step for a (256, TILE) tile, VMEM-resident
    (``codec.encode_frame``'s arithmetic; ``ref.mbcodec_ref`` spells out
    the same order).

    ``ref`` is the tile's decoded reference (zeros for an I-frame);
    ``qp`` is the (1, TILE) per-macroblock QP row, ``kf`` the (256, 256)
    Kronecker DCT and ``w`` the (256, 1) frequency-weight column. Returns
    the unclipped decoded tile and the (1, TILE) entropy bits.
    """
    step = _qstep(qp, w)
    q = jnp.round(_dot(kf, x - ref, 1, 0) / step)
    return ref + _dot(kf, q * step, 0, 0), _bits(q)


def _kernel(blocks_ref, qp_ref, kf_ref, w_ref, rec_ref, bits_ref):
    x = blocks_ref[...]
    rec, bits = _encode_tile_step(x, jnp.zeros_like(x), qp_ref[...],
                                  kf_ref[...], w_ref[...])
    rec_ref[...] = rec
    bits_ref[...] = bits


def _consts():
    return (jnp.asarray(dct_kron()),
            jnp.asarray(freq_weight().reshape(COEFS, 1)))


_CONST_SPECS = [pl.BlockSpec((COEFS, COEFS), lambda *_: (0, 0)),
                pl.BlockSpec((COEFS, 1), lambda *_: (0, 0))]


# ---------------------------------------------------------------------------
# chunk kernels: planar frame stripes
# ---------------------------------------------------------------------------
def stripe_rows(H: int) -> int:
    """Frame rows per chunk-kernel tile: the largest multiple of 16 that
    divides H, up to ``MAX_ROWS`` (48 at 720p). The row transform is an
    (R, R) block-diagonal matmul, so a taller tile spends more of the
    MXU on zeros."""
    return max(r for r in range(MB, min(H, MAX_ROWS) + 1, MB) if H % r == 0)


def _stripe_consts(R: int, lanes: int):
    """The separable transform's operands for an (R, lanes) tile:
    ``kron(I, D)`` (R, R) applies D to every 16-row stripe from the left,
    ``kron(I, D^T)`` (128, 128) applies D^T to every 16-lane group of a
    128-lane slice from the right, and the frequency weight is the (16,
    lanes) pattern ``w[row % 16, lane % 16]``."""
    d = dct_matrix()
    return (jnp.asarray(np.kron(np.eye(R // MB, dtype=np.float32), d)),
            jnp.asarray(np.kron(np.eye(LANES // MB, dtype=np.float32), d.T)),
            jnp.asarray(np.tile(freq_weight(), (1, lanes // MB))))


def _by_slices(y, k, ck):
    """Every 128-lane slice of ``y`` (R, lanes) times ``k`` (contracting
    its dimension ``ck``), as one matmul: the slices stack along
    sublanes, so the constant is loaded into the MXU once per tile."""
    R, n = y.shape[0], y.shape[1] // LANES
    z = _dot(jnp.concatenate([y[:, i * LANES:(i + 1) * LANES]
                              for i in range(n)], axis=0), k, 1, ck)
    return jnp.concatenate([z[i * R:(i + 1) * R] for i in range(n)], axis=1)


def _stripe_fwd(l, k, x):
    """2-D DCT of every 16x16 macroblock of an (R, lanes) tile, rows
    first as ``D X D^T`` reads: ``kron(I, D) x``, then each slice times
    ``kron(I, D^T)``. Coefficient (u, v) of a macroblock lands on its
    own pixel (u, v)."""
    return _by_slices(_dot(l, x, 1, 0), k, 0)


def _stripe_inv(l, k, c):
    """Inverse of :func:`_stripe_fwd` (``D^T C D``, rows first)."""
    return _by_slices(_dot(l, c, 0, 0), k, 1)


def _stripe_steps(qp, w):
    """(R/16, lanes) per-stripe QP rows x (16, lanes) weight pattern ->
    (R, lanes) quantization steps, each stripe's row broadcast over its
    16 rows."""
    return jnp.concatenate([_qstep(qp[i:i + 1], w)
                            for i in range(qp.shape[0])], axis=0)


def _lane_bits(q):
    """(R, lanes) quantized coefficients -> (1, lanes) entropy bits summed
    over the tile's rows (``codec.block_bits`` without the per-macroblock
    header, which ops.py adds once per macroblock)."""
    aq = jnp.abs(q)
    return (BITS_PER_MAG * jnp.log2(1.0 + aq)
            + RUN_BITS * (aq > 0.5).astype(jnp.float32)).sum(
                axis=0, keepdims=True)


def _chunk_step(clip_refs, x, qp, l_ref, k_ref, w_ref, dec_ref, bits_ref,
                ref_scr):
    """Shared chunk-scan body for one (R, lanes) stripe tile of one
    channel: the scratch carries the reference across the frame axis —
    clipped pixels for ``fused_exact`` (the exact encoder), unclipped
    coefficients for ``fused`` (the fast encoder). The decoded tile
    leaves clipped to [0, 1]."""
    @pl.when(pl.program_id(2) == 0)
    def _init():  # chunk head: I-frame against a zero reference
        ref_scr[...] = jnp.zeros_like(ref_scr)

    l, k = l_ref[...], k_ref[...]
    step = _stripe_steps(qp, w_ref[...])
    if clip_refs:  # codec.encode_frame's arithmetic: divide by the step
        ref = ref_scr[...]
        q = jnp.round(_stripe_fwd(l, k, x - ref) / step)
        dec = ref_scr[...] = jnp.clip(ref + _stripe_inv(l, k, q * step),
                                      0.0, 1.0)
    else:  # codec.encode_chunk_fast's coefficient-space recursion
        rec = ref_scr[...]
        q = jnp.round((_stripe_fwd(l, k, x) - rec) * (1.0 / step))
        rec = ref_scr[...] = rec + q * step
        dec = jnp.clip(_stripe_inv(l, k, rec), 0.0, 1.0)
    dec_ref[...] = dec
    bits_ref[...] = _lane_bits(q)


def _chunk_kernel(clip_refs, x_ref, qp_ref, l_ref, k_ref, w_ref,
                  dec_ref, bits_ref, ref_scr):
    _chunk_step(clip_refs, x_ref[...], qp_ref[...], l_ref, k_ref, w_ref,
                dec_ref, bits_ref, ref_scr)


def _chunk_scores_kernel(clip_refs, x_ref, pooled_ref, knobs_ref, l_ref,
                         k_ref, w_ref, dec_ref, bits_ref, ref_scr):
    # knobs (SMEM): alpha, qp_hi, qp_lo (traced controller knobs)
    qp = jnp.where(pooled_ref[...] >= knobs_ref[0], knobs_ref[1],
                   knobs_ref[2])
    _chunk_step(clip_refs, x_ref[...], qp, l_ref, k_ref, w_ref, dec_ref,
                bits_ref, ref_scr)


def _chunk_call(kernel, planes, rows, rows_spec, extra, extra_specs,
                interpret):
    T, C, H, lanes = planes.shape
    R = MB * rows.shape[-2]
    tile = pl.BlockSpec((_SQ, _SQ, R, lanes), lambda c, r, t: (t, c, r, 0))
    return pl.pallas_call(
        kernel,
        grid=(C, H // R, T),
        in_specs=[tile, rows_spec, *extra_specs,
                  pl.BlockSpec((R, R), lambda *_: (0, 0)),
                  pl.BlockSpec((LANES, LANES), lambda *_: (0, 0)),
                  pl.BlockSpec((MB, lanes), lambda *_: (0, 0))],
        out_specs=[tile, pl.BlockSpec((_SQ, _SQ, _SQ, 1, lanes),
                                      lambda c, r, t: (t, c, r, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(planes.shape, jnp.float32),
                   jax.ShapeDtypeStruct((T, C, H // R, 1, lanes),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((R, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(planes, rows, *extra, *_stripe_consts(R, lanes))


@functools.partial(jax.jit, static_argnames=("clip_refs", "interpret"))
def mbcodec_chunk_pallas(planes: jnp.ndarray, qp: jnp.ndarray,
                         clip_refs: bool = False, interpret: bool = False):
    """Chunk-fused codec on planar frames: planes (T, C, H, lanes) f32,
    qp (T or 1, H/R, R/16, lanes) f32 per-stripe QP rows (each
    macroblock's QP on its 16 lanes) -> (decoded (T, C, H, lanes) in
    [0, 1], bits (T, C, H/R, 1, lanes) per-lane row sums without headers).
    lanes % 128 == 0 and R = ``stripe_rows(H)`` (ops.py pads and shapes).

    Grid (C, H/R, T), T innermost: the P-frame reference tile is carried
    in VMEM scratch across the frame axis, so the whole chunk scan for a
    stripe runs without leaving VMEM.
    """
    per_frame = qp.shape[0] > 1
    return _chunk_call(
        functools.partial(_chunk_kernel, clip_refs), planes, qp,
        pl.BlockSpec((_SQ, _SQ) + qp.shape[2:],
                     lambda c, r, t: (t if per_frame else 0, r, 0, 0)),
        (), (), interpret)


@functools.partial(jax.jit, static_argnames=("clip_refs", "interpret"))
def mbcodec_chunk_scores_pallas(planes: jnp.ndarray, pooled: jnp.ndarray,
                                knobs: jnp.ndarray, clip_refs: bool = False,
                                interpret: bool = False):
    """Scores-fused variant: pooled (H/R, R/16, lanes) per-stripe rows of
    the dilated AccModel scores and knobs (3,) = (alpha, qp_hi, qp_lo)
    replace the explicit QP rows — the two-level threshold assignment
    happens in-register per tile (``dilate_scores(s) >= alpha`` ==
    dilate-then-select, see quality.dilate_scores), so the QP map never
    exists in HBM. The knob triple is traced: the rate controller moves
    it per chunk with zero recompiles.
    """
    return _chunk_call(
        functools.partial(_chunk_scores_kernel, clip_refs), planes, pooled,
        pl.BlockSpec((_SQ,) + pooled.shape[1:], lambda c, r, t: (r, 0, 0)),
        (knobs,), (pl.BlockSpec(memory_space=pltpu.SMEM),), interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mbcodec_pallas(blocks: jnp.ndarray, qp: jnp.ndarray,
                   interpret: bool = False):
    """blocks (256, n) f32, qp (1, n) f32 -> (rec (256, n), bits (1, n)).
    n % TILE == 0 (ops.py pads)."""
    n = blocks.shape[1]
    return pl.pallas_call(
        _kernel,
        grid=(n // TILE,),
        in_specs=[pl.BlockSpec((COEFS, TILE), lambda i: (0, i)),
                  pl.BlockSpec((1, TILE), lambda i: (0, i)),
                  *_CONST_SPECS],
        out_specs=[pl.BlockSpec((COEFS, TILE), lambda i: (0, i)),
                   pl.BlockSpec((1, TILE), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((COEFS, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(blocks, qp, *_consts())
