"""Fused macroblock codec Pallas kernels (TPU target).

Layout: macroblocks ride the lane axis. A tile is ``(256, TILE)``: each
column is one 16x16 macroblock channel flattened row-major, so every
operand is lane-dense, (8, 128)-aligned, and 4 B x 256 x TILE per buffer.
The separable 16x16 DCT ``D @ X @ D.T`` becomes one Kronecker matmul
``kron(D, D) @ x`` (and the inverse ``kron(D, D).T @ c``): a (256, 256) x
(256, TILE) contraction the MXU runs at full width, with no in-kernel
transposes or rank-changing reshapes. Per-macroblock operands (QP,
pooled scores, bits) are ``(1, TILE)`` rows that broadcast along
sublanes; the per-coefficient frequency weight is a ``(256, 1)`` column
that broadcasts along lanes. Matmuls run at ``Precision.HIGHEST`` so the
chip computes the f32 transform the CPU does.

Two kernel generations live here:

* ``mbcodec_pallas`` — the per-frame tile: transform, quantize, entropy
  bits, dequantize, inverse transform in one VMEM round-trip, with the
  per-macroblock QP fetched alongside the tile.

* ``mbcodec_chunk_pallas`` / ``mbcodec_chunk_scores_pallas`` — the fused
  camera fast-path (the registry's ``fused`` / ``fused_exact`` backends).
  Grid ``(n_tiles, T)`` with the frame axis innermost and sequential:
  each tile's P-frame reference lives in VMEM scratch across the whole
  chunk scan, so frame t+1 codes against frame t's reference without an
  HBM round-trip, while Pallas double-buffers the next frame's tile DMA
  against compute. ``fused`` carries the reference as unclipped DCT
  coefficients (``codec.encode_chunk_fast``'s recursion), ``fused_exact``
  as clipped pixels (``codec.encode_chunk``'s). The ``scores`` variant takes the
  dilated AccModel score row plus the (alpha, qp_hi, qp_lo) knob triple
  (in SMEM) and assigns the two-level QP inside the kernel, so no QP map
  ever materializes in HBM between scoring and encode.

Validated against ref.mbcodec_ref / codec.encode_chunk in interpret mode
(tests/test_kernels.py) and compiled for a described v5e in
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.codec.codec import BITS_PER_MAG, BLOCK_OVERHEAD, RUN_BITS
from repro.codec.dct import dct_kron, freq_weight

TILE = 256  # macroblock channels per tile (lanes): 256 KiB per buffer
COEFS = 256  # 16 x 16 coefficients per macroblock channel (sublanes)
_HI = jax.lax.Precision.HIGHEST

_SQ = pl.Squeezed()


def _qstep(qp, w):
    """(1, TILE) QP row x (256, 1) weight column -> (256, TILE) steps
    (``codec.dct.qstep`` times ``freq_weight``)."""
    return (0.625 * jnp.exp2((qp - 4.0) / 6.0) / 255.0) * w


def _bits(q):
    """(256, TILE) quantized coefficients -> (1, TILE) entropy bits
    (``codec.block_bits`` per channel column)."""
    aq = jnp.abs(q)
    return (BITS_PER_MAG * jnp.log2(1.0 + aq)
            + RUN_BITS * (aq > 0.5).astype(jnp.float32)).sum(
                axis=0, keepdims=True) + BLOCK_OVERHEAD


def _fwd(kf, x):
    return jax.lax.dot_general(kf, x, (((1,), (0,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _inv(kf, c):
    return jax.lax.dot_general(kf, c, (((0,), (0,)), ((), ())),
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
    q = jnp.round(_fwd(kf, x - ref) / step)
    return ref + _inv(kf, q * step), _bits(q)


def _encode_coef_step(x, rec, qp, kf, w):
    """One coefficient-space P-frame step (``codec.encode_chunk_fast``'s
    arithmetic): ``rec`` is the reference in DCT coefficients, never
    clipped between frames. Returns (new coefficient reference, decoded
    tile, bits)."""
    step = _qstep(qp, w)
    q = jnp.round((_fwd(kf, x) - rec) * (1.0 / step))
    rec = rec + q * step
    return rec, _inv(kf, rec), _bits(q)


def _kernel(blocks_ref, qp_ref, kf_ref, w_ref, rec_ref, bits_ref):
    x = blocks_ref[...]
    rec, bits = _encode_tile_step(x, jnp.zeros_like(x), qp_ref[...],
                                  kf_ref[...], w_ref[...])
    rec_ref[...] = rec
    bits_ref[...] = bits


def _chunk_step(clip_refs, x, qp, kf_ref, w_ref, rec_ref, bits_ref,
                ref_scr):
    """Shared chunk-scan body: the scratch carries the reference across
    the frame axis — clipped pixels for ``fused_exact`` (the exact
    encoder), unclipped coefficients for ``fused`` (the fast encoder)."""
    @pl.when(pl.program_id(1) == 0)
    def _init():  # chunk head: I-frame against a zero reference
        ref_scr[...] = jnp.zeros_like(ref_scr)

    if clip_refs:
        rec, bits = _encode_tile_step(x, ref_scr[...], qp, kf_ref[...],
                                      w_ref[...])
        dec = ref_scr[...] = jnp.clip(rec, 0.0, 1.0)
    else:
        state, dec, bits = _encode_coef_step(x, ref_scr[...], qp,
                                             kf_ref[...], w_ref[...])
        ref_scr[...] = state
    rec_ref[...] = dec
    bits_ref[...] = bits


def _chunk_kernel(clip_refs, blocks_ref, qp_ref, kf_ref, w_ref,
                  rec_ref, bits_ref, ref_scr):
    _chunk_step(clip_refs, blocks_ref[...], qp_ref[...], kf_ref, w_ref,
                rec_ref, bits_ref, ref_scr)


def _chunk_scores_kernel(clip_refs, blocks_ref, pooled_ref, knobs_ref,
                         kf_ref, w_ref, rec_ref, bits_ref, ref_scr):
    # knobs (SMEM): alpha, qp_hi, qp_lo (traced controller knobs)
    qp = jnp.where(pooled_ref[...] >= knobs_ref[0], knobs_ref[1],
                   knobs_ref[2])
    _chunk_step(clip_refs, blocks_ref[...], qp, kf_ref, w_ref, rec_ref,
                bits_ref, ref_scr)


def _consts():
    return (jnp.asarray(dct_kron()),
            jnp.asarray(freq_weight().reshape(COEFS, 1)))


_CONST_SPECS = [pl.BlockSpec((COEFS, COEFS), lambda *_: (0, 0)),
                pl.BlockSpec((COEFS, 1), lambda *_: (0, 0))]


def _chunk_call(kernel, blocks, per_mb, per_mb_spec, extra, extra_specs,
                interpret):
    T, _, n = blocks.shape
    tile = pl.BlockSpec((_SQ, COEFS, TILE), lambda i, t: (t, 0, i))
    return pl.pallas_call(
        kernel,
        grid=(n // TILE, T),
        in_specs=[tile, per_mb_spec, *extra_specs, *_CONST_SPECS],
        out_specs=[tile,
                   pl.BlockSpec((_SQ, 1, TILE), lambda i, t: (t, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((T, COEFS, n), jnp.float32),
                   jax.ShapeDtypeStruct((T, 1, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((COEFS, TILE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(blocks, per_mb, *extra, *_consts())


@functools.partial(jax.jit, static_argnames=("clip_refs", "interpret"))
def mbcodec_chunk_pallas(blocks: jnp.ndarray, qp: jnp.ndarray,
                         clip_refs: bool = False, interpret: bool = False):
    """Chunk-fused codec: blocks (T, 256, n) f32, qp (T, 1, n) f32 ->
    (rec (T, 256, n), bits (T, 1, n)). n % TILE == 0 (ops.py pads).

    Grid (n/TILE, T), T innermost: the P-frame reference tile is carried
    in VMEM scratch across the frame axis, so the whole chunk scan for a
    tile runs without leaving VMEM.
    """
    return _chunk_call(
        functools.partial(_chunk_kernel, clip_refs), blocks, qp,
        pl.BlockSpec((_SQ, 1, TILE), lambda i, t: (t, 0, i)), (), (),
        interpret)


@functools.partial(jax.jit, static_argnames=("clip_refs", "interpret"))
def mbcodec_chunk_scores_pallas(blocks: jnp.ndarray, pooled: jnp.ndarray,
                                knobs: jnp.ndarray, clip_refs: bool = False,
                                interpret: bool = False):
    """Scores-fused variant: pooled (1, n) dilated AccModel scores and
    knobs (3,) = (alpha, qp_hi, qp_lo) replace the explicit QP array —
    the two-level threshold assignment happens in-register per tile
    (``dilate_scores(s) >= alpha`` == dilate-then-select, see
    quality.dilate_scores), so the QP map never exists in HBM. The knob
    triple is traced: the rate controller moves it per chunk with zero
    recompiles.
    """
    return _chunk_call(
        functools.partial(_chunk_scores_kernel, clip_refs), blocks, pooled,
        pl.BlockSpec((1, TILE), lambda i, t: (0, i)), (knobs,),
        (pl.BlockSpec(memory_space=pltpu.SMEM),), interpret)


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
