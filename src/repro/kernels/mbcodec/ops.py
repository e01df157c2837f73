"""Public entry points for the fused macroblock codec.

Selects the Pallas kernel on TPU, interpret-mode Pallas for validation, or
the jnp reference elsewhere. The frame-level wrapper handles blockify /
padding / per-channel layout so callers never see kernel tiling; the
chunk-level wrappers (``encode_chunk_fused`` / ``encode_chunk_fused_scores``,
the registry's ``fused`` / ``fused_exact`` backends) hand the kernel the
frames' planar view (a bitcast on the TPU), per-stripe QP or score rows,
and add the per-macroblock headers to its lane bits; they also own the
off-TPU substitution: the kernel's VMEM-carried chunk scan lowers to the
shared-map coefficient-space XLA scan on CPU hosts, announced by a one-time
``RuntimeWarning`` naming the substituted backend.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.codec.codec import (BLOCK_OVERHEAD, block_bits, encode_chunk_fast)
from repro.codec.dct import (MB, blockify, dct2, freq_weight, idct2, qstep,
                             unblockify)
from repro.kernels.mbcodec.kernel import (LANES, TILE, mbcodec_chunk_pallas,
                                          mbcodec_chunk_scores_pallas,
                                          mbcodec_pallas, stripe_rows)
from repro.kernels.mbcodec.ref import mbcodec_ref

#: backends that already warned about their off-TPU substitution this
#: process (tests clear this to re-arm the warning)
_FALLBACK_WARNED: set = set()


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def warn_fallback(name: str, substitute: str) -> None:
    """One-time (per backend, per process) off-TPU substitution notice.

    The registry's TPU-preferred backends (``pallas``/``fused``/
    ``fused_exact``) silently resolving to a different lowering made CPU
    benchmark numbers easy to misread — say which backend actually ran.
    """
    if name in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(name)
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    reg = obs_metrics.get_metrics()
    if reg is not None:
        reg.counter("encoder_fallbacks_total", backend=name,
                    substitute=substitute).inc()
    tracer = obs_trace.get_tracer()
    if tracer is not None:  # surface the substitution on the timeline
        # too: a trace whose "pallas" spans actually ran the XLA scan
        # should say so next to the spans themselves
        tracer.instant("encoder_fallback", stage="events", backend=name,
                       substitute=substitute,
                       platform=jax.default_backend())
    warnings.warn(
        f"CHUNK_ENCODERS[{name!r}]: no TPU detected "
        f"(jax.default_backend()={jax.default_backend()!r}); substituting "
        f"{substitute}. Timings measure the fallback, not the Pallas "
        f"kernel.", RuntimeWarning, stacklevel=3)


def _pad_lanes(x, value, multiple=TILE):
    """Pad the last (lane) axis up to a ``multiple``; a no-op where it
    is one already."""
    pad = (-x.shape[-1]) % multiple
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                   constant_values=value)


def mbcodec(blocks: jnp.ndarray, qp: jnp.ndarray, impl: str = "auto"):
    """blocks (N, 16, 16), qp (N,) -> (rec, bits)."""
    if impl == "auto":
        impl = "pallas" if on_tpu() else "ref"
    if impl == "ref":
        return mbcodec_ref(blocks, qp)
    n = blocks.shape[0]
    x = _pad_lanes(blocks.reshape(n, MB * MB).T, 0.0)
    rec, bits = mbcodec_pallas(x, _pad_lanes(qp.reshape(1, n), 30.0),
                               interpret=(impl == "interpret"))
    return rec[:, :n].T.reshape(n, MB, MB), bits[0, :n]


def encode_frame_fused(frame: jnp.ndarray, qp_map: jnp.ndarray,
                       impl: str = "auto", reference: jnp.ndarray = None):
    """Kernel-backed equivalent of repro.codec.codec.encode_frame.

    frame (H, W, C); qp_map (H/16, W/16) -> (decoded, bits_map).
    ``reference`` is the previous *decoded* frame for P-frame coding
    (None -> I-frame), mirroring ``codec.encode_frame`` so the serving
    path's ``impl="pallas"`` chunk encoder can scan this per frame.
    """
    H, W, C = frame.shape
    src = frame if reference is None else frame - reference
    blocks = blockify(src).reshape(-1, MB, MB)  # (N*C, 16, 16)
    qp = jnp.repeat(qp_map.reshape(-1), C)
    rec, bits = mbcodec(blocks, qp, impl)
    rec = unblockify(rec.reshape(-1, C, MB, MB), H, W)
    if reference is not None:
        rec = rec + reference
    # one per-macroblock header, not one per channel (match codec.block_bits)
    bits_map = (bits.reshape(-1, C).sum(-1) - (C - 1) * BLOCK_OVERHEAD)
    bits_map = bits_map.reshape(H // MB, W // MB)
    return jnp.clip(rec, 0.0, 1.0), bits_map


# ---------------------------------------------------------------------------
# chunk-fused fast-path (registry backends "fused" / "fused_exact")
# ---------------------------------------------------------------------------
def _planes(frames):
    """frames (T, H, W, C) -> planar (T, C, H, lanes), W padded to a
    multiple of 128 lanes only where it is not one. Under the TPU's
    default layout for a 3-channel frame batch (W on lanes) the move is a
    bitcast."""
    return _pad_lanes(jnp.moveaxis(frames, -1, 1), 0.0, LANES)


def _stripe_rows(per_mb, H, value):
    """Per-macroblock values (..., H/16, W/16) -> the kernel's per-stripe
    rows (..., H/R, R/16, lanes): each macroblock's value on its 16 lanes,
    padded lanes set to ``value``."""
    x = _pad_lanes(jnp.repeat(per_mb, MB, axis=-1), value, LANES)
    R = stripe_rows(H)
    return x.reshape(x.shape[:-2] + (H // R, R // MB, x.shape[-1]))


def _chunk_finish(dec, bits, W):
    """Kernel outputs -> (decoded (T, H, W, C), bytes (T,)). The kernel
    sums each lane's coefficient bits; codec.block_bits charges
    BLOCK_OVERHEAD once per macroblock, added here. Padded lanes hold
    zeros and add no bits."""
    T, _, H, _ = dec.shape
    decoded = jnp.moveaxis(dec[..., :W], 1, -1)
    mb_bits = bits[..., :W].reshape(T, -1, MB).sum(-1)
    pbytes = (mb_bits.sum(-1) + (H // MB) * (W // MB) * BLOCK_OVERHEAD) / 8.0
    return decoded, pbytes


def _encode_chunk_fused_xla(frames, qp_maps, clip_refs):
    """Off-TPU lowering of the chunk-fused schedule.

    Shared-map chunks (the serving path's k = chunk_size frame sampling)
    run the scaled coefficient-space recursion: with one step per block
    for the whole chunk, the carried state is the reconstruction in
    *step units*, the scan body collapses to ``r += round(e_t - r)``, and
    the per-frame quantized updates are recovered outside the scan as
    exact integer diffs — no per-step dequantize multiply and no
    rescale before the entropy bits. Per-frame maps and the
    clip-corrected exact variant share ``encode_chunk_fast``'s scan
    (the clip correction needs pixel-space state anyway).
    """
    T, H, W, _ = frames.shape
    if clip_refs or qp_maps.shape[0] != 1:
        return encode_chunk_fast(frames, qp_maps, clip_correct=clip_refs)
    w = jnp.asarray(freq_weight())
    step = qstep(qp_maps.reshape(-1))[:, None, None, None] * w
    coefs = dct2(jax.vmap(blockify)(frames))     # (T, n_mb, C, 16, 16)
    e = coefs * (1.0 / step)

    def body(r, e_t):
        r = r + jnp.round(e_t - r)
        return r, r

    _, recs = jax.lax.scan(body, jnp.zeros_like(e[0]), e, unroll=T)
    qs = jnp.diff(recs, axis=0, prepend=jnp.zeros_like(recs[:1]))
    pbytes = jax.vmap(lambda q: block_bits(q).sum() / 8.0)(qs)
    decoded = jax.vmap(lambda c: unblockify(idct2(c * step), H, W))(recs)
    return jnp.clip(decoded, 0.0, 1.0), pbytes


def encode_chunk_fused(frames: jnp.ndarray, qp_maps: jnp.ndarray,
                       clip_refs: bool = False, impl: str = "auto"):
    """Chunk-fused equivalent of ``codec.encode_chunk`` / ``_fast``.

    frames (T, H, W, C); qp_maps (T or 1, H/16, W/16) ->
    (decoded (T, H, W, C), per_frame_bytes (T,)).

    On TPU this is one ``mbcodec_chunk_pallas`` call: the whole P-frame
    scan runs per VMEM tile with the decoded reference in scratch
    (``clip_refs=True`` clips that reference every step — structurally
    the exact encoder's semantics, the ``fused_exact`` backend).
    Off-TPU it lowers to the shared-map coefficient-space XLA scan
    (``warn_fallback`` announces the substitution once).
    """
    H, W = frames.shape[1], frames.shape[2]
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    if impl == "xla":
        warn_fallback(
            "fused_exact" if clip_refs else "fused",
            "the clip-corrected XLA scan (fast_exact)" if clip_refs
            else "the shared-map coefficient-space XLA scan (fast family)")
        return _encode_chunk_fused_xla(frames, qp_maps, clip_refs)
    dec, bits = mbcodec_chunk_pallas(
        _planes(frames), _stripe_rows(qp_maps.astype(jnp.float32), H, 30.0),
        clip_refs=clip_refs, interpret=(impl == "interpret"))
    return _chunk_finish(dec, bits, W)


def encode_chunk_fused_scores(frames: jnp.ndarray, pooled: jnp.ndarray,
                              knobs: jnp.ndarray, clip_refs: bool = False,
                              impl: str = "auto"):
    """Scores-path chunk encode: QP assignment fused into the kernel.

    ``pooled`` (H/16, W/16) is the *dilated* AccModel score map
    (``quality.dilate_scores``); ``knobs`` (3,) = (alpha, qp_hi, qp_lo)
    rides as a traced array so the rate controller can move it per chunk
    with zero recompiles. Because max-pooling commutes with monotone
    thresholding, ``pooled >= alpha`` inside the kernel reproduces the
    dilate-then-select QP map exactly — but the map itself never
    materializes in HBM. Used by ``serve.steps.make_camera_fleet_step``
    for the ``fused``/``fused_exact`` backends.
    """
    H, W = frames.shape[1], frames.shape[2]
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    if impl == "xla":
        warn_fallback(
            "fused_exact" if clip_refs else "fused",
            "the clip-corrected XLA scan (fast_exact)" if clip_refs
            else "the shared-map coefficient-space XLA scan (fast family)")
        qp_map = jnp.where(pooled >= knobs[0], knobs[1], knobs[2])[None]
        return _encode_chunk_fused_xla(frames, qp_map, clip_refs)
    # padded lanes score -inf: always the low-quality level
    rows = _stripe_rows(pooled.astype(jnp.float32), H, -jnp.inf)
    dec, bits = mbcodec_chunk_scores_pallas(
        _planes(frames), rows, knobs[:3].astype(jnp.float32),
        clip_refs=clip_refs, interpret=(impl == "interpret"))
    return _chunk_finish(dec, bits, W)
