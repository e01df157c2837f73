"""Macroblock RoI codec: per-macroblock QP maps, I/P frames, byte model.

API (all jit-friendly):
    encode_frame(frame, qp_map)          -> (decoded, bits_map)
    encode_chunk(frames, qp_maps)        -> (decoded, per_frame_bytes)

The byte model is an entropy proxy over quantized coefficients
(sum of per-coefficient magnitude bits + a per-nonzero run-length cost),
calibrated so QP response is monotone and high-quality-area growth is
sublinear (the Appendix-C property the paper relies on). Absolute sizes are
model units ("bytes") consistent across methods — all baselines share this
codec, so delay comparisons are apples-to-apples.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.codec.dct import (MB, blockify, dct2, freq_weight, idct2, qstep,
                             unblockify)

# entropy model constants (calibrated in tests/bench against the Appendix-C
# sublinearity property)
BITS_PER_MAG = 1.7  # bits per log2(1+|q|)
RUN_BITS = 0.9      # per-nonzero positional cost
BLOCK_OVERHEAD = 10.0  # per-macroblock header bits


def _quantize(coefs, qp):
    """coefs (..., C, 16, 16); qp broadcastable to (...,)."""
    w = jnp.asarray(freq_weight())
    step = qstep(qp)[..., None, None, None] * w
    q = jnp.round(coefs / step)
    return q, step


def block_bits(q) -> jnp.ndarray:
    """Entropy-proxy bits per macroblock. q: (N, C, 16, 16) -> (N,)."""
    mag = jnp.log2(1.0 + jnp.abs(q))
    nonzero = (jnp.abs(q) > 0.5).astype(jnp.float32)
    return (BITS_PER_MAG * mag + RUN_BITS * nonzero).sum(axis=(-3, -2, -1)) \
        + BLOCK_OVERHEAD


def encode_frame(frame: jnp.ndarray, qp_map: jnp.ndarray,
                 reference: Optional[jnp.ndarray] = None):
    """Encode one frame (H, W, C) float32 in [0,1].

    qp_map: (H/16, W/16) per-macroblock QP. reference: previous *decoded*
    frame for P-frame coding (None -> I-frame).

    Returns (decoded (H,W,C), bits_map (H/16, W/16)).
    """
    H, W, C = frame.shape
    src = frame if reference is None else frame - reference
    blocks = blockify(src)  # (N, C, 16, 16)
    coefs = dct2(blocks)
    q, step = _quantize(coefs, qp_map.reshape(-1))
    deq = q * step
    rec = idct2(deq)
    rec = unblockify(rec, H, W)
    if reference is not None:
        rec = rec + reference
    rec = jnp.clip(rec, 0.0, 1.0)
    bits = block_bits(q).reshape(H // MB, W // MB)
    return rec, bits


def _scan_chunk(encode_one, frames: jnp.ndarray, qp_maps: jnp.ndarray):
    """Shared I-frame + P-frame scan scaffold: ``encode_one(frame, qmap,
    reference)`` codes one frame (reference=None -> I-frame). Used by the
    exact and the kernel-backed chunk encoders so the chunk semantics
    (map broadcast, scan, byte accounting) exist once."""
    T = frames.shape[0]
    if qp_maps.shape[0] == 1:
        qp_maps = jnp.broadcast_to(qp_maps, (T,) + qp_maps.shape[1:])

    dec0, bits0 = encode_one(frames[0], qp_maps[0], None)

    def body(prev, args):
        frame, qmap = args
        dec, bits = encode_one(frame, qmap, prev)
        return dec, (dec, bits.sum() / 8.0)

    _, (decs, pbytes) = jax.lax.scan(body, dec0, (frames[1:], qp_maps[1:]))
    decoded = jnp.concatenate([dec0[None], decs], axis=0)
    all_bytes = jnp.concatenate([(bits0.sum() / 8.0)[None], pbytes])
    return decoded, all_bytes


def encode_chunk(frames: jnp.ndarray, qp_maps: jnp.ndarray):
    """frames: (T, H, W, C); qp_maps: (T, H/16, W/16) or (1, H/16, W/16)
    (one RoI map reused for the chunk — the paper's frame-sampling mode).

    First frame is an I-frame, the rest are P-frames against the decoded
    predecessor. Returns (decoded (T,H,W,C), per_frame_bytes (T,)).
    """
    return _scan_chunk(
        lambda f, q, ref: encode_frame(f, q, reference=ref), frames, qp_maps)


@functools.partial(jax.jit, static_argnames=("qp",))
def encode_chunk_uniform(frames: jnp.ndarray, qp: int):
    T, H, W, _ = frames.shape
    qmap = jnp.full((1, H // MB, W // MB), float(qp))
    return encode_chunk(frames, qmap)


def roi_qp_map(mask: jnp.ndarray, qp_hi: float, qp_lo: float) -> jnp.ndarray:
    """mask (mb_h, mb_w) bool -> QP map."""
    return jnp.where(mask, float(qp_hi), float(qp_lo))


# ---------------------------------------------------------------------------
# serving-path encoder: coefficient-space P-frame accumulation
# ---------------------------------------------------------------------------
def encode_chunk_fast(frames: jnp.ndarray, qp_maps: jnp.ndarray,
                      clip_correct: bool = False):
    """Throughput-oriented equivalent of :func:`encode_chunk`.

    DCT linearity lets the P-frame recursion run entirely in coefficient
    space: ``coefs(frame - prev_rec) = coefs(frame) - rec_coefs`` as long as
    reconstructions are not clipped between frames. All T forward DCTs are
    hoisted into one batched transform before the scan, all T inverse DCTs
    into one batched transform after it, and the per-frame scan body shrinks
    to four elementwise ops. The entropy bits are likewise recovered outside
    the scan from consecutive coefficient states.

    The one semantic difference from ``encode_chunk``: the [0, 1] clip is
    applied once at decode time instead of between reference frames, so
    outputs can drift from the exact encoder where reconstructions leave
    gamut (saturated pixels) — observed <=1e-3 mean / ~0.15 max pixel
    deviation and <0.5% byte deviation on the synthetic scenes. Use
    ``encode_chunk`` when bit-stable accounting matters; use this in the
    fleet serving path where the scan is the throughput bottleneck.

    ``clip_correct=True`` is the exactness knob (ROADMAP item 5): each scan
    step checks the pixel-space reconstruction and, *only when it leaves
    gamut*, folds the clip back into the coefficient state
    (``rec += dct2(clip(pix) - pix)``), so the next P-frame codes against
    the clipped reference exactly as :func:`encode_chunk` does. The check
    costs one inverse transform per step; the correction transform sits
    behind a ``lax.cond``, so single-stream jitted calls skip it entirely
    on in-gamut steps. Under ``jax.vmap`` (the batched fleet path) the
    cond lowers to a select, so the correction transform is computed
    unconditionally there — output identical, and the worst-case overhead
    is what ``benchmarks/multistream.py`` bounds (it measures the vmapped
    fleet step). Output is bit-comparable to the exact encoder on every
    scene (float round-trip error only).
    """
    T, H, W, _ = frames.shape
    if qp_maps.shape[0] == 1:
        qp_maps = jnp.broadcast_to(qp_maps, (T,) + qp_maps.shape[1:])
    w = jnp.asarray(freq_weight())
    steps = qstep(qp_maps.reshape(T, -1))[:, :, None, None, None] * w
    rsteps = 1.0 / steps
    coefs = dct2(jax.vmap(blockify)(frames))  # (T, N, C, 16, 16)

    if not clip_correct:
        def body(rec_prev, args):
            f, step, rstep = args
            q = jnp.round((f - rec_prev) * rstep)
            rec = rec_prev + q * step
            return rec, rec

        _, recs = jax.lax.scan(body, jnp.zeros_like(coefs[0]),
                               (coefs, steps, rsteps), unroll=T)
        qs = jnp.diff(recs, axis=0, prepend=jnp.zeros_like(recs[:1])) * rsteps
        pbytes = jax.vmap(lambda q: block_bits(q).sum() / 8.0)(qs)
        decoded = jax.vmap(lambda c: unblockify(idct2(c), H, W))(recs)
        return jnp.clip(decoded, 0.0, 1.0), pbytes

    def body(rec_prev, args):
        f, step, rstep = args
        q = jnp.round((f - rec_prev) * rstep)
        rec = rec_prev + q * step
        pix = idct2(rec)
        delta = jnp.clip(pix, 0.0, 1.0) - pix
        rec = jax.lax.cond(jnp.any(jnp.abs(delta) > 0.0),
                           lambda a: a[0] + dct2(a[1]),
                           lambda a: a[0], (rec, delta))
        return rec, (pix + delta, q)

    _, (pix, qs) = jax.lax.scan(body, jnp.zeros_like(coefs[0]),
                                (coefs, steps, rsteps), unroll=T)
    pbytes = jax.vmap(lambda q: block_bits(q).sum() / 8.0)(qs)
    decoded = jax.vmap(lambda p: unblockify(p, H, W))(pix)
    return decoded, pbytes


# ---------------------------------------------------------------------------
# chunk-encoder backend registry
# ---------------------------------------------------------------------------
class ChunkEncoderRegistry:
    """Named chunk-encoder backends behind the serving path's ``impl=`` knob.

    Every backend shares the chunk-encoder signature
    ``(frames (T, H, W, C), qp_maps (T or 1, H/16, W/16)) ->
    (decoded (T, H, W, C), per_frame_bytes (T,))`` and is jit/vmap friendly,
    so the engine, the fused fleet step, and the batched entry points can
    select one by name without caring how it is lowered. Mapping-style
    ``CHUNK_ENCODERS[impl]`` resolves the backend (kept for callers of the
    old two-entry dict); :meth:`register` admits new backends.

    Backends may declare ``preferred_backend`` (e.g. ``"tpu"``): they still
    resolve everywhere — off-platform fallback is the backend's own job
    (the ``pallas`` entry drops to the jnp reference tile off-TPU) —
    :meth:`describe` surfaces whether the preferred lowering is active.
    """

    def __init__(self):
        self._backends = {}

    def register(self, name: str, fn=None, *, doc: str = "",
                 preferred_backend: str = None):
        """Register ``fn`` under ``name`` (usable as a decorator).

        Names are write-once: the jitted-encoder caches downstream
        (``_batched_encoder``, ``engine._jit_encoder``) are keyed by name,
        so silently replacing a backend would leave them serving the old
        function — re-registration raises instead."""
        def _add(f):
            if name in self._backends:
                raise ValueError(
                    f"chunk encoder {name!r} already registered; pick a "
                    "new name (downstream jit caches are keyed by name)")
            self._backends[name] = {
                "fn": f, "doc": doc or (f.__doc__ or "").split("\n")[0],
                "preferred_backend": preferred_backend,
            }
            return f
        return _add(fn) if fn is not None else _add

    def resolve(self, name: str):
        try:
            entry = self._backends[name]
            from repro.obs import metrics as obs_metrics

            reg = obs_metrics.get_metrics()
            if reg is not None:  # which backend the impl= knob actually
                # chose, and whether its preferred lowering is live here
                pref = entry["preferred_backend"]
                reg.counter(
                    "chunk_encoder_resolve_total", backend=name,
                    native=str(pref is None
                               or jax.default_backend() == pref)).inc()
            return entry["fn"]
        except KeyError:
            # ValueError, not KeyError: every engine/fleet-step impl= knob
            # funnels through here, and a typo'd backend name should read
            # as "bad argument", not as a mapping miss swallowed upstream
            raise ValueError(
                f"unknown chunk encoder {name!r}; registered backends: "
                f"{', '.join(sorted(self._backends))}") from None

    def describe(self, name: str) -> dict:
        e = self._backends[name]
        pref = e["preferred_backend"]
        return {"name": name, "doc": e["doc"],
                "preferred_backend": pref,
                "native": pref is None or jax.default_backend() == pref}

    # Mapping protocol (back-compat with the old dict)
    def __getitem__(self, name: str):
        return self.resolve(name)

    def __contains__(self, name: str) -> bool:
        return name in self._backends

    def __iter__(self):
        return iter(self._backends)

    def __len__(self) -> int:
        return len(self._backends)

    def keys(self):
        return self._backends.keys()

    def names(self):
        return sorted(self._backends)


CHUNK_ENCODERS = ChunkEncoderRegistry()
CHUNK_ENCODERS.register("exact", encode_chunk,
                        doc="bit-stable reference scan (per-frame DCTs)")
CHUNK_ENCODERS.register("fast", encode_chunk_fast,
                        doc="coefficient-space scan, hoisted transforms")
CHUNK_ENCODERS.register(
    "fast_exact", functools.partial(encode_chunk_fast, clip_correct=True),
    doc="fast scan + per-step clip correction (bit-comparable to exact)")


@CHUNK_ENCODERS.register("pallas", preferred_backend="tpu",
                         doc="fused mbcodec tile (TPU); exact scan off-TPU")
def encode_chunk_pallas(frames: jnp.ndarray, qp_maps: jnp.ndarray):
    """Chunk encoder backed by the fused ``kernels/mbcodec`` tile.

    Per frame, ``kernels.mbcodec.ops.encode_frame_fused`` runs
    blockify-DCT-quant-dequant-IDCT + the entropy bits in one VMEM
    round-trip. P-frames code the residual against the previous *decoded*
    frame exactly like :func:`encode_chunk` (same :func:`_scan_chunk`
    scaffold), so output is bit-comparable to ``impl="exact"``. Off-TPU
    the backend substitutes :func:`encode_chunk` itself (selected at
    trace time, announced once by ``warn_fallback``).
    """
    from repro.kernels.mbcodec.ops import encode_frame_fused, on_tpu, \
        warn_fallback

    if not on_tpu():
        warn_fallback("pallas", "the exact scan (codec.encode_chunk)")
        return encode_chunk(frames, qp_maps)
    return _scan_chunk(
        lambda f, q, ref: encode_frame_fused(f, q, reference=ref),
        frames, qp_maps)


@CHUNK_ENCODERS.register("fused", preferred_backend="tpu",
                         doc="chunk-fused VMEM scan (TPU); shared-map "
                             "coefficient XLA scan off-TPU")
def encode_chunk_fused_backend(frames: jnp.ndarray, qp_maps: jnp.ndarray):
    """The fused camera fast-path (``kernels/mbcodec`` chunk kernel).

    One ``mbcodec_chunk_pallas`` call encodes the whole chunk from the
    frames' planar layout: grid ``(C, H/R, T)`` over R-row stripes with
    the frame axis innermost, the P-frame reference carried in VMEM
    scratch across the scan, and the per-frame stripe DMA
    double-buffered against compute — quantize, entropy bits,
    and reconstruction never leave VMEM between frames. Clip semantics
    match ``fast`` (one decode-time clip); use ``fused_exact`` for the
    per-step reference clip. Off-TPU this lowers to the shared-map
    coefficient-space XLA scan (one-time RuntimeWarning names the
    substitution).
    """
    from repro.kernels.mbcodec.ops import encode_chunk_fused

    return encode_chunk_fused(frames, qp_maps)


@CHUNK_ENCODERS.register("fused_exact", preferred_backend="tpu",
                         doc="chunk-fused VMEM scan + per-step reference "
                             "clip (bit-comparable to exact)")
def encode_chunk_fused_exact_backend(frames: jnp.ndarray,
                                     qp_maps: jnp.ndarray):
    """``fused`` with the exact encoder's reference semantics.

    The VMEM-carried reference tile is clipped to [0, 1] every scan step
    (clip is elementwise, so the per-tile clip equals the exact
    encoder's full-frame clip), making output bit-comparable to
    ``impl="exact"`` — the chunk-kernel analogue of ``fast_exact``'s
    clip-correction trick, but structural instead of cond-gated: the
    reference lives in pixel-adjacent block space already, so exactness
    costs nothing extra on the kernel path. Off-TPU it lowers to
    ``fast_exact`` itself.
    """
    from repro.kernels.mbcodec.ops import encode_chunk_fused

    return encode_chunk_fused(frames, qp_maps, clip_refs=True)


# ---------------------------------------------------------------------------
# batched leading-axis entry points (N independent streams)
# ---------------------------------------------------------------------------
@functools.lru_cache()
def _batched_encoder(impl: str):
    return jax.jit(jax.vmap(CHUNK_ENCODERS.resolve(impl)))


def encode_chunk_batched(frames: jnp.ndarray, qp_maps: jnp.ndarray,
                         impl: str = "exact"):
    """frames (N, T, H, W, C); qp_maps (N, T or 1, H/16, W/16).

    vmaps :data:`CHUNK_ENCODERS`[impl] over N independent streams in one
    jitted program. Returns (decoded (N, T, H, W, C), bytes (N, T)).
    """
    return _batched_encoder(impl)(frames, qp_maps)


def encode_chunk_uniform_batched(frames: jnp.ndarray, qp: int,
                                 impl: str = "exact"):
    """Uniform-QP variant of :func:`encode_chunk_batched`."""
    N, _, H, W, _ = frames.shape
    qmaps = jnp.full((N, 1, H // MB, W // MB), float(qp))
    return _batched_encoder(impl)(frames, qmaps)
