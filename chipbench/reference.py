"""Plain reference of what one ``MultiStreamEngine.run`` chunk produces.

For one stream's chunk of T frames it computes, in straightforward
``jax.numpy`` with no kernel, batching or cache:

- the AccModel's macroblock scores on the chunk head, max-pooled over the
  dilation window, and the two-level QP map (``QualityConfig`` semantics);
- the chunk encode the ``fused`` codec performs: the 16x16 DCT of every
  block channel, then the P-frame recursion in coefficient space
  (``q_t = round((c_t - r_{t-1}) / step)``, ``r_t = r_{t-1} + q_t step``,
  one clip at decode time), and the entropy-proxy bytes of every frame
  (1.7 bits per log2(1 + |q|), 0.9 bits per nonzero, 10 header bits per
  macroblock);
- D(H): for detection the server DNN on the chunk encoded uniformly at
  ``ref_qp`` by the exact pixel-space scan (clipped reference every
  frame), for segmentation the server DNN on the raw chunk;
- the server DNN on the decoded chunk, and the accuracy of that against
  D(H): greedy detection F1 (3x3 max-pool NMS, threshold 0.3, top 50,
  IoU 0.5) or the two-class segmentation IoU.

``precision`` selects the arithmetic of every transform and convolution
(see ``nets.product``); the benchmark runs ``"highest"``, the control
``"bf16_3x"`` (and, on the chip, ``"high"``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import nets

MB = 16
STRIDE = 8
BITS_PER_MAG, RUN_BITS, BLOCK_OVERHEAD = 1.7, 0.9, 10.0
DET_THRESH, DET_TOPK, IOU_THRESH = 0.3, 50, 0.5


def dct_matrix(n: int = MB) -> np.ndarray:
    k = np.arange(n)[:, None]
    i = np.arange(n)[None, :]
    d = np.cos(np.pi * (2 * i + 1) * k / (2 * n)) * np.sqrt(2.0 / n)
    d[0] /= np.sqrt(2.0)
    return d.astype(np.float32)


def freq_weight(n: int = MB) -> np.ndarray:
    k = np.arange(n, dtype=np.float32)
    return (1.0 + (k[:, None] + k[None, :]) / (2.0 * (n - 1))).astype(
        np.float32)


def qstep(qp):
    return 0.625 * jnp.exp2((jnp.asarray(qp, jnp.float32) - 4.0) / 6.0) / 255.0


def blocks_of(frames):
    """(T, H, W, C) -> (T, H/16 * W/16, C, 16, 16), macroblocks row-major."""
    T, H, W, C = frames.shape
    x = frames.reshape(T, H // MB, MB, W // MB, MB, C)
    return x.transpose(0, 1, 3, 5, 2, 4).reshape(T, -1, C, MB, MB)


def frames_of(blocks, H, W):
    T, _, C = blocks.shape[:3]
    x = blocks.reshape(T, H // MB, W // MB, C, MB, MB)
    return x.transpose(0, 1, 4, 2, 5, 3).reshape(T, H, W, C)


def dct(x, precision, inverse=False):
    """16x16 DCT of (..., 16, 16) blocks, ``D X D^T`` (inverse
    ``D^T X D``), as two products, each at ``precision``."""
    d = jnp.asarray(dct_matrix())
    first = nets.product(lambda m, a, pr: jnp.einsum(
        "ji,...jk->...ik" if inverse else "ij,...jk->...ik", m, a,
        precision=pr), d, x, precision)
    return nets.product(lambda a, m, pr: jnp.einsum(
        "...ik,kl->...il" if inverse else "...ik,lk->...il", a, m,
        precision=pr), first, d, precision)


def block_bits(q):
    """(..., C, 16, 16) quantized coefficients -> bits per macroblock."""
    aq = jnp.abs(q)
    per_channel = (BITS_PER_MAG * jnp.log2(1.0 + aq)
                   + RUN_BITS * (aq > 0.5)).sum(axis=(-2, -1)) + BLOCK_OVERHEAD
    return per_channel.sum(-1) - (q.shape[-3] - 1) * BLOCK_OVERHEAD


def qp_map(acc_params, head, qcfg, precision):
    """Chunk head (H, W, 3) -> per-macroblock QP (H/16 * W/16,)."""
    scores = jax.nn.sigmoid(nets.accmodel_logits(acc_params, head[None],
                                                 precision))
    k = 2 * qcfg["gamma"] + 1
    pooled = jax.lax.reduce_window(scores, -jnp.inf, jax.lax.max,
                                   (1, k, k), (1, 1, 1), "SAME")[0]
    qp = jnp.where(pooled >= qcfg["alpha"], float(qcfg["qp_hi"]),
                   float(qcfg["qp_lo"]))
    return qp.reshape(-1)


def encode_coefficient_scan(frames, qp, precision):
    """The ``fused``/``fast`` chunk encode. frames (T, H, W, C), qp
    (n_mb,) -> (decoded (T, H, W, C), bytes per frame (T,))."""
    T, H, W, _ = frames.shape
    step = qstep(qp)[:, None, None, None] * jnp.asarray(freq_weight())
    rstep = 1.0 / step
    coefs = dct(blocks_of(frames), precision)

    def body(rec, c):
        q = jnp.round((c - rec) * rstep)
        rec = rec + q * step
        return rec, (rec, block_bits(q).sum() / 8.0)

    _, (recs, nbytes) = jax.lax.scan(body, jnp.zeros_like(coefs[0]), coefs)
    decoded = frames_of(dct(recs, precision, inverse=True), H, W)
    return jnp.clip(decoded, 0.0, 1.0), nbytes


def encode_pixel_scan(frames, qp, precision):
    """The exact encoder at one QP: each P-frame codes its residual against
    the previous decoded, clipped frame. -> decoded (T, H, W, C)."""
    T, H, W, C = frames.shape
    step = qstep(qp) * jnp.asarray(freq_weight())

    def body(prev, f):
        c = dct(blocks_of((f - prev)[None]), precision)
        q = jnp.round(c / step)
        rec = frames_of(dct(q * step, precision, inverse=True), H, W)[0]
        rec = jnp.clip(rec + prev, 0.0, 1.0)
        return rec, rec

    _, decoded = jax.lax.scan(body, jnp.zeros_like(frames[0]), frames)
    return decoded


def nms_keep(heat_logits):
    """(B, hs, ws, 1) heat logits -> suppressed heat (B, hs, ws)."""
    heat = jax.nn.sigmoid(heat_logits)
    pooled = jax.lax.reduce_window(heat, -jnp.inf, jax.lax.max,
                                   (1, 3, 3, 1), (1, 1, 1, 1), "SAME")
    return jnp.where(heat >= pooled - 1e-6, heat, 0.0)[..., 0]


def _outputs(task, dnn_params, frames, precision):
    out = nets.dnn_outputs(task, dnn_params, frames, precision)
    if task == "detection":
        return {"keep": nms_keep(out["heat"]), "wh": out["wh"]}
    return {"label": jnp.argmax(out["seg"], -1)}


@functools.partial(jax.jit, static_argnames=("task", "qkey", "ref_qp",
                                             "precision"))
def chunk_outputs(acc_params, dnn_params, frames, task, qkey, ref_qp,
                  precision):
    """One stream-chunk through the reference: per-frame bytes, the server
    outputs on the decoded chunk, and D(H)."""
    qcfg = dict(qkey)
    qp = qp_map(acc_params, frames[0], qcfg, precision)
    decoded, nbytes = encode_coefficient_scan(frames, qp, precision)
    hq = frames if ref_qp is None else encode_pixel_scan(
        frames, float(ref_qp), precision)
    return (nbytes, _outputs(task, dnn_params, decoded, precision),
            _outputs(task, dnn_params, hq, precision))


# ---------------------------------------------------------------------------
# accuracy on the host
# ---------------------------------------------------------------------------
def detections(keep, wh):
    """(hs, ws) suppressed heat, (hs, ws, 2) sizes -> (n, 4) boxes in
    descending score order (half-sizes in the sizes' f32, centres and
    corners in f64)."""
    ys, xs = np.where(keep >= DET_THRESH)
    order = np.argsort(-keep[ys, xs])[:DET_TOPK]
    ys, xs = ys[order], xs[order]
    size = (np.maximum(wh[ys, xs], np.float32(0.5))
            * np.float32(STRIDE / 2)).astype(np.float64)
    cx = (xs + 0.5) * STRIDE
    cy = (ys + 0.5) * STRIDE
    return np.stack([cx - size[:, 0], cy - size[:, 1],
                     cx + size[:, 0], cy + size[:, 1]], axis=1)


def frame_f1(dets, refs):
    if len(dets) == 0 and len(refs) == 0:
        return 1.0
    tp = 0
    if len(dets) and len(refs):
        ix0 = np.maximum(dets[:, None, 0], refs[None, :, 0])
        iy0 = np.maximum(dets[:, None, 1], refs[None, :, 1])
        ix1 = np.minimum(dets[:, None, 2], refs[None, :, 2])
        iy1 = np.minimum(dets[:, None, 3], refs[None, :, 3])
        inter = np.maximum(0, ix1 - ix0) * np.maximum(0, iy1 - iy0)
        area = lambda b: (b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])
        union = area(dets)[:, None] + area(refs)[None, :] - inter
        iou = np.where(union > 0, inter / np.where(union > 0, union, 1), 0)
        free = np.ones(len(refs), bool)
        for row in iou:  # greedy, highest-scoring detection first
            row = np.where(free, row, -1.0)
            j = int(np.argmax(row))
            if row[j] >= IOU_THRESH:
                free[j] = False
                tp += 1
    prec = tp / max(len(dets), 1)
    rec = tp / max(len(refs), 1)
    return 2 * prec * rec / max(prec + rec, 1e-9)


def detection_f1(out, ref):
    keep, wh = np.asarray(out["keep"]), np.asarray(out["wh"])
    rkeep, rwh = np.asarray(ref["keep"]), np.asarray(ref["wh"])
    return float(np.mean([frame_f1(detections(keep[t], wh[t]),
                                   detections(rkeep[t], rwh[t]))
                          for t in range(keep.shape[0])]))


def segmentation_iou(out, ref):
    a, b = np.asarray(out["label"]), np.asarray(ref["label"])
    ious = []
    for cls in (0, 1):
        union = np.logical_or(a == cls, b == cls).sum()
        if union > 0:
            ious.append(np.logical_and(a == cls, b == cls).sum() / union)
    return float(np.mean(ious)) if ious else 1.0


def stream_chunk(acc_params, dnn_params, frames, cfg, precision="highest"):
    """-> (bytes of the chunk, accuracy against D(H)) for one stream's
    chunk ``frames`` (T, H, W, 3)."""
    q = cfg["quality"]
    qkey = tuple(sorted((k, q[k]) for k in ("alpha", "gamma", "qp_hi",
                                            "qp_lo")))
    ref_qp = cfg["ref_qp"] if cfg["refs"] == "precomputed" else None
    nbytes, out, ref = jax.device_get(chunk_outputs(
        acc_params, dnn_params, jnp.asarray(frames), cfg["task"], qkey,
        ref_qp, precision))
    score = detection_f1 if cfg["task"] == "detection" else segmentation_iou
    return float(np.sum(nbytes, dtype=np.float64)), score(out, ref)
