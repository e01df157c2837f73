"""Server-side final DNNs (the paper's black-box D): detector, segmenter,
keypoint net — small convnets trainable on CPU, treated strictly as
*differentiable black boxes* by the AccMPEG core.

Accuracy is measured against the DNN's own output on the high-quality frame
D(H) (paper §2 fn.3), so modest model quality does not bias the comparison.
The differentiable accuracy proxy (Appendix B fn.15) is an output-
consistency loss between D(X) and stop_grad(D(H)).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import fold_in_str

STRIDE = 8  # output stride of every head


# ---------------------------------------------------------------------------
# minimal conv substrate (pure jax)
# ---------------------------------------------------------------------------
def conv_init(key, kh, kw, ci, co, scale=None):
    scale = scale or 1.0 / np.sqrt(kh * kw * ci)
    return {
        "w": scale * jax.random.normal(key, (kh, kw, ci, co), jnp.float32),
        "b": jnp.zeros((co,), jnp.float32),
    }


def conv(p, x, stride=1, groups=1, precision=None):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=precision)
    return y + p["b"]


def dw_sep_init(key, ci, co):
    k1, k2 = jax.random.split(key)
    return {"dw": conv_init(k1, 3, 3, 1, ci), "pw": conv_init(k2, 1, 1, ci, co)}


def dw_sep(p, x, stride=1, precision=None):
    ci = x.shape[-1]
    # depthwise: HWIO with I=1, groups=ci
    y = jax.lax.conv_general_dilated(
        x, p["dw"]["w"].reshape(3, 3, 1, ci),
        window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=ci,
        precision=precision)
    y = jax.nn.relu(y + p["dw"]["b"])
    return jax.nn.relu(conv(p["pw"], y, precision=precision))


def backbone_init(key, width=32):
    ks = jax.random.split(key, 5)
    return {
        "stem": conv_init(ks[0], 3, 3, 3, width // 2),
        "b1": dw_sep_init(ks[1], width // 2, width),
        "b2": dw_sep_init(ks[2], width, width * 2),
        "b3": dw_sep_init(ks[3], width * 2, width * 3),
        "b4": dw_sep_init(ks[4], width * 3, width * 3),
    }


def backbone(p, x, precision=None):
    """(B, H, W, 3) -> (B, H/8, W/8, 3*width)."""
    x = jax.nn.relu(conv(p["stem"], x, stride=2, precision=precision))
    x = dw_sep(p["b1"], x, stride=2, precision=precision)
    x = dw_sep(p["b2"], x, stride=2, precision=precision)
    x = dw_sep(p["b3"], x, stride=1, precision=precision)
    x = dw_sep(p["b4"], x, stride=1, precision=precision)
    return x


def head_init(key, ci, cout):
    k1, k2 = jax.random.split(key)
    return {"c1": conv_init(k1, 3, 3, ci, 64), "c2": conv_init(k2, 1, 1, 64, cout)}


def head(p, x, precision=None):
    return conv(p["c2"], jax.nn.relu(conv(p["c1"], x, precision=precision)),
                precision=precision)


# ---------------------------------------------------------------------------
# task nets
# ---------------------------------------------------------------------------
def init_net(task: str, key, width=32):
    kb, kh = jax.random.split(key)
    p = {"backbone": backbone_init(kb, width)}
    ci = width * 3
    if task == "detection":
        k1, k2, k3 = jax.random.split(kh, 3)
        p["heat"] = head_init(k1, ci, 1)
        p["wh"] = head_init(k2, ci, 2)
        p["off"] = head_init(k3, ci, 2)
    elif task == "segmentation":
        p["seg"] = head_init(kh, ci, 2)
    elif task == "keypoint":
        p["kp"] = head_init(kh, ci, 5)
    else:
        raise ValueError(task)
    return p


def apply_net(task: str, params, frames, precision=None):
    """frames (B, H, W, 3) -> dict of dense outputs at stride 8, every
    convolution at ``precision`` (None: JAX's default matmul
    precision)."""
    f = backbone(params["backbone"], frames, precision)
    heads = {"detection": ("heat", "wh", "off"), "segmentation": ("seg",),
             "keypoint": ("kp",)}[task]
    return {k: head(params[k], f, precision) for k in heads}


# ---------------------------------------------------------------------------
# ground-truth target rendering (for training D itself on synthetic scenes)
# ---------------------------------------------------------------------------
def render_detection_targets(boxes_per_frame, H, W):
    hs, ws = H // STRIDE, W // STRIDE
    B = len(boxes_per_frame)
    heat = np.zeros((B, hs, ws, 1), np.float32)
    wh = np.zeros((B, hs, ws, 2), np.float32)
    mask = np.zeros((B, hs, ws, 1), np.float32)
    yy, xx = np.mgrid[0:hs, 0:ws]
    for b, boxes in enumerate(boxes_per_frame):
        for (x0, y0, x1, y1) in boxes:
            cx, cy = (x0 + x1) / 2 / STRIDE, (y0 + y1) / 2 / STRIDE
            w, h = (x1 - x0) / STRIDE, (y1 - y0) / STRIDE
            if w < 0.5 or h < 0.5:
                continue
            sig = max(0.8, 0.15 * np.sqrt(w * h))
            g = np.exp(-((xx - cx) ** 2 + (yy - cy) ** 2) / (2 * sig ** 2))
            heat[b, :, :, 0] = np.maximum(heat[b, :, :, 0], g)
            ci, cj = int(np.clip(cy, 0, hs - 1)), int(np.clip(cx, 0, ws - 1))
            wh[b, ci, cj] = (w, h)
            mask[b, ci, cj] = 1.0
    return jnp.asarray(heat), jnp.asarray(wh), jnp.asarray(mask)


def detection_train_loss(params, frames, targets):
    out = apply_net("detection", params, frames)
    heat_t, wh_t, mask = targets
    p = jax.nn.sigmoid(out["heat"])
    pos = (heat_t > 0.95).astype(jnp.float32)
    # penalty-reduced focal loss (CenterNet)
    lp = -pos * ((1 - p) ** 2) * jnp.log(p + 1e-6)
    ln = -(1 - pos) * ((1 - heat_t) ** 4) * (p ** 2) * jnp.log(1 - p + 1e-6)
    n_pos = jnp.maximum(pos.sum(), 1.0)
    l_heat = (lp + ln).sum() / n_pos
    l_wh = (jnp.abs(out["wh"] - wh_t) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return l_heat + 0.1 * l_wh


def segmentation_train_loss(params, frames, seg_t):
    out = apply_net("segmentation", params, frames)["seg"]
    logp = jax.nn.log_softmax(out, axis=-1)
    onehot = jax.nn.one_hot(seg_t, 2)
    return -(onehot * logp).mean() * 2.0


def keypoint_train_loss(params, frames, kp_heat_t):
    out = apply_net("keypoint", params, frames)["kp"]
    return jnp.mean((jax.nn.sigmoid(out) - kp_heat_t) ** 2) * 100.0


def render_kp_targets(kps_per_frame, H, W, K=5):
    hs, ws = H // STRIDE, W // STRIDE
    B = len(kps_per_frame)
    heat = np.zeros((B, hs, ws, K), np.float32)
    yy, xx = np.mgrid[0:hs, 0:ws]
    for b, persons in enumerate(kps_per_frame):
        for kps in persons:
            for k in range(min(K, len(kps))):
                cx, cy = kps[k][0] / STRIDE, kps[k][1] / STRIDE
                g = np.exp(-((xx - cx) ** 2 + (yy - cy) ** 2) / (2 * 1.5 ** 2))
                heat[b, :, :, k] = np.maximum(heat[b, :, :, k], g)
    return jnp.asarray(heat)


# ---------------------------------------------------------------------------
# decoding + accuracy metrics (host-side, vs D(H))
# ---------------------------------------------------------------------------
def detection_keep_heat(out):
    """Device half of :func:`decode_detections`: sigmoid + 3x3 max-pool NMS.
    Returns the suppressed heat (B, hs, ws). The batched server fleet step
    precomputes this inside its jitted program (key ``"keep"``) so the
    host-side decode is pure numpy and can overlap the next chunk's camera
    step instead of enqueuing device work behind it."""
    heat = jax.nn.sigmoid(out["heat"])
    pooled = jax.lax.reduce_window(heat, -jnp.inf, jax.lax.max,
                                   (1, 3, 3, 1), (1, 1, 1, 1), "SAME")
    return jnp.where(heat >= pooled - 1e-6, heat, 0.0)[..., 0]


def decode_detections(out, thresh=0.3, topk=50):
    """-> per-frame list of (x0, y0, x1, y1, score)."""
    keep = out["keep"] if "keep" in out else detection_keep_heat(out)
    keep_np = np.asarray(keep)
    wh = np.asarray(out["wh"])
    results = []
    for b in range(keep_np.shape[0]):
        ys, xs = np.where(keep_np[b] >= thresh)
        scores = keep_np[b][ys, xs]
        order = np.argsort(-scores)[:topk]
        dets = []
        for i in order:
            y, x = ys[i], xs[i]
            w, h = np.maximum(wh[b, y, x], 0.5)
            cx, cy = (x + 0.5) * STRIDE, (y + 0.5) * STRIDE
            dets.append((cx - w * STRIDE / 2, cy - h * STRIDE / 2,
                         cx + w * STRIDE / 2, cy + h * STRIDE / 2,
                         float(scores[i])))
        results.append(dets)
    return results


def _iou(a, b):
    ix0, iy0 = max(a[0], b[0]), max(a[1], b[1])
    ix1, iy1 = min(a[2], b[2]), min(a[3], b[3])
    iw, ih = max(0.0, ix1 - ix0), max(0.0, iy1 - iy0)
    inter = iw * ih
    ua = (a[2] - a[0]) * (a[3] - a[1]) + (b[2] - b[0]) * (b[3] - b[1]) - inter
    return inter / ua if ua > 0 else 0.0


def detection_f1(dets, refs, iou_thresh=0.5):
    """Mean F1 across frames, greedy IoU matching vs D(H) detections."""
    f1s = []
    for d, r in zip(dets, refs):
        if not r and not d:
            f1s.append(1.0)
            continue
        matched = set()
        tp = 0
        for box in sorted(d, key=lambda x: -x[4]):
            best, bi = 0.0, -1
            for j, rb in enumerate(r):
                if j in matched:
                    continue
                i = _iou(box, rb)
                if i > best:
                    best, bi = i, j
            if best >= iou_thresh:
                matched.add(bi)
                tp += 1
        prec = tp / max(len(d), 1)
        rec = tp / max(len(r), 1)
        f1s.append(2 * prec * rec / max(prec + rec, 1e-9))
    return float(np.mean(f1s)) if f1s else 1.0


def _class_counts(a, b, n_classes):
    """Pixels of each class's intersection and union in two stacks of
    label maps, row by row: (R, ...) int maps -> two (R, C) int64."""
    rows = a.shape[0]
    idx_a = (np.arange(rows).reshape((rows,) + (1,) * (a.ndim - 1))
             * n_classes + a)
    idx_b = idx_a - a + b
    count = lambda idx: np.bincount(idx.ravel(), minlength=rows
                                    * n_classes).reshape(rows, n_classes)
    inter = count(idx_a[a == b])
    return inter, count(idx_a) + count(idx_b) - inter


def _mean_iou(inter, union) -> float:
    """Mean IoU over every class present in either map (1.0 where no
    class is)."""
    present = union > 0
    if not present.any():
        return 1.0
    return float(np.mean(inter[present] / union[present]))


def segmentation_iou(out, ref_out):
    """Mean IoU of the two argmax label maps over every class present in
    either."""
    return float(segmentation_iou_batched(
        {"seg": np.asarray(out["seg"])[None]},
        {"seg": np.asarray(ref_out["seg"])[None]})[0])


def keypoint_accuracy(out, ref_out, radius=2.0):
    """Distance-based accuracy: fraction of keypoints within ``radius``
    head-units of the reference prediction."""
    def peaks(o):
        h = np.asarray(jax.nn.sigmoid(o["kp"]))
        B, hs, ws, K = h.shape
        flat = h.reshape(B, hs * ws, K).argmax(axis=1)
        return np.stack([flat // ws, flat % ws], axis=-1)  # (B, K, 2)

    pa, pb = peaks(out), peaks(ref_out)
    d = np.sqrt(((pa - pb) ** 2).sum(-1))
    return float((d <= radius).mean())


# ---------------------------------------------------------------------------
# batched (per-lane) accuracy — the vectorized host scoring path
# ---------------------------------------------------------------------------
# The fleet engine's server step emits one output tree whose leaves carry a
# leading lane axis: (N, T, hs, ws, C). The legacy host path sliced lane i
# out of that tree and called ``FinalDNN.accuracy`` N times per chunk — an
# O(streams) Python loop. These `_batched` variants score every lane in one
# numpy pass and are engineered to match the sliced per-lane calls
# *bit-for-bit* (same reductions in the same order per lane), which the
# aggregation parity tests pin.

def _decode_detection_frames(keep_np, wh, thresh=0.3, topk=50):
    """Decode a flat (F, hs, ws) stack of suppressed heatmaps into padded
    box arrays: ``(boxes, counts)``, boxes (F, K, 4) float64 corners
    (x0, y0, x1, y1) in descending score order, counts (F,) the number of
    real boxes per frame, K = max(counts, 1) and the padding zeros.

    One global ``np.where`` + searchsorted frame grouping replaces F
    per-frame ``np.where`` calls; row-major ordering gives each frame the
    candidate order, and the same per-frame ``np.argsort`` the same
    tiebreaks, as :func:`decode_detections` on that frame alone. The
    corners follow its dtype path too (half extents in ``wh``'s dtype,
    centres and corners in float64), so every coordinate is bit-equal."""
    n_frames = keep_np.shape[0]
    fs, ys_all, xs_all = np.where(keep_np >= thresh)
    bounds = np.searchsorted(fs, np.arange(n_frames + 1))
    scores = keep_np[fs, ys_all, xs_all]
    picks = np.concatenate([lo + np.argsort(-scores[lo:hi])[:topk]
                            for lo, hi in zip(bounds[:-1], bounds[1:])])
    counts = np.minimum(np.diff(bounds), topk)
    frame = np.repeat(np.arange(n_frames), counts)
    rank = np.arange(picks.size) - np.repeat(np.cumsum(counts) - counts,
                                             counts)
    ys, xs = ys_all[picks], xs_all[picks]
    half = np.maximum(wh[frame, ys, xs], 0.5) * STRIDE / 2   # (M, 2)
    cx, cy = (xs + 0.5) * STRIDE, (ys + 0.5) * STRIDE
    corners = np.stack([cx - half[:, 0], cy - half[:, 1],
                        cx + half[:, 0], cy + half[:, 1]], axis=-1)
    boxes = np.zeros((n_frames, max(int(counts.max()), 1), 4), np.float64)
    boxes[frame, rank] = corners
    return boxes, counts


def _iou_frames(a, b):
    """(F, Ka, Kb) IoU of every box pair within each frame for padded
    (F, K, 4) box arrays: :func:`_iou`'s operations in its order."""
    a, b = a[:, :, None, :], b[:, None, :, :]
    ix0, iy0 = np.maximum(a[..., 0], b[..., 0]), np.maximum(a[..., 1],
                                                             b[..., 1])
    ix1, iy1 = np.minimum(a[..., 2], b[..., 2]), np.minimum(a[..., 3],
                                                             b[..., 3])
    inter = np.maximum(0.0, ix1 - ix0) * np.maximum(0.0, iy1 - iy0)
    ua = ((a[..., 2] - a[..., 0]) * (a[..., 3] - a[..., 1])
          + (b[..., 2] - b[..., 0]) * (b[..., 3] - b[..., 1]) - inter)
    return np.divide(inter, ua, out=np.zeros_like(inter), where=ua > 0)


def _lane_keep(out):
    """Suppressed detection heat for a (N, T, ...) lane tree, flattened to
    (N*T, hs, ws). Uses the precomputed ``"keep"`` when the server fleet
    step shipped it; otherwise folds lanes into the batch axis so the 4-D
    max-pool NMS applies unchanged."""
    if "keep" in out:
        keep = np.asarray(out["keep"])
        return keep.reshape((-1,) + keep.shape[2:])
    heat = np.asarray(out["heat"])
    n, t = heat.shape[:2]
    flat = {"heat": heat.reshape((n * t,) + heat.shape[2:])}
    return np.asarray(detection_keep_heat(flat))


def detection_f1_batched(out, ref_out, iou_thresh=0.5):
    """Per-lane mean-F1 for lane trees with leaves (N, T, ...); returns
    (N,) float64, each entry bit-equal to ``detection_f1`` on that lane's
    slice.

    One vectorized numpy pass over all N*T frames, with no Python work per
    box or box pair: padded decode, one (F, K, K) IoU tensor, and the
    greedy match stepped over detection rank (at most ``topk`` steps of
    (F, K) arrays). Detections come out of the decode in descending score
    order, which is ``detection_f1``'s greedy order; at each rank the
    first-index argmax over the still-unmatched references is its
    ``i > best`` scan from ``best = 0.0``. It stays on the host: the
    references are often precomputed D(H) held there, and a float32
    device IoU would not be bit-equal to this float64 one."""
    wh = np.asarray(out["wh"])
    n, t = wh.shape[:2]
    if n * t == 0:
        return np.ones(n, np.float64)
    ref_wh = np.asarray(ref_out["wh"])
    dets, n_d = _decode_detection_frames(
        _lane_keep(out), wh.reshape((n * t,) + wh.shape[2:]))
    refs, n_r = _decode_detection_frames(
        _lane_keep(ref_out), ref_wh.reshape((n * t,) + ref_wh.shape[2:]))
    iou = _iou_frames(dets, refs)                     # (F, Kd, Kr)
    frames = np.arange(n * t)
    matched = np.arange(refs.shape[1]) >= n_r[:, None]   # padding: taken
    tp = np.zeros(n * t, np.int64)
    for k in range(int(n_d.max())):
        row = np.where(matched, -1.0, iou[:, k])
        j = row.argmax(axis=1)
        top = row[frames, j]
        # detection_f1's best starts at 0.0 and moves on a strictly
        # larger IoU, so it ends at max(0, top), matching ref j iff top > 0
        hit = (k < n_d) & (np.maximum(top, 0.0) >= iou_thresh)
        take = hit & (top > 0)
        matched[frames[take], j[take]] = True
        tp += hit
    prec = tp / np.maximum(n_d, 1)
    rec = tp / np.maximum(n_r, 1)
    f1 = np.where((n_d == 0) & (n_r == 0), 1.0,
                  2 * prec * rec / np.maximum(prec + rec, 1e-9))
    return f1.reshape(n, t).mean(axis=1)


def segmentation_iou_batched(out, ref_out):
    """Per-lane segmentation IoU for (N, T, hs, ws, C) trees -> (N,):
    each lane's mean IoU over every class in either label map, the
    classes' counts in one numpy pass."""
    seg = np.asarray(out["seg"])
    a = np.argmax(seg, -1)                            # (N, T, hs, ws)
    b = np.argmax(np.asarray(ref_out["seg"]), -1)
    inter, union = _class_counts(a, b, seg.shape[-1])
    return np.array([_mean_iou(i, u) for i, u in zip(inter, union)],
                    np.float64)


def keypoint_accuracy_batched(out, ref_out, radius=2.0):
    """Per-lane keypoint accuracy for (N, T, hs, ws, K) trees -> (N,)."""
    def peaks(o):
        h = np.asarray(jax.nn.sigmoid(o["kp"]))
        n, t, hs, ws, k = h.shape
        flat = h.reshape(n, t, hs * ws, k).argmax(axis=2)
        return np.stack([flat // ws, flat % ws], axis=-1)  # (N, T, K, 2)

    pa, pb = peaks(out), peaks(ref_out)
    d = np.sqrt(((pa - pb) ** 2).sum(-1))
    return (d <= radius).mean(axis=(1, 2)).astype(np.float64)


def device_lane_accuracy(task, out, ref_out):
    """Pure-jnp per-lane accuracy (N,) for (N, T, ...) lane trees —
    jit/shard_map-safe, so the fleet step can reduce accuracy on device
    and ship O(N) scalars to host instead of full output trees.

    Only segmentation and keypoint reduce on device; detection's greedy
    F1 matching is data-dependent and stays on the (batched numpy) host
    path. Device math is float32, so results track the float64 host path
    to ~1e-6 rather than bit-exactly — the windowed bench keeps a
    host-scored parity stage for the bit-equal rows.
    """
    if task == "segmentation":
        a = jnp.argmax(out["seg"], -1)
        b = jnp.argmax(ref_out["seg"], -1)
        axes = tuple(range(1, a.ndim))
        iou_sum = jnp.zeros(a.shape[0], jnp.float32)
        n_valid = jnp.zeros(a.shape[0], jnp.float32)
        for cls in range(out["seg"].shape[-1]):
            inter = ((a == cls) & (b == cls)).sum(axis=axes)
            union = ((a == cls) | (b == cls)).sum(axis=axes)
            valid = union > 0
            iou = jnp.where(valid, inter / jnp.maximum(union, 1), 0.0)
            iou_sum += iou.astype(jnp.float32)
            n_valid += valid.astype(jnp.float32)
        return jnp.where(n_valid > 0, iou_sum / jnp.maximum(n_valid, 1.0),
                         1.0)
    if task == "keypoint":
        def peaks(o):
            h = jax.nn.sigmoid(o["kp"])
            n, t, hs, ws, k = h.shape
            flat = h.reshape(n, t, hs * ws, k).argmax(axis=2)
            return jnp.stack([flat // ws, flat % ws], axis=-1)

        pa, pb = peaks(out), peaks(ref_out)
        d = jnp.sqrt(((pa - pb) ** 2).sum(-1).astype(jnp.float32))
        return (d <= 2.0).mean(axis=(1, 2))
    raise ValueError(f"no device accuracy reduction for task {task!r} "
                     f"(detection decodes on host)")


# ---------------------------------------------------------------------------
# the black-box wrapper used by AccMPEG
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class FinalDNN:
    """A server DNN: its task, its parameters, and the forward pass and
    convolution precision of its architecture. ``forward(params, frames,
    precision)`` defaults to the repository's convnet (``apply_net`` for
    the task), ``precision`` (a ``lax.Precision``) to JAX's default
    matmul precision; ``name`` labels the server in spans and
    counters."""
    task: str
    params: dict
    name: str = "convnet"
    forward: Optional[Callable] = None
    precision: Optional[jax.lax.Precision] = None

    def __post_init__(self):
        if self.forward is None:
            self.forward = functools.partial(apply_net, self.task)

    def apply(self, params, frames):
        """The forward pass on ``params``, which the fleet steps pass as
        arguments of their jitted programs."""
        return self.forward(params, frames, self.precision)

    @property
    def precision_name(self) -> str:
        """The convolutions' precision: the stated one, else JAX's
        default matmul precision at the time of the call."""
        if self.precision is not None:
            return self.precision.name.lower()
        return str(jax.config.jax_default_matmul_precision or "default")

    def __call__(self, frames):
        return self.apply(self.params, frames)

    @functools.cached_property
    def _jit_apply(self):
        return jax.jit(self.apply)

    def predict(self, frames):
        return self._jit_apply(self.params, frames)

    # differentiable proxy of Acc(D(X); D(H)) — fn.15 of the paper
    def proxy_loss(self, frames, ref_out):
        out = self(frames)
        if self.task == "detection":
            ph = jax.nn.sigmoid(jax.lax.stop_gradient(ref_out["heat"]))
            p = jax.nn.sigmoid(out["heat"])
            l = jnp.mean((p - ph) ** 2) * 100.0
            mask = (ph > 0.3).astype(jnp.float32)
            l += (jnp.abs(out["wh"] - jax.lax.stop_gradient(ref_out["wh"]))
                  * mask).sum() / jnp.maximum(mask.sum(), 1.0) * 0.1
            return l
        if self.task == "segmentation":
            ref = jax.lax.stop_gradient(
                jax.nn.softmax(ref_out["seg"], axis=-1))
            logp = jax.nn.log_softmax(out["seg"], axis=-1)
            return -(ref * logp).mean() * 10.0
        ref = jax.lax.stop_gradient(jax.nn.sigmoid(ref_out["kp"]))
        return jnp.mean((jax.nn.sigmoid(out["kp"]) - ref) ** 2) * 100.0

    def accuracy(self, out, ref_out) -> float:
        if self.task == "detection":
            return detection_f1(decode_detections(out),
                                decode_detections(ref_out))
        if self.task == "segmentation":
            return segmentation_iou(out, ref_out)
        return keypoint_accuracy(out, ref_out)

    def accuracy_batched(self, out, ref_out) -> np.ndarray:
        """Score every lane of a (N, T, ...) output tree in one numpy
        pass -> (N,) float64, lane i bit-equal to ``accuracy`` on lane
        i's slice."""
        if self.task == "detection":
            return detection_f1_batched(out, ref_out)
        if self.task == "segmentation":
            return segmentation_iou_batched(out, ref_out)
        return keypoint_accuracy_batched(out, ref_out)

    @property
    def supports_device_accuracy(self) -> bool:
        """Whether :func:`device_lane_accuracy` can reduce this task's
        accuracy inside the jitted fleet step (detection cannot: greedy
        box matching stays on host)."""
        return self.task in ("segmentation", "keypoint")
