"""Plain convnets of the reference: the camera AccModel and the server DNNs.

Weights are drawn here from the seed, in the parameter layout the program
takes (``{"stem": {"w", "b"}, "b1": {"dw": ..., "pw": ...}, ...}``), and
handed to both the program and the reference. The forward passes follow
the published architecture of the repository's models: a stride-16
MobileNet-style AccModel with three appended convs, and a stride-8
depthwise-separable backbone with one 3x3 + 1x1 head per output.

Every convolution runs at ``precision``: ``"highest"`` (f32);
``"bf16_3x"``, which splits each f32 operand into a bf16 high part and a
bf16 low part (``lax.reduce_precision``, which XLA may not fold away as
it may a convert pair) and sums the three largest partial products, as a
TPU's ``Precision.HIGH`` does, on any backend; or ``"high"``, the
backend's own ``Precision.HIGH`` (three bf16 passes on a TPU, f32 on a
CPU). The last two are the control's arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
HIGH = jax.lax.Precision.HIGH
HEADS = {"detection": (("heat", 1), ("wh", 2), ("off", 2)),
         "segmentation": (("seg", 2),)}


def _conv_init(key, k, ci, co):
    w = jax.random.normal(key, (k, k, ci, co), jnp.float32)
    return {"w": w / jnp.sqrt(float(k * k * ci)),
            "b": jnp.zeros((co,), jnp.float32)}


def _dw_sep_init(key, ci, co):
    k1, k2 = jax.random.split(key)
    return {"dw": _conv_init(k1, 3, 1, ci), "pw": _conv_init(k2, 1, ci, co)}


def accmodel_init(key, width: int):
    ks = jax.random.split(key, 8)
    w = width
    return {"stem": _conv_init(ks[0], 3, 3, w),
            "b1": _dw_sep_init(ks[1], w, 2 * w),
            "b2": _dw_sep_init(ks[2], 2 * w, 4 * w),
            "b3": _dw_sep_init(ks[3], 4 * w, 8 * w),
            "b4": _dw_sep_init(ks[4], 8 * w, 8 * w),
            "c1": _conv_init(ks[5], 3, 8 * w, 4 * w),
            "c2": _conv_init(ks[6], 3, 4 * w, 2 * w),
            "c3": _conv_init(ks[7], 1, 2 * w, 1)}


def dnn_init(key, task: str, width: int):
    kb, kh = jax.random.split(key)
    ks = jax.random.split(kb, 5)
    w = width
    p = {"backbone": {"stem": _conv_init(ks[0], 3, 3, w // 2),
                      "b1": _dw_sep_init(ks[1], w // 2, w),
                      "b2": _dw_sep_init(ks[2], w, 2 * w),
                      "b3": _dw_sep_init(ks[3], 2 * w, 3 * w),
                      "b4": _dw_sep_init(ks[4], 3 * w, 3 * w)}}
    for (name, co), k in zip(HEADS[task],
                             jax.random.split(kh, len(HEADS[task]))):
        k1, k2 = jax.random.split(k)
        p[name] = {"c1": _conv_init(k1, 3, 3 * w, 64),
                   "c2": _conv_init(k2, 1, 64, co)}
    return p


@functools.partial(jax.jit, static_argnames=("task", "acc_width",
                                             "dnn_width"))
def make_weights(key, task: str, acc_width: int, dnn_width: int):
    """(AccModel params, server-DNN params), f32, in one device call."""
    ka, kd = jax.random.split(key)
    return accmodel_init(ka, acc_width), dnn_init(kd, task, dnn_width)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------
def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def product(fn, a, b, precision: str):
    """``fn(a, b, lax_precision)`` for a bilinear ``fn`` at the named
    precision."""
    if precision == "highest":
        return fn(a, b, HI)
    if precision == "high":
        return fn(a, b, HIGH)
    if precision == "bf16_3x":
        ah, bh = _bf16(a), _bf16(b)
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return fn(ah, bh, HI) + (fn(ah, bl, HI) + fn(al, bh, HI))
    raise ValueError(f"unknown precision {precision!r}")


def conv(p, x, precision, stride=1, groups=1):
    def f(a, w, lax_precision):
        return jax.lax.conv_general_dilated(
            a, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=lax_precision)
    return product(f, x, p["w"], precision) + p["b"]


def dw_sep(p, x, precision, stride=1):
    ci = x.shape[-1]
    dw = {"w": p["dw"]["w"].reshape(3, 3, 1, ci), "b": p["dw"]["b"]}
    x = jax.nn.relu(conv(dw, x, precision, stride, groups=ci))
    return jax.nn.relu(conv(p["pw"], x, precision))


def accmodel_logits(p, frames, precision):
    """(B, H, W, 3) -> per-macroblock logits (B, H/16, W/16)."""
    x = jax.nn.relu(conv(p["stem"], frames, precision, 2))
    for name, s in (("b1", 2), ("b2", 2), ("b3", 2), ("b4", 1)):
        x = dw_sep(p[name], x, precision, s)
    x = jax.nn.relu(conv(p["c1"], x, precision))
    x = jax.nn.relu(conv(p["c2"], x, precision))
    return conv(p["c3"], x, precision)[..., 0]


def dnn_outputs(task, p, frames, precision):
    """(B, H, W, 3) -> {head: (B, H/8, W/8, C)}."""
    b = p["backbone"]
    x = jax.nn.relu(conv(b["stem"], frames, precision, 2))
    for name, s in (("b1", 2), ("b2", 2), ("b3", 1), ("b4", 1)):
        x = dw_sep(b[name], x, precision, s)
    return {name: conv(p[name]["c2"],
                       jax.nn.relu(conv(p[name]["c1"], x, precision)),
                       precision)
            for name, _ in HEADS[task]}
