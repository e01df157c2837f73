"""FCN-ResNet101, the paper's segmentation server DNN (torchvision
``fcn_resnet101``; Long et al. 2015, He et al. 2016), written plainly:

- ImageNet input normalisation;
- stem: 7x7/2 conv to 64 channels, BN, ReLU, 3x3/2 max-pool;
- bottleneck blocks 3-4-23-3 (1x1 conv to the width, 3x3 conv with the
  stride and the dilation, 1x1 conv to 4x the width, each with BN; the
  first block of a layer adds a 1x1 projection of its input with BN),
  widths 64/128/256/512, with ``replace_stride_with_dilation=[False,
  True, True]``: ``layer3`` and ``layer4`` stay at stride 8, their first
  blocks at the dilation of the layer before (1, then 2), the other
  blocks at 2 and 4;
- ``FCNHead(2048, 21)``: 3x3 conv to 512, BN, ReLU, 1x1 conv to 21
  classes; logits at stride 8 (torchvision upsamples them x8 before the
  argmax; the benchmark's server contract takes stride-8 outputs).

Every convolution uses torchvision's symmetric padding and runs at the
precision the configuration states for the server (``server_precision``;
``"default"``: the backend's ``Precision.DEFAULT``, one bf16 pass with
f32 accumulation on a TPU, as the program serves it), else through
``nets.product`` at the precision asked for; eval-mode BN is a
per-channel scale and shift after it. The controls lower the camera
side's precision (``"high"``, ``"bf16_3x"``); no control goes below one
bf16 pass, so the server keeps its stated precision under them.

Frames go through ``BLOCK`` at a time, so the reference holds a bounded
share of the activations (~0.45 GB a 720p frame).

Weights (``init``): every conv He-normal (std ``sqrt(2 / fan_in)``); BN
shift 0 and scale 1, but for the last conv of each residual branch,
whose scale is ``BRANCH_SCALE = 1 / sqrt(2 * 33)``: each of the 33
blocks then adds at most ``1 / 33`` of its input's second moment, so
the residual stream stays of order 1 through the network; the
classifier is normal with std ``sqrt(1 / 512)``, bias 0.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import nets
from chipbench.flops import conv_macs

CLASSES = 21
STRIDE = 8
EXPANSION = 4
HEAD_WIDTH = 512
#: (name, width, blocks, stride of the first block, dilation of the
#: first block, dilation of the others)
LAYERS = (("layer1", 64, 3, 1, 1, 1),
          ("layer2", 128, 4, 2, 1, 1),
          ("layer3", 256, 23, 1, 1, 2),
          ("layer4", 512, 3, 1, 2, 4))
BLOCKS = sum(n for _, _, n, *_ in LAYERS)
BRANCH_SCALE = 1.0 / math.sqrt(2.0 * BLOCKS)
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)
BLOCK = 2  # frames per pass of the reference


def _conv_init(key, k, ci, co, scale=1.0):
    w = jax.random.normal(key, (k, k, ci, co), jnp.float32)
    return {"w": w * math.sqrt(2.0 / (k * k * ci)),
            "scale": jnp.full((co,), scale, jnp.float32),
            "shift": jnp.zeros((co,), jnp.float32)}


def _block_init(key, ci, width, project):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"c1": _conv_init(k1, 1, ci, width),
         "c2": _conv_init(k2, 3, width, width),
         "c3": _conv_init(k3, 1, width, EXPANSION * width, BRANCH_SCALE)}
    if project:
        p["down"] = _conv_init(k4, 1, ci, EXPANSION * width)
    return p


def init(key, cfg):
    """Seeded weights in the program's layout (``repro.vision.fcn``):
    each layer's first block, then its other blocks stacked on a leading
    axis."""
    ks = jax.random.split(key, len(LAYERS) + 2)
    p = {"stem": _conv_init(ks[0], 7, 3, 64)}
    ci = 64
    for (name, width, n, *_), k in zip(LAYERS, ks[1:]):
        kf, kr = jax.random.split(k)
        co = EXPANSION * width
        p[name] = {"first": _block_init(kf, ci, width, True),
                   "rest": jax.vmap(lambda kb: _block_init(
                       kb, co, width, False))(jax.random.split(kr, n - 1))}
        ci = co
    k1, k2 = jax.random.split(ks[-1])
    w = jax.random.normal(k2, (1, 1, HEAD_WIDTH, CLASSES), jnp.float32)
    p["head"] = {"c1": _conv_init(k1, 3, ci, HEAD_WIDTH),
                 "c2": {"w": w * math.sqrt(1.0 / HEAD_WIDTH),
                        "b": jnp.zeros((CLASSES,), jnp.float32)}}
    return p


def _conv(w, x, precision, stride=1, dilation=1):
    pad = dilation * (w.shape[0] - 1) // 2

    def f(a, b, lax_precision):
        return jax.lax.conv_general_dilated(
            a, b, (stride, stride), ((pad, pad), (pad, pad)),
            rhs_dilation=(dilation, dilation),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax_precision)
    if precision == "default":
        return f(x, w, jax.lax.Precision.DEFAULT)
    return nets.product(f, x, w, precision)


def _bn(p, y):
    return y * p["scale"] + p["shift"]


def _bottleneck(p, x, precision, stride, dilation):
    y = jax.nn.relu(_bn(p["c1"], _conv(p["c1"]["w"], x, precision)))
    y = jax.nn.relu(_bn(p["c2"], _conv(p["c2"]["w"], y, precision, stride,
                                       dilation)))
    y = _bn(p["c3"], _conv(p["c3"]["w"], y, precision))
    if "down" in p:
        x = _bn(p["down"], _conv(p["down"]["w"], x, precision, stride))
    return jax.nn.relu(y + x)


def _net(p, x, precision):
    x = (x - jnp.asarray(MEAN)) / jnp.asarray(STD)
    x = jax.nn.relu(_bn(p["stem"], _conv(p["stem"]["w"], x, precision, 2)))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    for name, _, _, stride, d_first, d_rest in LAYERS:
        x = _bottleneck(p[name]["first"], x, precision, stride, d_first)
        # the other blocks of the layer, one after another (one compiled
        # body a layer)
        x = jax.lax.scan(
            lambda x, block, d=d_rest: (_bottleneck(block, x, precision, 1,
                                                    d), None),
            x, p[name]["rest"])[0]
    h = p["head"]
    x = jax.nn.relu(_bn(h["c1"], _conv(h["c1"]["w"], x, precision)))
    return _conv(h["c2"]["w"], x, precision) + h["c2"]["b"]


def forward(params, frames, cfg, precision):
    """(B, H, W, 3) -> {"seg": (B, H/8, W/8, 21)}, ``BLOCK`` frames at a
    time, every convolution at ``cfg["server_precision"]`` where the
    configuration states one, else at ``precision``."""
    precision = cfg.get("server_precision", precision)
    B = frames.shape[0]
    blk = min(B, BLOCK)
    n = -(-B // blk)
    x = jnp.concatenate([frames, jnp.zeros((n * blk - B,) + frames.shape[1:],
                                           frames.dtype)])
    seg = jax.lax.map(lambda f: _net(params, f, precision),
                      x.reshape((n, blk) + frames.shape[1:]))
    return {"seg": seg.reshape((n * blk,) + seg.shape[2:])[:B]}


def program(params, cfg):
    from repro.vision import fcn

    return fcn.final_dnn(params, cfg["server_precision"])


def frame_flops(cfg) -> float:
    """2 x the multiply-adds of one frame: the stem, every bottleneck
    (its first 1x1 conv at the block's input resolution, the rest at its
    output's), the projections, and the head."""
    H, W = cfg["height"], cfg["width"]
    h, w = H // 2, W // 2
    macs = conv_macs(h, w, 7, 3, 64)
    h, w = h // 2, w // 2  # the max-pool
    ci = 64
    for _, width, n, stride, _, _ in LAYERS:
        co = EXPANSION * width
        for b in range(n):
            cin = ci if b == 0 else co
            macs += conv_macs(h, w, 1, cin, width)
            s = stride if b == 0 else 1
            ho, wo = h // s, w // s
            macs += conv_macs(ho, wo, 3, width, width)
            macs += conv_macs(ho, wo, 1, width, co)
            if b == 0:
                macs += conv_macs(ho, wo, 1, cin, co)
            h, w = ho, wo
        ci = co
    macs += conv_macs(h, w, 3, ci, HEAD_WIDTH)
    macs += conv_macs(h, w, 1, HEAD_WIDTH, CLASSES)
    return 2.0 * macs
