"""FCN-ResNet101, the paper's segmentation server DNN, as torchvision's
``fcn_resnet101`` builds it (Long et al. 2015 on He et al. 2016):

- ImageNet input normalisation;
- stem: 7x7/2 conv to 64 channels, then a 3x3/2 max-pool;
- ``layer1``-``layer4``: bottleneck blocks 3-4-23-3 of widths
  64/128/256/512, expanded x4, the stride on each block's 3x3 conv;
  ``replace_stride_with_dilation=[False, True, True]`` keeps ``layer3``
  and ``layer4`` at stride 8 and dilates them, each layer's first block
  at the dilation before it (``layer3`` 1 then 2, ``layer4`` 2 then 4);
- ``FCNHead(2048, 21)``: 3x3 conv to 512 channels, BN, ReLU, 1x1 conv to
  the 21 classes (dropout is the identity at inference; the auxiliary
  head is not computed).

NHWC, f32 weights and activations. Eval-mode BN is folded into each
conv's per-channel ``scale`` and ``shift``. Parameters::

    {"stem": conv,
     "layer1".."layer4": {"first": block, "rest": blocks stacked on a
                          leading axis},
     "head": {"c1": conv, "c2": {"w", "b"}}}

with ``conv = {"w": (k, k, ci, co), "scale": (co,), "shift": (co,)}`` and
``block = {"c1": 1x1, "c2": 3x3, "c3": 1x1[, "down": 1x1]}``.

``forward`` returns ``{"seg": (B, H/8, W/8, 21)}`` logits at stride 8.
Frames run through the network ``FRAME_BLOCK`` at a time (a ``lax.map``
over blocks), which bounds the activations a call holds: ResNet-101's
f32 activations at 720p are ~0.4-0.5 GB a frame.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

ARCH = "fcn_resnet101"
CLASSES = 21
STRIDE = 8
EXPANSION = 4
HEAD_WIDTH = 512
#: (name, width, blocks, stride of the first block, (dilation of the
#: first block, of the others))
LAYERS = (("layer1", 64, 3, 1, (1, 1)),
          ("layer2", 128, 4, 2, (1, 1)),
          ("layer3", 256, 23, 1, (1, 2)),
          ("layer4", 512, 3, 1, (2, 4)))
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)
#: frames per pass through the network; the fastest of 2, 5 and 10 that
#: fits one v5e beside the camera step at 4 streams of 720p (PERF.md)
FRAME_BLOCK = 5

_DN = ("NHWC", "HWIO", "NHWC")


def _conv(w, x, precision, stride=1, dilation=1):
    """Convolution with torchvision's symmetric padding, ``dilation *
    (k - 1) / 2`` on each side."""
    pad = dilation * (w.shape[0] - 1) // 2
    return lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        rhs_dilation=(dilation, dilation), dimension_numbers=_DN,
        precision=precision)


def _conv_bn(p, x, precision, stride=1, dilation=1, relu=True):
    y = _conv(p["w"], x, precision, stride, dilation) * p["scale"] \
        + p["shift"]
    return jax.nn.relu(y) if relu else y


def _bottleneck(p, x, precision, stride, dilation):
    y = _conv_bn(p["c1"], x, precision)
    y = _conv_bn(p["c2"], y, precision, stride, dilation)
    y = _conv_bn(p["c3"], y, precision, relu=False)
    skip = _conv_bn(p["down"], x, precision, stride, relu=False) \
        if "down" in p else x
    return jax.nn.relu(y + skip)


def _layer(p, x, precision, stride, dilations):
    x = _bottleneck(p["first"], x, precision, stride, dilations[0])

    def block(x, bp):
        return _bottleneck(bp, x, precision, 1, dilations[1]), None

    return lax.scan(block, x, p["rest"])[0]


def _network(params, x, precision):
    with jax.named_scope("stem"):
        x = (x - jnp.asarray(MEAN, x.dtype)) / jnp.asarray(STD, x.dtype)
        x = _conv_bn(params["stem"], x, precision, stride=2)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
    for name, _, _, stride, dilations in LAYERS:
        with jax.named_scope(name):
            x = _layer(params[name], x, precision, stride, dilations)
    with jax.named_scope("head"):
        h = params["head"]
        x = _conv_bn(h["c1"], x, precision)
        return _conv(h["c2"]["w"], x, precision) + h["c2"]["b"]


def forward(params, frames, precision=None):
    """frames (B, H, W, 3) in [0, 1] -> {"seg": (B, H/8, W/8, 21)},
    ``FRAME_BLOCK`` frames at a time, every convolution at
    ``precision``."""
    B = frames.shape[0]
    blk = min(B, FRAME_BLOCK)
    n = -(-B // blk)
    x = jnp.pad(frames, ((0, n * blk - B),) + ((0, 0),) * 3)
    x = x.reshape((n, blk) + frames.shape[1:])
    seg = lax.map(lambda f: _network(params, f, precision), x)
    return {"seg": seg.reshape((n * blk,) + seg.shape[2:])[:B]}


def final_dnn(params, precision="default"):
    """The served DNN: ``FinalDNN`` over this forward pass with its
    convolutions at ``precision`` (a ``lax.Precision`` or its name)."""
    from repro.vision.dnn import FinalDNN

    return FinalDNN("segmentation", params, name=ARCH, forward=forward,
                    precision=lax.Precision(precision))
