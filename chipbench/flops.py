"""Operations and bytes each layer of the serving path needs, from shapes.

FLOPs count multiply-adds twice and ignore biases, activations and
pooling. They are the algorithm's counts, whatever form the program
computes them in: the codec kernel runs each 16x16 transform as one
256x256 Kronecker matmul (8x the arithmetic), but its roofline is judged
by the separable transform's 4 x 2 x 16^3 FLOPs per block, channel and
frame.
"""
from __future__ import annotations

MB = 16  # macroblock edge, pixels
STRIDE = 8  # output stride of the server DNNs' heads


def _conv_macs(h, w, k, ci, co):
    return h * w * k * k * ci * co


def _dw_sep_macs(h, w, ci, co):
    """Depthwise 3x3 on ci channels, then pointwise ci -> co, both at the
    output resolution (h, w)."""
    return h * w * (9 * ci + ci * co)


def accmodel_flops(H: int, W: int, width: int = 16) -> float:
    """AccModel on one frame (the chunk head), H x W -> per-macroblock
    logit."""
    w = width
    macs = _conv_macs(H // 2, W // 2, 3, 3, w)
    macs += _dw_sep_macs(H // 4, W // 4, w, 2 * w)
    macs += _dw_sep_macs(H // 8, W // 8, 2 * w, 4 * w)
    macs += _dw_sep_macs(H // 16, W // 16, 4 * w, 8 * w)
    macs += _dw_sep_macs(H // 16, W // 16, 8 * w, 8 * w)
    h, ww = H // 16, W // 16
    macs += _conv_macs(h, ww, 3, 8 * w, 4 * w)
    macs += _conv_macs(h, ww, 3, 4 * w, 2 * w)
    macs += _conv_macs(h, ww, 1, 2 * w, 1)
    return 2.0 * macs


HEAD_CHANNELS = {"detection": (1, 2, 2), "segmentation": (2,)}


def dnn_flops(task: str, H: int, W: int, width: int = 32) -> float:
    """Server DNN on one frame: the stride-8 backbone and the task's heads
    (each a 3x3 conv to 64 channels and a 1x1 conv to its outputs)."""
    w = width
    macs = _conv_macs(H // 2, W // 2, 3, 3, w // 2)
    macs += _dw_sep_macs(H // 4, W // 4, w // 2, w)
    macs += _dw_sep_macs(H // 8, W // 8, w, 2 * w)
    macs += _dw_sep_macs(H // 8, W // 8, 2 * w, 3 * w)
    macs += _dw_sep_macs(H // 8, W // 8, 3 * w, 3 * w)
    h, ww = H // STRIDE, W // STRIDE
    for co in HEAD_CHANNELS[task]:
        macs += _conv_macs(h, ww, 3, 3 * w, 64) + _conv_macs(h, ww, 1, 64, co)
    return 2.0 * macs


def codec_blocks(H: int, W: int, channels: int = 3) -> int:
    """Macroblock channels per frame (the codec's unit of work)."""
    return (H // MB) * (W // MB) * channels


def codec_flops(T: int, H: int, W: int, channels: int = 3) -> float:
    """Forward and inverse 16x16 DCT of every block channel of a T-frame
    chunk: two separable transforms of two 16x16x16 GEMMs each."""
    return 4 * 2 * MB ** 3 * codec_blocks(H, W, channels) * T


def codec_bytes(T: int, H: int, W: int, channels: int = 3) -> float:
    """Least HBM traffic of one chunk encode: the f32 frames read once and
    the f32 decoded frames written once (per-macroblock rows, scores and
    the two 16x16 constants are under 0.5% of it and left out)."""
    return 2.0 * T * H * W * channels * 4


def stream_chunk_flops(cfg: dict) -> float:
    """Model work per stream-chunk: AccModel on the chunk head, every
    server-DNN pass over every frame (the decoded chunk, plus the raw
    chunk where D(H) is computed in the loop), and the codec's
    transforms."""
    H, W, T = cfg["height"], cfg["width"], cfg["chunk_size"]
    passes = 2 if cfg["refs"] == "in_loop" else 1
    f = accmodel_flops(H, W, cfg["accmodel_width"])
    f += passes * T * dnn_flops(cfg["task"], H, W, cfg["dnn_width"])
    f += codec_flops(T, H, W)
    return f
