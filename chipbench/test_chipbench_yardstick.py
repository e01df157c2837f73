"""The benchmark's peak table and FLOP/byte functions against hand counts
at 720p."""
import pytest

from chipbench import flops, peaks

H, W = 720, 1280
STEM = 360 * 640 * 9 * 3          # 3x3 stride-2 conv from RGB, per out ch
S4, S8, S16 = 180 * 320, 90 * 160, 45 * 80


def test_detection_dnn_hand_count():
    macs = (STEM * 16 + S4 * (9 * 16 + 16 * 32) + S8 * (9 * 32 + 32 * 64)
            + S8 * (9 * 64 + 64 * 96) + S8 * (9 * 96 + 96 * 96)
            + 3 * S8 * 9 * 96 * 64 + S8 * 64 * (1 + 2 + 2))
    assert macs == 2_806_272_000
    assert flops.dnn_flops("detection", H, W) == 2 * macs  # ~5.6 GFLOP


def test_segmentation_dnn_hand_count():
    macs = (STEM * 16 + S4 * (9 * 16 + 16 * 32) + S8 * (9 * 32 + 32 * 64)
            + S8 * (9 * 64 + 64 * 96) + S8 * (9 * 96 + 96 * 96)
            + S8 * 9 * 96 * 64 + S8 * 64 * 2)
    assert macs == 1_210_982_400
    assert flops.dnn_flops("segmentation", H, W) == 2 * macs  # ~2.4 GFLOP


def test_accmodel_hand_count():
    macs = (STEM * 16 + S4 * (9 * 16 + 16 * 32) + S8 * (9 * 32 + 32 * 64)
            + S16 * (9 * 64 + 64 * 128) + S16 * (9 * 128 + 128 * 128)
            + S16 * 9 * 128 * 64 + S16 * 9 * 64 * 32 + S16 * 32)
    assert macs == 597_542_400
    assert flops.accmodel_flops(H, W) == 2 * macs  # ~1.2 GFLOP


def test_codec_hand_count():
    blocks = 45 * 80 * 3
    assert flops.codec_blocks(H, W) == blocks
    per_block_frame = 4 * 2 * 16 ** 3  # two separable transforms, 2 GEMMs
    four_streams = 4 * flops.codec_flops(10, H, W)
    assert four_streams == 4 * 10 * blocks * per_block_frame
    assert four_streams == pytest.approx(14.16e9, rel=1e-3)
    assert 4 * flops.codec_bytes(10, H, W) == 2 * 4 * 10 * H * W * 3 * 4


def test_stream_chunk_flops_counts_each_server_pass():
    cfg = {"height": H, "width": W, "chunk_size": 10, "accmodel_width": 16,
           "dnn_width": 32, "task": "segmentation"}
    once = flops.stream_chunk_flops(dict(cfg, refs="precomputed"))
    twice = flops.stream_chunk_flops(dict(cfg, refs="in_loop"))
    assert twice - once == 10 * flops.dnn_flops("segmentation", H, W)


def test_v5e_peaks_and_unknown_device():
    p = peaks.peak_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_s) == (197e12, 819e9)
    assert "Google Cloud" in p.source
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak_for("TPU v9 imaginary")
