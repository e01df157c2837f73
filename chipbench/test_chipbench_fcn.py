"""The FCN-ResNet101 cell (``fcn720_x4_sat``) rehearsed on the CPU at
48x64: served and compared through the harness, ``correct``; each fault
planted in the program makes ``correct`` false, among them two that
only the accuracy numbers see: the server's weights and activations
rounded to ``float8_e4m3fn``, a precision below the one the
configuration states, and its logits collapsed to a constant, in the
served passes and D(H) alike; the ``bf16_3x`` control fails where the
program passes; and the server module's FLOPs against a hand count."""
import warnings

import jax.numpy as jnp
import pytest

from chipbench import calibrate, compare, servers
from chipbench.test_chipbench_drivers import (_bytes_altered, _half_batch,
                                              check_line, execute, small)

CELL = "fcn720_x4_sat"


def test_driver_rehearsal():
    cell = small(CELL)
    result = execute(cell)
    check_line(result, cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 4 * 3


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def _fp8_server(conv):
    """The server's convolutions on weights and activations rounded to
    ``float8_e4m3fn``."""
    def rounded(w, x, precision, stride=1, dilation=1):
        return conv(_fp8(w), _fp8(x), precision, stride, dilation)
    return rounded


def _collapsed(forward):
    """The server's logits collapsed to a constant: every label map one
    class, in the served passes and in D(H) alike."""
    def constant(params, frames, precision=None):
        return {"seg": forward(params, frames, precision)["seg"] * 0.0}
    return constant


# (module, name, fault, size): the collapsed map at 96x160, since at
# 48x64 a frame's label map is 6x8 and the reference's own IoU against
# D(H) sits near 0.98, so a program that reads 1.0 everywhere stays
# within the chip's acc_gap_mean limit there (0.017); at 96x160 it reads
# 0.028, on the chip 0.036-0.057 (PERF.md)
FAULTS = [("repro.engine.multistream", "make_camera_fleet_step",
           _bytes_altered, (48, 64)),
          ("repro.engine.multistream", "make_server_fleet_step", _half_batch,
           (48, 64)),
          ("repro.vision.fcn", "_conv", _fp8_server, (48, 64)),
          ("repro.vision.fcn", "forward", _collapsed, (96, 160))]


@pytest.mark.parametrize("module,target,wrap,size", FAULTS,
                         ids=[f[2].__name__ for f in FAULTS])
def test_fault_makes_correct_false(monkeypatch, module, target, wrap, size):
    import importlib

    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, target, wrap(getattr(mod, target)))
    result = execute(small(CELL, size=size), seconds=0.4)
    assert result["correct"] is False, result["checks"]
    if wrap in (_fp8_server, _collapsed):  # the camera side untouched
        checks = result["checks"]
        assert all(checks[k]["value"] <= checks[k]["limit"]
                   for k in ("bytes_gap", "bytes_gap_mean")), checks


def test_control_fails_where_the_program_passes():
    cell = small(CELL, size=(96, 160))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # kernel fallback
        r = calibrate.readings(cell, 2 ** 35 + 3, 0.5)
    assert r["failed"] == 0
    assert compare.judge(r["program"], cell["limits"], 0), r
    assert not compare.judge(r["control.bf16_3x"], cell["limits"], 0), r


def _hand_count(H, W):
    """Multiply-adds of one frame, layer by layer: each bottleneck's 1x1
    to its width at the block's input resolution, its 3x3 (with the
    stride) and 1x1 to 4x the width at its output's, the first block's
    1x1 projection; ``layer3``/``layer4`` at stride 8."""
    S2, S4, S8 = (H // 2) * (W // 2), (H // 4) * (W // 4), (H // 8) * (W // 8)
    stem = S2 * 49 * 3 * 64
    layer1 = (S4 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
              + 2 * S4 * (256 * 64 + 9 * 64 * 64 + 64 * 256))
    layer2 = (S4 * 256 * 128 + S8 * (9 * 128 * 128 + 128 * 512 + 256 * 512)
              + 3 * S8 * (512 * 128 + 9 * 128 * 128 + 128 * 512))
    layer3 = (S8 * (512 * 256 + 9 * 256 * 256 + 256 * 1024 + 512 * 1024)
              + 22 * S8 * (1024 * 256 + 9 * 256 * 256 + 256 * 1024))
    layer4 = (S8 * (1024 * 512 + 9 * 512 * 512 + 512 * 2048 + 1024 * 2048)
              + 2 * S8 * (2048 * 512 + 9 * 512 * 512 + 512 * 2048))
    head = S8 * (9 * 2048 * 512 + 512 * 21)
    return stem + layer1 + layer2 + layer3 + layer4 + head


@pytest.mark.parametrize("size,macs", [((48, 64), 2_530_615_296),
                                       ((720, 1280), 759_184_588_800)])
def test_frame_flops_hand_count(size, macs):
    cfg = dict(small(CELL)["cfg"], height=size[0], width=size[1])
    assert _hand_count(*size) == macs
    assert servers.for_config(cfg).frame_flops(cfg) == 2 * macs
    if size == (720, 1280):  # ~1.52 TFLOP a frame
        assert 2 * macs == pytest.approx(1.518e12, rel=1e-3)
