"""The chip smoke's work functions, rehearsed on the CPU at a tiny size.

``chip_smoke.py`` refuses to run without a TPU; its work functions do
not check the device, so the serving path it drives (engine, fleet
steps, CPU reference, tolerance checks) is exercised here end to end.
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_run_smoke_tiny_reports_every_field():
    lines = []
    out = chip_smoke.run_smoke(H=48, W=64, n_streams=2, impl="fast",
                               log=lines.append)
    assert out["n_streams"] == 2 and out["frames"] == [48, 64]
    assert out["chunks"] == 3
    assert set(out["compile_s"]) == {"camera", "server"}
    assert all(v > 0 for v in out["compile_s"].values())
    for step in ("camera", "server"):
        assert set(out["memory"][step]) == {"argument", "output", "temp"}
    assert "peak_bytes_in_use" in out  # None where the backend has none
    assert len(out["camera_s"]) == 3 and len(out["server_s"]) == 3
    assert out["wall_s"] > 0
    assert len(out["accuracy"]) == 2
    assert all(0.0 <= a <= 1.0 for a in out["accuracy"])
    # the same backend on both sides: the reference gaps vanish
    assert out["gaps"]["bytes_rel_max"] == 0.0
    assert out["gaps"]["accuracy_abs_max"] == 0.0
    text = "\n".join(lines)
    for needle in ("smoke timings, not benchmark numbers",
                   "peak_bytes_in_use", "chip vs CPU", "streams fit"):
        assert needle in text


def test_run_mesh_check_tiny():
    out = chip_smoke.run_mesh_check(H=48, W=64, n_streams=2, group=1,
                                    impl="fast", log=lambda *a: None)
    assert out["devices"] == 1 and out["n_streams"] == 2
    assert out["gaps"]["bytes_rel_max"] == 0.0


def test_check_rejects_a_gap_over_tolerance():
    gaps = {"bytes_rel_max": 0.0, "decoded_mean_abs_max": 0.0,
            "accuracy_abs_max": 2 * chip_smoke.ACC_ATOL}
    with pytest.raises(AssertionError, match="accuracy_abs_max"):
        chip_smoke._check(gaps, "test")


def test_main_without_a_tpu_fails_and_prints_no_result(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    for line in out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
