"""CPU rehearsals of the benchmark at 48x64: each traffic driver end to end
through the harness (not ``run.py``), the refusal without a TPU, and the
faults that must make ``correct`` come out false."""
import json
import os
import subprocess
import sys
import time
import warnings
from pathlib import Path

import jax
import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[1]
SEED = 2 ** 33 + 17  # seeds may be wider than 32 bits


def small(name, size=(48, 64)):
    """A cell at a test's size. ``seg720_open`` is the generator's open
    loop (a chunk of every camera due each chunk duration, one engine call
    per chunk) on the seg720_x6_sat cell, as an open-loop mix drives it."""
    if name == "seg720_open":
        cell = harness.load_cell("seg720_x6_sat")
        cell["traffic_mix"] = {"loop": "open", "period_chunks": 1.0,
                               "pool_chunks": 2, "trace_seconds": 6}
    else:
        cell = harness.load_cell(name)
    cell["cfg"].update(height=size[0], width=size[1])
    cell["traffic_mix"]["pool_chunks"] = 2
    return cell


def execute(cell, seconds=0.6, seed=SEED):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # kernel fallback
        return harness.execute(cell, seed, seconds, False,
                               time.perf_counter(), require_chip=False,
                               log=lambda *a, **k: None)


def check_line(result, cell):
    keys = list(result)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    json.dumps(result)  # one JSON object
    want = {m["name"] for m in cell["end_to_end"]}
    assert set(result["metrics"]) == want
    for m in cell["end_to_end"]:
        got = result["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0 or \
            m["name"] == "peak_hbm_gb"  # the CPU reports no memory peak
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["checks"]) == set(cell["limits"]) | {"failed"}


@pytest.mark.parametrize("name", ["det720_x6_sat", "seg720_x6_sat",
                                  "seg720_open"])
def test_driver_rehearsal(name):
    cell = small(name)
    result = execute(cell)
    check_line(result, cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 4


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "det720_x6_sat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def _bytes_altered(make_step):
    """The camera step, with stream 0's wire bytes altered where they are
    produced."""
    def make(*a, **k):
        step = make_step(*a, **k)

        def altered(chunks, *rest):
            dec, pbytes, scores = step(chunks, *rest)
            return dec, pbytes.at[0].multiply(1.01), scores
        return altered
    return make


def _keep_altered(make_step):
    """The detection server step, with lane 0's first frame of detections
    removed where they are produced."""
    def make(*a, **k):
        step = make_step(*a, **k)

        def altered(decoded):
            out = dict(step(decoded))
            out["keep"] = out["keep"].at[0, 0].set(0.0)
            return out
        return altered
    return make


def _half_batch(make_step):
    """The server step with the second half of the batch left out: its
    lanes carry the first half's outputs."""
    def make(*a, **k):
        step = make_step(*a, **k)

        def altered(decoded):
            h = decoded.shape[0] // 2
            return jax.tree.map(lambda x: x.at[h:2 * h].set(x[:h]),
                                step(decoded))
        return altered
    return make


def _iou_altered(make_step):
    """The device accuracy reduction, with lane 0's IoU altered where it
    is produced."""
    def make(*a, **k):
        step = make_step(*a, **k)

        def altered(outs, ref_outs):
            return step(outs, ref_outs).at[0].add(1e-3)
        return altered
    return make


FAULTS = [("det720_x6_sat", "make_camera_fleet_step", _bytes_altered),
          ("det720_x6_sat", "make_server_fleet_step", _keep_altered),
          ("det720_x6_sat", "make_server_fleet_step", _half_batch),
          ("seg720_x6_sat", "make_camera_fleet_step", _bytes_altered),
          ("seg720_x6_sat", "make_server_fleet_step", _half_batch),
          ("seg720_x6_sat", "make_accuracy_reduce_step", _iou_altered)]


@pytest.mark.parametrize("name,target,wrap", FAULTS)
def test_fault_makes_correct_false(monkeypatch, name, target, wrap):
    from repro.engine import multistream

    monkeypatch.setattr(multistream, target,
                        wrap(getattr(multistream, target)))
    result = execute(small(name), seconds=0.4)
    assert result["correct"] is False, result["checks"]
