"""Compile the codec kernels and the fused camera step for a described
TPU v5e at 720p shapes.

Nothing runs: ``.lower().compile()`` against a topology description makes
the TPU compiler (Mosaic for the Pallas kernels) accept or refuse each
program exactly as it would on the chip, which interpret-mode tests
cannot show. The topology is described inside a fixture, never at import
time, so every test worker collects the same tests; this is the only test
file that describes one.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# 1280x720: 80 x 45 = 3,600 macroblocks x 3 channels, chunk of 10 frames
H, W, T = 720, 1280, 10
N_MB = (H // 16) * (W // 16) * 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of the way
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _n_padded():
    from repro.kernels.mbcodec.kernel import TILE

    return -(-N_MB // TILE) * TILE


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _stripe_rows_shape():
    """Per-stripe QP or score rows at 720p: (H/R, R/16, W)."""
    from repro.kernels.mbcodec.kernel import stripe_rows

    R = stripe_rows(H)
    return (H // R, R // 16, W)


@pytest.mark.parametrize("clip_refs", [False, True])
def test_chunk_scores_kernel_compiles_720p(one_chip, clip_refs):
    from repro.kernels.mbcodec.kernel import mbcodec_chunk_scores_pallas

    compiled = mbcodec_chunk_scores_pallas.lower(
        _spec((T, 3, H, W), one_chip), _spec(_stripe_rows_shape(), one_chip),
        _spec((3,), one_chip), clip_refs=clip_refs).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("clip_refs", [False, True])
def test_chunk_kernel_compiles_720p(one_chip, clip_refs):
    from repro.kernels.mbcodec.kernel import mbcodec_chunk_pallas

    compiled = mbcodec_chunk_pallas.lower(
        _spec((T, 3, H, W), one_chip),
        _spec((T,) + _stripe_rows_shape(), one_chip),
        clip_refs=clip_refs).compile()
    _assert_kernel(compiled)


def test_frame_kernel_compiles_720p(one_chip):
    from repro.kernels.mbcodec.kernel import COEFS, mbcodec_pallas

    n = _n_padded()
    compiled = mbcodec_pallas.lower(_spec((COEFS, n), one_chip),
                                    _spec((1, n), one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("impl", ["fused", "fused_exact"])
def test_fused_camera_step_compiles_720p(one_chip, monkeypatch, impl):
    """The serving camera step (AccModel scoring, in-kernel QP assignment,
    chunk-fused encode) for two 720p streams, with its TPU branch taken:
    the step asks ``ops.on_tpu()`` at trace time, which sees this host's
    CPU, so the test steers it."""
    from repro.core.accmodel import AccModel, accmodel_init
    from repro.core.quality import QualityConfig
    from repro.kernels.mbcodec import ops
    from repro.serve.steps import make_camera_fleet_step

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    am = AccModel(accmodel_init(jax.random.PRNGKey(1)))
    step = make_camera_fleet_step(am, QualityConfig(), impl=impl)
    compiled = step.lower(_spec((2, T, H, W, 3), one_chip)).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    # one 10-frame 720p f32 chunk is 110.6 MB per stream; the kernel reads
    # the frames where they lie, so the step's temporaries are the
    # AccModel's and the per-stripe rows', not copies of the frames
    assert mem.temp_size_in_bytes < 1e9
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 4e9
    assert _frame_sized_layout_ops(compiled.as_text(), 2 * T * H * W * 3) \
        == []


def _frame_sized_layout_ops(hlo, n):
    """The copies, transposes, reshapes, pads and slices in compiled HLO
    text (fused computations included) whose f32 result holds ``n``
    elements or more."""
    found = []
    for m in re.finditer(r"= f32\[([\d,]*)\]\S* (copy|transpose|reshape|"
                         r"pad|slice)\(", hlo):
        if math.prod(int(d) for d in m.group(1).split(",") if d) >= n:
            found.append(m.group(0))
    return found


def test_fcn_server_step_compiles_720p(one_chip):
    """The FCN-ResNet101 server step for four 720p streams (40 frames, in
    blocks of ``vision.fcn.FRAME_BLOCK``) at one bf16 pass a convolution:
    its temporaries stay under the camera step's at four streams (7.08 GB),
    so the server does not set the chip's peak."""
    import sys
    from pathlib import Path

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench.servers import fcn_resnet101
    from repro.serve.steps import make_server_fleet_step
    from repro.vision import fcn

    cfg = {"height": H, "width": W}
    params = jax.jit(lambda k: fcn_resnet101.init(k, cfg))(jax.random.key(0))
    step = make_server_fleet_step(fcn.final_dnn(params, "default"))
    on_chip = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    compiled = step.jitted.lower(on_chip,
                                 _spec((4, T, H, W, 3), one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 7.08e9
    assert mem.argument_size_in_bytes < 0.21e9 + 4 * T * H * W * 3 * 4 + 1e6
