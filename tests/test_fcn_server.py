"""FCN-ResNet101 as the program serves it (``repro.vision.fcn``) against
the benchmark's plain reference (``chipbench/servers/fcn_resnet101.py``)
on seeded random weights; the server step with the weights as arguments
against the step as it was, with the weights compiled in; and the
segmentation IoU over every class."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import nets, scene  # noqa: E402
from chipbench.servers import fcn_resnet101 as yardstick  # noqa: E402
from repro.vision import dnn as V  # noqa: E402
from repro.vision import fcn  # noqa: E402

CFG = {"task": "segmentation", "height": 720, "width": 1280,
       "server_precision": "default"}


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: yardstick.init(k, CFG))(jax.random.key(11))


@pytest.mark.parametrize("size", [(48, 64), (96, 160)])
def test_forward_matches_the_plain_reference(params, size):
    """Both compute the same f32 convolutions in the same order on the
    same backend; the program scans each layer's repeated blocks and maps
    over frame blocks of another size, which may change how XLA tiles a
    convolution, so the logits are held to 2e-5 of their largest value
    (f32 rounding over 104 convolutions), not to the bit."""
    frames = scene.dashcam_chunk(scene.seed_key(2 ** 33 + 1), 0, 3, *size)
    got = jax.jit(lambda p, f: fcn.forward(p, f, jax.lax.Precision.HIGHEST)
                  )(params, frames)["seg"]
    cfg = dict(CFG, server_precision="highest")
    want = jax.jit(lambda p, f: yardstick.forward(p, f, cfg, "highest")
                   )(params, frames)["seg"]
    assert got.shape == (3, size[0] // 8, size[1] // 8, 21)
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=2e-5 * np.abs(want).max())


def _torchvision_dilations(replace=(False, True, True)):
    """(stride, dilation) of every bottleneck, as torchvision's
    ``ResNet._make_layer`` sets them under ``replace_stride_with_dilation``:
    a dilated layer turns its stride into dilation, and its first block
    keeps the dilation of the layer before."""
    dilation, out = 1, []
    for depth, stride, dilate in zip((3, 4, 23, 3), (1, 2, 2, 2),
                                     (False,) + tuple(replace)):
        previous = dilation
        if dilate:
            dilation *= stride
            stride = 1
        out.append([(stride, previous)] + [(1, dilation)] * (depth - 1))
    return out


def test_dilation_schedule_and_output_stride(params):
    want = _torchvision_dilations()
    assert want[2] == [(1, 1)] + [(1, 2)] * 22
    assert want[3] == [(1, 2), (1, 4), (1, 4)]
    for table in (fcn.LAYERS, [(n, w, d, s, (f, r)) for n, w, d, s, f, r
                               in yardstick.LAYERS]):
        got = [[(s, dil[0])] + [(1, dil[1])] * (d - 1)
               for _, _, d, s, dil in table]
        assert got == want
    stride = 2 * 2 * int(np.prod([s for _, _, _, s, _ in fcn.LAYERS]))
    assert stride == fcn.STRIDE == 8
    out = jax.eval_shape(lambda p, f: fcn.forward(p, f), params,
                         jax.ShapeDtypeStruct((2, 720, 1280, 3),
                                              jnp.float32))
    assert out["seg"].shape == (2, 90, 160, 21)


def test_parameter_count(params):
    """torchvision's ``fcn_resnet101`` holds 54,314,346 parameters; its
    auxiliary head (3x3 conv 1024 -> 256, BN, 1x1 conv 256 -> 21 with
    bias) 2,365,205 of them, which the server does not compute. BN's
    weight and bias count as parameters there, as its scale and shift
    do here."""
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == 54_314_346 - (1024 * 256 * 9 + 2 * 256 + 256 * 21 + 21)
    assert n == 51_949_141
    assert params["layer3"]["rest"]["c2"]["w"].shape == (22, 3, 3, 256, 256)
    assert params["head"]["c1"]["w"].shape == (3, 3, 2048, 512)
    assert params["head"]["c2"]["w"].shape == (1, 1, 512, 21)


def _old_server_step(final_dnn):
    """The server fleet step as it was, the weights closed over and so
    compiled in as constants."""
    task, params = final_dnn.task, final_dnn.params

    def _server(decoded):
        N, T = decoded.shape[:2]
        flat = decoded.reshape((N * T,) + decoded.shape[2:])
        out = V.apply_net(task, params, flat)
        if task == "detection":
            out = dict(out, keep=V.detection_keep_heat(out))
        return jax.tree_util.tree_map(
            lambda v: v.reshape((N, T) + v.shape[1:]), out)
    return jax.jit(_server)


@pytest.mark.parametrize("task", ["detection", "segmentation"])
def test_server_step_with_weights_as_arguments_is_bit_equal(task):
    """The det720/seg720 stand-in, served with its weights as arguments,
    gives the outputs it gave with the weights compiled in, bit for
    bit."""
    from repro.serve.steps import make_server_fleet_step

    cfg = {"task": task, "accmodel_width": 16, "dnn_width": 32}
    _, params = nets.make_weights(scene.seed_key(0), cfg)
    dnn = V.FinalDNN(task, params)
    decoded = jnp.stack([scene.dashcam_chunk(scene.seed_key(40 + s), 0, 3,
                                             48, 64) for s in range(2)])
    new = make_server_fleet_step(dnn)(decoded)
    old = _old_server_step(dnn)(decoded)
    assert set(new) == set(old)
    for k in old:
        np.testing.assert_array_equal(np.asarray(new[k]), np.asarray(old[k]),
                                      err_msg=k)
    # ``predict`` (D(H) in set-up) as it was: the weights compiled in
    old_predict = jax.jit(lambda f: V.apply_net(task, params, f))
    for k, v in old_predict(decoded[0]).items():
        np.testing.assert_array_equal(np.asarray(dnn.predict(decoded[0])[k]),
                                      np.asarray(v), err_msg=k)


def _old_iou(out, ref_out):
    """``segmentation_iou`` as it was: classes 0 and 1 only."""
    a = np.asarray(jnp.argmax(out["seg"], -1))
    b = np.asarray(jnp.argmax(ref_out["seg"], -1))
    ious = []
    for cls in (0, 1):
        inter = np.logical_and(a == cls, b == cls).sum()
        union = np.logical_or(a == cls, b == cls).sum()
        if union > 0:
            ious.append(inter / union)
    return float(np.mean(ious)) if ious else 1.0


def _old_device_iou(out, ref_out):
    """``device_lane_accuracy``'s segmentation IoU as it was."""
    a = jnp.argmax(out["seg"], -1)
    b = jnp.argmax(ref_out["seg"], -1)
    axes = tuple(range(1, a.ndim))
    iou_sum = jnp.zeros(a.shape[0], jnp.float32)
    n_valid = jnp.zeros(a.shape[0], jnp.float32)
    for cls in (0, 1):
        inter = ((a == cls) & (b == cls)).sum(axis=axes)
        union = ((a == cls) | (b == cls)).sum(axis=axes)
        valid = union > 0
        iou = jnp.where(valid, inter / jnp.maximum(union, 1), 0.0)
        iou_sum += iou.astype(jnp.float32)
        n_valid += valid.astype(jnp.float32)
    return jnp.where(n_valid > 0, iou_sum / jnp.maximum(n_valid, 1.0), 1.0)


@pytest.mark.parametrize("p1", [0.0, 0.02, 0.5, 0.98, 1.0])
def test_iou_on_two_classes_is_the_old_number(p1):
    rng = np.random.default_rng(int(p1 * 100))
    shape = (3, 4, 6, 8, 2)
    for _ in range(10):
        one = rng.random(shape[:-1]) < p1
        flip = rng.random(shape[:-1]) < 0.1
        logits = lambda lab: np.stack([1.0 - lab, lab.astype(float)],
                                      -1).astype(np.float32)
        out = {"seg": logits(one)}
        ref = {"seg": logits(one ^ flip)}
        lanes = V.segmentation_iou_batched(out, ref)
        for i in range(shape[0]):
            lane = ({"seg": out["seg"][i]}, {"seg": ref["seg"][i]})
            assert lanes[i] == _old_iou(*lane)
            assert V.segmentation_iou(*lane) == _old_iou(*lane)
        np.testing.assert_array_equal(
            np.asarray(V.device_lane_accuracy("segmentation", out, ref)),
            np.asarray(_old_device_iou(out, ref)))


def _one_hot(labels, n=21):
    return np.eye(n, dtype=np.float32)[np.asarray(labels)]


def test_iou_on_21_classes_by_hand():
    a = _one_hot([[[0, 0, 1, 2]]])
    b = _one_hot([[[0, 1, 1, 1]]])
    # class 0: 1 of 2 pixels; class 1: 1 of 3; class 2: 0 of 1; the 18
    # classes in neither map do not count
    want = np.mean([1 / 2, 1 / 3, 0.0])
    assert V.segmentation_iou({"seg": a}, {"seg": b}) == \
        pytest.approx(want, rel=1e-15)
    c = _one_hot([[[20, 20, 7, 7]]])
    assert V.segmentation_iou({"seg": c}, {"seg": c}) == 1.0
    d = _one_hot([[[20, 7, 7, 7]]])
    # class 20: 1 of 2; class 7: 2 of 3
    assert V.segmentation_iou({"seg": c}, {"seg": d}) == \
        pytest.approx(np.mean([1 / 2, 2 / 3]), rel=1e-15)
    lanes = V.segmentation_iou_batched({"seg": np.stack([a, c])},
                                       {"seg": np.stack([b, d])})
    np.testing.assert_allclose(lanes, [want, np.mean([1 / 2, 2 / 3])],
                               rtol=1e-15)
    dev = V.device_lane_accuracy("segmentation", {"seg": np.stack([a, c])},
                                 {"seg": np.stack([b, d])})
    np.testing.assert_allclose(np.asarray(dev), lanes, rtol=0, atol=1e-6)


def test_host_and_device_iou_agree_on_21_classes():
    rng = np.random.default_rng(5)
    out = {"seg": rng.normal(size=(4, 3, 9, 16, 21)).astype(np.float32)}
    noise = 0.5 * rng.normal(size=out["seg"].shape).astype(np.float32)
    ref = {"seg": out["seg"] + noise}
    host = V.segmentation_iou_batched(out, ref)
    dev = np.asarray(V.device_lane_accuracy("segmentation", out, ref))
    assert 0.2 < host.min() and host.max() < 1.0
    np.testing.assert_allclose(dev, host, rtol=0, atol=1e-6)


def test_label_map_of_a_dashcam_chunk_is_not_blind(params):
    """On seeded weights, a dashcam chunk's label map holds several
    classes, and QP 40 moves it off QP 30's, so an accuracy check can see
    a change."""
    from repro.codec.codec import encode_chunk_uniform

    dnn = fcn.final_dnn(params, "highest")
    chunk = scene.dashcam_chunk(scene.seed_key(2 ** 33 + 9), 0, 4, 96, 160)
    hq, _ = encode_chunk_uniform(chunk, 30)
    lq, _ = encode_chunk_uniform(chunk, 40)
    ref, out = dnn.predict(hq), dnn.predict(lq)
    labels = np.argmax(np.asarray(ref["seg"]), -1)
    assert len(np.unique(labels)) >= 3
    assert V.segmentation_iou(out, ref) < 1.0
    assert dnn.name == fcn.ARCH and dnn.precision_name == "highest"


def test_served_through_the_engine_with_spans_and_counters(params):
    """FCN-ResNet101 through ``MultiStreamEngine.run``: each interval's
    ``dispatch_server`` span names the server, the frames of its passes
    and its precision, ``server_frames_total`` counts the frames by
    server, and the server program's operations carry the stage names."""
    from repro import obs
    from repro.core.accmodel import AccModel, accmodel_init
    from repro.engine import EngineConfig, MultiStreamEngine

    dnn = fcn.final_dnn(params, "default")
    engine = MultiStreamEngine(dnn, AccModel(accmodel_init(
        jax.random.PRNGKey(1), 8)), config=EngineConfig(impl="fast"))
    video = np.stack([np.asarray(scene.dashcam_chunk(
        scene.seed_key(60 + s), 0, 20, 48, 64)) for s in range(2)])
    tr, reg = obs.enable(host=0)
    try:
        res = engine.run(video)
    finally:
        obs.disable()
    spans = [e for e in tr.events if e.name == "dispatch_server"]
    assert len(spans) == len(res.timing.camera_s) == 2
    # refs=None: D(H) is a second pass over the 2 streams' 10 frames
    assert all((e.args["server"], e.args["frames"], e.args["precision"])
               == ("fcn_resnet101", 40, "default") for e in spans)
    assert reg.get("server_frames_total", server="fcn_resnet101").value \
        == 80
    step = engine._steps_for(2)[1]
    hlo = step.lower(jnp.asarray(video[:, :10])).compile().as_text()
    for stage in ("stem", "layer1", "layer2", "layer3", "layer4", "head"):
        assert f"/{stage}/" in hlo, stage
