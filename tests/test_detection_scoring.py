"""The vectorized detection scorer against its per-frame oracle.

``detection_f1_batched`` decodes, intersects and greedily matches every
frame of a (N, T, ...) lane tree in one numpy pass. Each lane's score must
be bit-equal (``np.array_equal``, not approximately) to the per-lane
``detection_f1(decode_detections(...), decode_detections(...))`` path that
``FinalDNN.accuracy`` and the engine's ``detail="legacy"`` loop run: the
padded decode, the IoU tensor, the rank-stepped greedy match and the lane
means each follow the oracle's arithmetic and tie order.
"""
import numpy as np
import pytest

from repro.vision.dnn import (STRIDE, _iou, decode_detections,
                              detection_f1, detection_f1_batched)


def _oracle(out, ref, iou_thresh=0.5):
    lanes = out["wh"].shape[0]
    return np.asarray([
        detection_f1(decode_detections({k: v[i] for k, v in out.items()}),
                     decode_detections({k: v[i] for k, v in ref.items()}),
                     iou_thresh)
        for i in range(lanes)], np.float64)


def _empty(n=2, t=3, hs=6, ws=8):
    return {"keep": np.zeros((n, t, hs, ws), np.float32),
            "wh": np.ones((n, t, hs, ws, 2), np.float32)}


def _put(tree, lane, frame, y, x, score, w, h):
    tree["keep"][lane, frame, y, x] = score
    tree["wh"][lane, frame, y, x] = (w, h)


def _empty_one_side():
    out, ref = _empty(), _empty()
    _put(out, 0, 0, 2, 2, 0.9, 2.0, 2.0)        # no reference here
    _put(ref, 0, 1, 3, 4, 0.8, 1.5, 1.0)        # no detection here
    _put(out, 0, 2, 1, 1, 0.7, 1.0, 1.0)        # and a plain match
    _put(ref, 0, 2, 1, 1, 0.6, 1.0, 1.0)
    _put(ref, 1, 0, 4, 6, 0.5, 3.0, 2.0)        # lane 1: refs only
    return out, ref


def _empty_both():
    out, ref = _empty(), _empty()
    _put(out, 1, 2, 0, 0, 0.95, 1.0, 1.0)       # lane 0 empty throughout
    return out, ref


def _cap_binds():
    rng = np.random.default_rng(7)
    out, ref = _empty(n=2, t=2, hs=10, ws=10), _empty(n=2, t=2, hs=10,
                                                      ws=10)
    out["keep"][:] = rng.uniform(0.3, 1.0, out["keep"].shape)
    ref["keep"][:] = rng.uniform(0.3, 1.0, ref["keep"].shape)
    out["wh"][:] = rng.uniform(0.2, 3.0, out["wh"].shape)
    ref["wh"][:] = out["wh"] * rng.choice([1.0, 1.3], ref["wh"].shape)
    assert (out["keep"][0, 0] >= 0.3).sum() > 50       # topk binds
    return out, ref


def _score_ties():
    rng = np.random.default_rng(8)
    out, ref = _empty(n=2, t=2, hs=9, ws=9), _empty(n=2, t=2, hs=9, ws=9)
    out["keep"][:] = 0.5                         # 81 equal scores a frame
    ref["keep"][:] = np.where(rng.random(ref["keep"].shape) < 0.5, 0.5,
                              0.75)
    out["wh"][:] = rng.uniform(0.2, 2.5, out["wh"].shape)
    ref["wh"][:] = rng.uniform(0.2, 2.5, ref["wh"].shape)
    return out, ref


def _iou_exactly_half():
    out, ref = _empty(n=1, t=1), _empty(n=1, t=1)
    _put(out, 0, 0, 2, 3, 0.9, 2.0, 1.5)         # 16 x 12 px
    _put(ref, 0, 0, 2, 3, 0.9, 1.0, 1.5)         # 8 x 12 px, same centre
    return out, ref


def _ref_already_taken():
    out, ref = _empty(n=1, t=1), _empty(n=1, t=1)
    _put(ref, 0, 0, 2, 2, 0.9, 2.0, 2.0)         # A: x in [12, 28]
    _put(ref, 0, 0, 2, 5, 0.8, 2.0, 2.0)         # B: x in [36, 52]
    _put(out, 0, 0, 2, 2, 0.9, 2.0, 2.0)         # takes A (IoU 1)
    _put(out, 0, 0, 2, 3, 0.8, 4.0, 2.0)         # A at 0.5 taken, B 0.2
    return out, ref


def _iou_tie_first_ref_wins():
    out, ref = _empty(n=1, t=1), _empty(n=1, t=1)
    _put(out, 0, 0, 2, 3, 0.9, 4.0, 2.0)         # x in [12, 44]
    _put(out, 0, 0, 2, 2, 0.8, 2.0, 2.0)         # A's twin, B at 0
    _put(ref, 0, 0, 2, 4, 0.9, 2.0, 2.0)         # B first: 0.5 each
    _put(ref, 0, 0, 2, 2, 0.7, 2.0, 2.0)         # A
    return out, ref


def _heat_path():
    rng = np.random.default_rng(9)
    heat = rng.normal(-1.0, 2.0, (2, 3, 8, 10, 1)).astype(np.float32)
    wh = rng.uniform(0.2, 3.0, (2, 3, 8, 10, 2)).astype(np.float32)
    out = {"heat": heat, "wh": wh}
    ref = {"heat": heat + rng.normal(0, 0.5, heat.shape).astype(np.float32),
           "wh": wh}
    return out, ref


CASES = {
    "empty_one_side": _empty_one_side,
    "empty_both": _empty_both,
    "cap_binds": _cap_binds,
    "score_ties": _score_ties,
    "iou_exactly_half": _iou_exactly_half,
    "ref_already_taken": _ref_already_taken,
    "iou_tie_first_ref_wins": _iou_tie_first_ref_wins,
    "heat_path": _heat_path,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_batched_f1_bit_equal_to_per_lane_oracle(case):
    out, ref = CASES[case]()
    got = detection_f1_batched(out, ref)
    assert got.dtype == np.float64 and got.shape == (out["wh"].shape[0],)
    assert np.array_equal(got, _oracle(out, ref))


def test_hand_made_cases_reach_their_edges():
    """The hand-made cases hit the edges they are named for."""
    out, ref = _iou_exactly_half()
    (d,) = decode_detections({k: v[0] for k, v in out.items()})
    (r,) = decode_detections({k: v[0] for k, v in ref.items()})
    assert _iou(d[0], r[0]) == 0.5
    assert detection_f1_batched(out, ref)[0] == 1.0      # >= matches
    # the second detection loses A and falls below threshold on B
    assert detection_f1_batched(*_ref_already_taken())[0] == 0.5
    # the tie goes to the first reference in score order (B), leaving A
    # for the second detection
    assert detection_f1_batched(*_iou_tie_first_ref_wins())[0] == 1.0
    assert STRIDE == 8                           # the boxes above assume it


def _random_pair(seed, n=3, t=5, hs=12, ws=12):
    rng = np.random.default_rng(seed)
    density = rng.choice([0.05, 0.3, 0.9])
    keep = np.where(rng.random((n, t, hs, ws)) < density,
                    np.round(rng.uniform(0.2, 1.0, (n, t, hs, ws)), 1), 0.0)
    wh = rng.gamma(2.0, 1.0, (n, t, hs, ws, 2))
    out = {"keep": keep.astype(np.float32), "wh": wh.astype(np.float32)}
    jitter = rng.choice([1.0, 0.8, 1.25], wh.shape)
    drop = rng.random(keep.shape) < 0.3
    ref = {"keep": np.where(drop, 0.0, keep).astype(np.float32),
           "wh": (wh * jitter).astype(np.float32)}
    return out, ref


@pytest.mark.parametrize("iou_thresh", [0.5, 0.3, 0.0])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_batched_f1_bit_equal_on_seeded_random_trees(seed, iou_thresh):
    out, ref = _random_pair(seed)
    assert np.array_equal(detection_f1_batched(out, ref, iou_thresh),
                          _oracle(out, ref, iou_thresh))


def test_batched_f1_zero_frames_scores_one():
    out = _empty(n=2, t=0)
    assert np.array_equal(detection_f1_batched(out, _empty(n=2, t=0)),
                          np.ones(2))
