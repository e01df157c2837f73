"""Per-kernel shape/dtype sweeps, interpret-mode Pallas vs the pure-jnp
oracle (assert_allclose per the brief)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # dev-only dep; fall back to a fixed sample grid
    from _hypothesis_compat import given, settings, st

from repro.kernels.accgrad_reduce.ops import accgrad_reduce
from repro.kernels.accgrad_reduce.ref import accgrad_reduce_ref
from repro.kernels.decode_attn.ops import decode_attn
from repro.kernels.decode_attn.ref import decode_attn_ref
from repro.kernels.mbcodec.ops import (encode_chunk_fused,
                                       encode_chunk_fused_scores,
                                       encode_frame_fused, mbcodec)
from repro.kernels.mbcodec.ref import mbcodec_ref
from repro.kernels.wkv6.ops import wkv6
from repro.kernels.wkv6.ref import wkv6_ref
from repro.codec.codec import encode_chunk, encode_chunk_fast, encode_frame


# ---------------------------------------------------------------------------
# mbcodec
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [64, 128, 65, 200, 1])
def test_mbcodec_matches_ref(n):
    blocks = jax.random.uniform(jax.random.PRNGKey(n), (n, 16, 16))
    qp = jax.random.uniform(jax.random.PRNGKey(n + 1), (n,), minval=10,
                            maxval=50)
    r_ref, b_ref = mbcodec_ref(blocks, qp)
    r_pl, b_pl = mbcodec(blocks, qp, impl="interpret")
    np.testing.assert_allclose(np.asarray(r_pl), np.asarray(r_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(b_pl), np.asarray(b_ref),
                               rtol=1e-4)


@given(st.integers(5, 50), st.sampled_from([0.0, 0.5, 1.0]))
@settings(max_examples=10, deadline=None)
def test_mbcodec_property_qp_and_fill(qp, fill):
    blocks = jnp.full((64, 16, 16), fill) + 0.1 * jax.random.normal(
        jax.random.PRNGKey(qp), (64, 16, 16))
    qpv = jnp.full((64,), float(qp))
    r_ref, b_ref = mbcodec_ref(blocks, qpv)
    r_pl, b_pl = mbcodec(blocks, qpv, impl="interpret")
    np.testing.assert_allclose(np.asarray(r_pl), np.asarray(r_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(b_pl), np.asarray(b_ref), rtol=1e-4)


@pytest.mark.parametrize("hw", [(32, 48), (64, 96)])
def test_frame_fused_matches_codec(hw):
    H, W = hw
    frame = jax.random.uniform(jax.random.PRNGKey(0), (H, W, 3))
    qmap = jax.random.uniform(jax.random.PRNGKey(1), (H // 16, W // 16),
                              minval=20, maxval=45)
    d1, b1 = encode_frame(frame, qmap)
    d2, b2 = encode_frame_fused(frame, qmap, impl="interpret")
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(b2), np.asarray(b1), rtol=1e-3)


def test_frame_fused_pframe_reference(hw=(64, 96)):
    """The serving path's P-frame mode: residual coding against the previous
    decoded frame must match codec.encode_frame(reference=...) through the
    actual kernel semantics (interpret mode)."""
    H, W = hw
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    prev = jax.random.uniform(k1, (H, W, 3))
    frame = jnp.clip(prev + 0.05 * jax.random.normal(k2, (H, W, 3)), 0, 1)
    qmap = jnp.full((H // 16, W // 16), 34.0)
    ref_dec, _ = encode_frame(prev, qmap)
    d1, b1 = encode_frame(frame, qmap, reference=ref_dec)
    d2, b2 = encode_frame_fused(frame, qmap, impl="interpret",
                                reference=ref_dec)
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(b2), np.asarray(b1), rtol=1e-3)


# ---------------------------------------------------------------------------
# chunk-fused mbcodec (the "fused" / "fused_exact" registry backends):
# interpret-mode Pallas vs the exact/fast chunk encoders, CPU-runnable
# ---------------------------------------------------------------------------
def _chunk(T=4, H=32, W=48, seed=3, drift=0.04):
    """Drifting scene: consecutive frames differ enough that the P-frame
    reference chain is load-bearing (a wrong carried reference shows up
    as a growing per-frame error, not a one-frame blip)."""
    rng = np.random.RandomState(seed)
    base = rng.rand(H, W, 3)
    frames = np.stack([
        np.clip(base + 0.02 * t + drift * rng.randn(H, W, 3), 0, 1)
        for t in range(T)])
    return jnp.asarray(frames.astype(np.float32))


def _two_level_map(H, W, qp_hi=30.0, qp_lo=42.0):
    mb = np.indices((H // 16, W // 16)).sum(0) % 2
    return jnp.asarray(np.where(mb, qp_hi, qp_lo).astype(np.float32))


@pytest.mark.parametrize("qp", [5.0, 30.0, 50.0])
def test_chunk_fused_exact_parity_qp_extremes(qp):
    """fused_exact (interpret) is bit-comparable to the exact encoder
    across the QP range — including QP 5 (near-lossless, large coefficient
    magnitudes) and QP 50 (coarse steps, heavy clipping pressure). At QP 5
    the quant step is ~3e-3: f32 op-ordering differences between the
    kernel's batched GEMM transforms and the reference dct2 can flip a
    round() boundary, moving one coefficient by exactly one step — the
    decoded tolerance admits that single-step flip (well under a pixel
    LSB), nothing larger."""
    frames = _chunk()
    qmap = jnp.full((1, 2, 3), qp)
    d_e, b_e = encode_chunk(frames, qmap)
    d_f, b_f = encode_chunk_fused(frames, qmap, clip_refs=True,
                                  impl="interpret")
    atol = 1e-3 if qp <= 5.0 else 1e-5
    np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_e), atol=atol)
    np.testing.assert_allclose(np.asarray(b_f), np.asarray(b_e), rtol=1e-3)


def test_chunk_fused_pframe_reference_chain():
    """Per-frame QP maps exercise the carried VMEM reference under a QP
    that changes every frame; both the exact and fast semantics hold."""
    frames = _chunk(T=5)
    qmaps = jnp.stack([jnp.full((2, 3), q)
                       for q in (30.0, 42.0, 26.0, 50.0, 34.0)])
    d_e, b_e = encode_chunk(frames, qmaps)
    d_f, b_f = encode_chunk_fused(frames, qmaps, clip_refs=True,
                                  impl="interpret")
    np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_e), atol=1e-5)
    np.testing.assert_allclose(np.asarray(b_f), np.asarray(b_e), rtol=1e-3)
    d_fa, b_fa = encode_chunk_fast(frames, qmaps)
    d_fu, b_fu = encode_chunk_fused(frames, qmaps, impl="interpret")
    np.testing.assert_allclose(np.asarray(d_fu), np.asarray(d_fa), atol=1e-5)
    np.testing.assert_allclose(np.asarray(b_fu), np.asarray(b_fa), rtol=1e-3)


def test_chunk_fused_matches_fast_shared_map():
    """The serving shape (one shared QP map per chunk): fused vs fast."""
    frames = _chunk(T=6, H=48, W=64)
    qmap = _two_level_map(48, 64)[None]
    d_fa, b_fa = encode_chunk_fast(frames, qmap)
    d_fu, b_fu = encode_chunk_fused(frames, qmap, impl="interpret")
    np.testing.assert_allclose(np.asarray(d_fu), np.asarray(d_fa), atol=1e-5)
    np.testing.assert_allclose(np.asarray(b_fu), np.asarray(b_fa), rtol=1e-3)


def test_chunk_fused_scores_path_identity():
    """The in-kernel QP assignment (pooled >= alpha) must reproduce the
    materialized dilate-then-select map exactly, including the traced
    knob triple — the fused fleet step's correctness contract."""
    from repro.core.quality import (QualityConfig, dilate_scores,
                                    qp_maps_from_scores_batched)

    frames = _chunk(T=4, H=48, W=64)
    qcfg = QualityConfig(alpha=0.4, gamma=1, qp_hi=30, qp_lo=42)
    scores = jax.random.uniform(jax.random.PRNGKey(5), (3, 4))
    pooled = dilate_scores(scores, qcfg.gamma)
    knobs = jnp.array([qcfg.alpha, 30.0, 42.0], jnp.float32)
    qmaps, _ = qp_maps_from_scores_batched(scores[None], qcfg)
    for clip_refs in (False, True):
        d_s, b_s = encode_chunk_fused_scores(frames, pooled, knobs,
                                             clip_refs=clip_refs,
                                             impl="interpret")
        d_m, b_m = encode_chunk_fused(frames, qmaps[0], clip_refs=clip_refs,
                                      impl="interpret")
        np.testing.assert_array_equal(np.asarray(d_s), np.asarray(d_m))
        np.testing.assert_array_equal(np.asarray(b_s), np.asarray(b_m))


def test_chunk_fused_all_dropped_frames():
    """All-dropped-frame knob setting: every frame after the chunk head is
    identical (the soft-drop replaced them with the previous kept frame),
    so the P-frames carry only the reference's residual quantization error
    — per-frame bytes collapse to a few percent of the I-frame, and parity
    with exact still holds."""
    one = _chunk(T=1)
    frames = jnp.broadcast_to(one, (4,) + one.shape[1:])
    qmap = jnp.full((1, 2, 3), 35.0)
    d_e, b_e = encode_chunk(frames, qmap)
    d_f, b_f = encode_chunk_fused(frames, qmap, clip_refs=True,
                                  impl="interpret")
    np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_e), atol=1e-5)
    np.testing.assert_allclose(np.asarray(b_f), np.asarray(b_e), rtol=1e-3)
    assert np.all(np.asarray(b_f[1:]) <= 0.05 * float(b_f[0]))


@pytest.mark.parametrize("hw", [(48, 208), (96, 160)])
@pytest.mark.parametrize("clip_refs", [False, True])
def test_chunk_fused_planar_stripes(hw, clip_refs):
    """Shapes the planar stripe layout has to adapt to: a width that is no
    multiple of 128 lanes (48x208 pads to 256) and a height of several
    row blocks (96x160: two 48-row stripes). The QP-map and the scores
    variants match the encoder they stand for (``fused_exact`` the exact
    one, ``fused`` the fast one) and each other, and the padded lanes
    decode to zero and carry no bits, so they change neither the decoded
    pixels nor the bytes."""
    from repro.kernels.mbcodec import ops
    from repro.kernels.mbcodec.kernel import (mbcodec_chunk_pallas,
                                              stripe_rows)

    H, W = hw
    frames = _chunk(T=4, H=H, W=W)
    scores = jax.random.uniform(jax.random.PRNGKey(7), (H // 16, W // 16))
    knobs = jnp.array([0.5, 30.0, 42.0], jnp.float32)
    qmap = jnp.where(scores >= 0.5, 30.0, 42.0)[None]
    d_r, b_r = (encode_chunk if clip_refs else encode_chunk_fast)(frames,
                                                                  qmap)
    d_m, b_m = encode_chunk_fused(frames, qmap, clip_refs=clip_refs,
                                  impl="interpret")
    d_s, b_s = encode_chunk_fused_scores(frames, scores, knobs,
                                         clip_refs=clip_refs,
                                         impl="interpret")
    np.testing.assert_allclose(np.asarray(d_m), np.asarray(d_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(b_m), np.asarray(b_r), rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(d_s), np.asarray(d_m))
    np.testing.assert_array_equal(np.asarray(b_s), np.asarray(b_m))

    planes = ops._planes(frames)
    assert planes.shape == (4, 3, H, -(-W // 128) * 128)
    assert H // stripe_rows(H) == H // 48
    dec, bits = mbcodec_chunk_pallas(planes, ops._stripe_rows(qmap, H, 30.0),
                                     clip_refs=clip_refs, interpret=True)
    assert not np.any(np.asarray(dec[..., W:]))
    assert not np.any(np.asarray(bits[..., W:]))


def test_chunk_fused_xla_fallback_warns_and_matches_fast():
    """Off-TPU the fused backend substitutes the shared-map XLA scan: it
    must announce the substitution once (RuntimeWarning naming the
    substitute) and match the fast encoder."""
    from repro.kernels.mbcodec import ops

    if ops.on_tpu():
        pytest.skip("fallback path only exists off-TPU")
    frames = _chunk()
    qmap = _two_level_map(32, 48)[None]
    ops._FALLBACK_WARNED.clear()
    with pytest.warns(RuntimeWarning, match="substituting"):
        d_x, b_x = encode_chunk_fused(frames, qmap, impl="auto")
    # one-time: a second call must not warn again
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error", RuntimeWarning)
        encode_chunk_fused(frames, qmap, impl="auto")
    d_fa, b_fa = encode_chunk_fast(frames, qmap)
    np.testing.assert_allclose(np.asarray(d_x), np.asarray(d_fa), atol=1e-5)
    np.testing.assert_allclose(np.asarray(b_x), np.asarray(b_fa), rtol=1e-3)


# ---------------------------------------------------------------------------
# accgrad_reduce
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(32, 32, 1), (64, 96, 3), (16, 160, 3)])
def test_accgrad_reduce_matches_ref(shape):
    ks = jax.random.split(jax.random.PRNGKey(shape[0]), 3)
    g, hq, lq = (jax.random.normal(k, shape) for k in ks)
    a = accgrad_reduce_ref(g, hq, lq)
    b = accgrad_reduce(g, hq, lq, impl="interpret")
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-3,
                               atol=1e-3)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dims", [(2, 64, 2, 16, 16), (1, 128, 4, 32, 64),
                                  (2, 100, 2, 16, 32), (1, 32, 1, 8, 32)])
def test_wkv6_kernel_matches_sequential(dims):
    B, S, H, hd, c = dims
    ks = jax.random.split(jax.random.PRNGKey(S), 6)
    r, k, v = (jax.random.normal(kk, (B, S, H, hd)) * 0.5 for kk in ks[:3])
    ld = -jnp.exp(jax.random.normal(ks[3], (B, S, H, hd)) * 0.5 - 1.0)
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.2
    o_ref, s_ref = wkv6_ref(r, k, v, ld, u, s0)
    o_pl, s_pl = wkv6(r, k, v, ld, u, s0, impl="interpret", chunk=c)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_ref),
                               atol=2e-4, rtol=1e-3)


def test_wkv6_model_chunked_matches_sequential():
    from repro.models.rwkv6 import wkv_chunked

    B, S, H, hd = 2, 96, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    r, k, v = (jax.random.normal(kk, (B, S, H, hd)) * 0.5 for kk in ks[:3])
    ld = -jnp.exp(jax.random.normal(ks[3], (B, S, H, hd)) * 0.5 - 1.0)
    u = jax.random.normal(ks[4], (H, hd)) * 0.3
    s0 = jnp.zeros((B, H, hd, hd))
    o_ref, s_ref = wkv6_ref(r, k, v, ld, u, s0)
    o_m, s_m = wkv_chunked(r, k, v, ld, u, s0, chunk=32)
    np.testing.assert_allclose(np.asarray(o_m), np.asarray(o_ref),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_m), np.asarray(s_ref),
                               atol=2e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# decode_attn
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dims", [(2, 256, 2, 4, 32, 255),
                                  (1, 1024, 4, 8, 64, 700),
                                  (2, 96, 1, 2, 16, 40)])
def test_decode_attn_matches_ref(dims):
    B, S, KV, G, hd, pos = dims
    ks = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    a = decode_attn_ref(q, k, v, pos)
    b = decode_attn(q, k, v, pos, impl="interpret", blk=64)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5,
                               rtol=1e-4)


def test_decode_attn_bf16_inputs():
    B, S, KV, G, hd = 1, 128, 2, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd)).astype(jnp.bfloat16)
    a = decode_attn_ref(q, k, v, 100)
    b = decode_attn(q, k, v, 100, impl="interpret", blk=64)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-2)
