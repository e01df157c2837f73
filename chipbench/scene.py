"""Seeded synthetic dashcam video, generated on the device.

The statistics follow the repository's ``dashcam`` genre: a textured
background of six plane waves plus pixel noise, panned 1.4 px a frame,
with a per-channel tint; three to six rectangular objects (every third one
small and low-contrast) that move, grow, and carry a darker border and an
inner patch. One jitted call draws a chunk of a stream's frames from the
stream's key and the chunk's first frame index, so set-up makes 720p video
in milliseconds instead of host seconds, one chunk at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MAX_OBJECTS = 6
PAN_PX = 1.4


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed that fits 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _background(key, t, H, W):
    kw, kn, kt = jax.random.split(key, 3)
    f = jax.random.uniform(kw, (6, 4))
    fx = 0.002 + 0.018 * f[:, 0]
    fy = 0.002 + 0.018 * f[:, 1]
    ph = 2 * jnp.pi * f[:, 2]
    amp = 0.05 + 0.10 * f[:, 3]
    yy = jnp.arange(H, dtype=jnp.float32)[:, None, None]
    xx = (jnp.arange(W, dtype=jnp.float32)[None, :, None]
          - jnp.floor(t * PAN_PX))  # the pan: a roll along the width
    xx = jnp.mod(xx, W)
    base = 0.45 + (amp * jnp.sin(2 * jnp.pi * (fx * xx + fy * yy) + ph)).sum(-1)
    noise = 0.015 * jax.random.normal(kn, (H, W))
    base = base + jnp.roll(noise, jnp.floor(t * PAN_PX).astype(jnp.int32),
                           axis=1)
    tint = jax.random.uniform(kt, (3,), minval=0.85, maxval=1.15)
    return jnp.clip(base[..., None] * tint, 0.0, 1.0)


def _objects(key, H, W):
    ks = jax.random.split(key, 10)
    i = jnp.arange(MAX_OBJECTS)
    n = jax.random.randint(ks[0], (), 3, 7)
    small = i % 3 == 0

    def u(k, lo, hi):
        return jax.random.uniform(k, (MAX_OBJECTS,), minval=lo, maxval=hi)

    w0 = jnp.where(small, u(ks[1], 12, 26), u(ks[2], 24, 64))
    contrast = jnp.where(small, u(ks[3], 0.3, 0.5), u(ks[4], 0.35, 0.8))
    base = u(ks[5], 0.35, 0.6)
    color = jnp.clip(base[:, None] + contrast[:, None] * jax.random.uniform(
        ks[6], (MAX_OBJECTS, 3), minval=-1, maxval=1), 0.05, 0.95)
    pos = jax.random.uniform(ks[7], (MAX_OBJECTS, 4))
    return {
        "on": i < n,
        "cx": (0.1 + 0.8 * pos[:, 0]) * W, "cy": (0.35 + 0.5 * pos[:, 1]) * H,
        "vx": -3.5 + 7.0 * pos[:, 2], "vy": -1.0 + 2.0 * pos[:, 3],
        "w": w0, "h": w0 * u(ks[8], 0.55, 0.8),
        "grow": u(ks[9], 1.0, 1.02), "color": color,
    }


def _draw(img, t, o, H, W):
    """Draw one object into one frame (H, W, 3) at frame index t."""
    s = o["grow"] ** t
    w, h = o["w"] * s, o["h"] * s
    cx, cy = o["cx"] + o["vx"] * t, o["cy"] + o["vy"] * t
    x0 = jnp.maximum(0, jnp.floor(cx - w / 2)).astype(jnp.int32)
    x1 = jnp.minimum(W, jnp.floor(cx + w / 2)).astype(jnp.int32)
    y0 = jnp.maximum(0, jnp.floor(cy - h / 2)).astype(jnp.int32)
    y1 = jnp.minimum(H, jnp.floor(cy + h / 2)).astype(jnp.int32)
    hh, ww = y1 - y0, x1 - x0
    ok = o["on"] & (hh > 1) & (ww > 1)
    yy = jnp.arange(H)[:, None]
    xx = jnp.arange(W)[None, :]
    inside = ok & (yy >= y0) & (yy < y1) & (xx >= x0) & (xx < x1)
    grad = 0.85 + 0.3 * (yy - y0) / jnp.maximum(hh - 1, 1)
    fill = jnp.clip(o["color"] * grad[..., None], 0.0, 1.0)
    img = jnp.where(inside[..., None], fill, img)
    border = inside & ((xx < x0 + jnp.maximum(1, ww // 12))
                       | (yy < y0 + jnp.maximum(1, hh // 10)))
    img = jnp.where(border[..., None], img * 0.4, img)
    iy0, ix0 = y0 + hh // 4, x0 + ww // 4
    patch = (inside & (yy >= iy0) & (yy < iy0 + jnp.maximum(1, hh // 5))
             & (xx >= ix0) & (xx < ix0 + jnp.maximum(1, ww // 3)))
    return jnp.where(patch[..., None], 0.15, img)


@functools.partial(jax.jit, static_argnames=("T", "H", "W"))
def dashcam_chunk(key, t0, T: int, H: int, W: int) -> jax.Array:
    """Frames ``t0 .. t0 + T - 1`` of the stream drawn from ``key``:
    ``(T, H, W, 3)`` float32 in [0, 1]."""
    kb, ko = jax.random.split(key)
    objs = _objects(ko, H, W)

    def frame(t):
        img = _background(kb, t, H, W)
        for k in range(MAX_OBJECTS):
            img = _draw(img, t, jax.tree_util.tree_map(lambda v: v[k], objs),
                        H, W)
        return img

    return jax.lax.map(frame, t0 + jnp.arange(T, dtype=jnp.float32))
