"""Camera frames made on the device from the seed.

The same generative classes as the program's `ImageStream` (a tilted
sine grating, a signed Gaussian blob, pixel noise, per-channel gains;
u8), drawn with `jax.random` so a pool of 1080p frames costs one
compiled call per chunk instead of seconds of host work per frame.  The
same seed gives the same frames.  Channel c's gain does not depend on
how many channels a frame has, so a C1 frame is channel 0 of the C3
frame of the same seed.
"""
from __future__ import annotations

import functools
import math

import numpy as np

N_CLASSES = 10
CHUNK = 16


def seed_key(seed: int):
    """A PRNG key for any whole seed, including ones past 32 bits."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.lru_cache(maxsize=None)
def _chunk_fn(h: int, w: int, n: int, channels: int):
    import jax
    import jax.numpy as jnp

    size = min(h, w)
    ii = jnp.arange(h, dtype=jnp.float32)[:, None]
    jj = jnp.arange(w, dtype=jnp.float32)[None, :]

    def one(key):
        kc, kp, ka, kb, kn = jax.random.split(key, 5)
        c = jax.random.randint(kc, (), 0, N_CLASSES)
        cf = c.astype(jnp.float32)
        phase = jax.random.uniform(kp) * 2 * math.pi
        freq = 1.0 + (c % 5).astype(jnp.float32)
        angle = (c // 5).astype(jnp.float32) * math.pi / 4 \
            + 0.1 * jax.random.normal(ka)
        wave = jnp.sin(freq * 2 * math.pi / size
                       * (jnp.cos(angle) * ii + jnp.sin(angle) * jj) + phase)
        bi, bj = jax.random.randint(kb, (2,), 8, 24).astype(jnp.float32)
        radius = (2.0 + 3.0 * (c % 3).astype(jnp.float32)) * size / 32
        blob = jnp.exp(-((ii - bi * h / 32) ** 2 + (jj - bj * w / 32) ** 2)
                       / radius ** 2)
        sign = (c % 2).astype(jnp.float32) * 2 - 1
        img = 0.6 * wave + 0.8 * blob * sign \
            + 0.15 * jax.random.normal(kn, (h, w))
        gains = 0.5 + 0.5 * jnp.sin(cf + jnp.arange(channels,
                                                  dtype=jnp.float32))
        px = (img[..., None] * gains * 0.5 + 0.5) * 255
        return jnp.clip(px, 0, 255).astype(jnp.uint8)

    return jax.jit(lambda key: jax.vmap(one)(jax.random.split(key, n)))


def pool(seed: int, n: int, h: int, w: int, channels: int = 3) -> np.ndarray:
    """(n, h, w, channels) u8 host frames from `seed`, made CHUNK at a
    time on the default device."""
    import jax
    key = seed_key(seed)
    fn = _chunk_fn(h, w, CHUNK, channels)
    chunks = [jax.device_get(fn(jax.random.fold_in(key, i)))
              for i in range(-(-n // CHUNK))]
    return np.concatenate(chunks)[:n]
