"""Deterministic synthetic data pipelines.

Token stream: a stateless, seekable generator — batch(step) is a pure
function of (seed, step, shard), so restarts and elastic re-sharding resume
exactly (no iterator state to checkpoint). The "language" has Zipfian
unigrams with Markov bigram structure so cross-entropy has learnable
signal.
"""
from __future__ import annotations

import zlib

import numpy as np
import jax.numpy as jnp


class TokenStream:
    def __init__(self, *, vocab_size: int, seq_len: int, global_batch: int,
                 seed: int = 0, n_shards: int = 1, shard: int = 0):
        assert global_batch % n_shards == 0
        self.vocab = vocab_size
        self.seq = seq_len
        self.batch = global_batch // n_shards
        self.seed = seed
        self.shard = shard
        # fixed Markov mixing params (vocab-sized state kept implicit)
        self._a = 1664525
        self._c = 1013904223

    def batch_at(self, step: int) -> dict:
        """Pure function of step -> {'tokens': (B, S), 'labels': (B, S)}."""
        rng = np.random.default_rng((self.seed, self.shard, step))
        zipf = rng.zipf(1.3, size=(self.batch, self.seq + 1))
        base = (zipf - 1) % self.vocab
        # bigram structure: with p=0.5 the next token is a deterministic
        # function of the previous one (learnable signal)
        follow = (base[:, :-1] * self._a + self._c) % self.vocab
        coin = rng.random((self.batch, self.seq)) < 0.5
        seq = np.where(coin, follow, base[:, 1:])
        tokens = np.concatenate([base[:, :1], seq[:, :-1]], axis=1)
        labels = seq
        return {"tokens": jnp.asarray(tokens, jnp.int32),
                "labels": jnp.asarray(labels, jnp.int32)}


class ImageStream:
    """Synthetic CIFAR-like classification set: 10 generative classes with
    distinct spatial structure (bars, blobs, checker, gradient x frequency),
    u8 C3 — same compute character as the paper's Cifar-10 testbed at the
    default 32x32.  `res` is a side length or an (H, W) pair; the structure
    scales with the frame (at 32x32 the frames are the historical ones)."""

    def __init__(self, *, n_classes: int = 10, res=32, seed: int = 0):
        self.n_classes = n_classes
        self.res = res
        self.seed = seed

    def batch(self, n: int, *, split: str = "train"):
        # crc32, not hash(): str hashes are salted per process, and a
        # seeded set must be the same set in every run
        rng = np.random.default_rng((self.seed, zlib.crc32(split.encode())))
        h, w = (self.res, self.res) if np.isscalar(self.res) else self.res
        size = min(h, w)                # length unit of the class structure
        scale = size / 32
        y = rng.integers(0, self.n_classes, n)
        xs = np.zeros((n, h, w, 3), np.uint8)
        i_idx, j_idx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
        for i in range(n):
            c = y[i]
            phase = rng.random() * 2 * np.pi
            freq = 1 + (c % 5)
            angle = (c // 5) * np.pi / 4 + rng.normal(0, 0.1)
            wave = np.sin(freq * 2 * np.pi / size *
                          (np.cos(angle) * i_idx + np.sin(angle) * j_idx) + phase)
            blob_i, blob_j = rng.integers(8, 24, 2)
            blob_i, blob_j = blob_i * h / 32, blob_j * w / 32
            radius = (2 + 3 * (c % 3)) * scale
            blob = np.exp(-(((i_idx - blob_i) ** 2 + (j_idx - blob_j) ** 2) / radius ** 2))
            img = 0.6 * wave + 0.8 * blob * ((c % 2) * 2 - 1)
            img = img + rng.normal(0, 0.15, img.shape)
            for ch in range(3):
                scale_ch = 0.5 + 0.5 * np.sin(c + ch)
                xs[i, :, :, ch] = np.clip((img * scale_ch * 0.5 + 0.5) * 255, 0, 255)
        return jnp.asarray(xs), jnp.asarray(y, jnp.int32)

    def image(self, resolution: tuple[int, int], *, channels: int = 1, seed: int = 0):
        """A single large test image (for the filtering/erosion benchmarks)."""
        rng = np.random.default_rng((self.seed, seed, resolution[0]))
        h, w = resolution
        shape = (h, w) if channels == 1 else (h, w, channels)
        return jnp.asarray(rng.integers(0, 256, shape, dtype=np.uint8))
