"""Inputs from --seed: CIFAR-shaped uint8 images and int32 labels, made
once with numpy and uploaded once by the trainer's own staging.

Class templates (low-frequency 4x4 colour patterns, a shared base plus a
per-class part) under heavy per-pixel noise, so every row differs, the
task is learnable but not trivial, and no step overflows at the
configuration's learning rate.  The trainer's `Split` is host arrays, so
the data cannot be made on the device; integer arithmetic keeps 200,000
images (the four-chip cell) to about a second.
"""

from __future__ import annotations

import numpy as np

NUM_CLASSES = 10
_NOISE_MASK = 63        # per-pixel uniform noise in [0, 64)
_TEMPLATE_SPAN = 44     # class template in [64, 64 + 44)


def _templates(rng) -> np.ndarray:
    """[classes, 32, 32, 3] uint8 in [64, 108): a base pattern shared by
    all classes plus a per-class one, 4x4 blocks of colour."""
    base = rng.integers(0, _TEMPLATE_SPAN, size=(1, 4, 4, 3))
    cls = rng.integers(0, _TEMPLATE_SPAN, size=(NUM_CLASSES, 4, 4, 3))
    small = 64 + (base + cls) // 2
    return np.repeat(np.repeat(small, 8, axis=1), 8, axis=2).astype(np.uint8)


def make_split(seed: int, n: int, salt: int):
    """(images [n,32,32,3] uint8, labels [n] int32) for (seed, salt).
    Pixels stay in [64, 172): a small normalised input scale keeps the
    first steps finite (the program's own synthetic set found the same,
    data/cifar10.py _CONTRAST)."""
    rng = np.random.default_rng([int(seed), int(salt)])
    templates = _templates(np.random.default_rng([int(seed), 7]))
    labels = rng.integers(0, NUM_CLASSES, size=n).astype(np.int32)
    # raw generator bytes, masked: the fastest uniform uint8 numpy offers
    images = np.frombuffer(bytearray(rng.bytes(n * 3072)), np.uint8)
    images &= _NOISE_MASK
    images = images.reshape(n, 32, 32, 3)
    block = 8192
    for lo in range(0, n, block):
        hi = min(n, lo + block)
        images[lo:hi] += templates[labels[lo:hi]]
    return images, labels
