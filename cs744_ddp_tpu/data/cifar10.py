"""CIFAR-10 loading (host side, NumPy) with a deterministic synthetic fallback.

The reference loads CIFAR-10 via ``torchvision.datasets.CIFAR10(download=True)``
(``/root/reference/src/Part 1/main.py:94-103``).  This environment has no
network egress, so:

  * if the standard python-pickle batches (``cifar-10-batches-py``) exist under
    ``data_dir`` they are loaded (bit-identical to torchvision's arrays, but
    kept NHWC uint8 — the TPU-friendly layout);
  * otherwise a *deterministic, learnable* synthetic stand-in with the same
    shapes/dtypes/cardinalities (50k train / 10k test, 32x32x3 uint8,
    10 classes) is generated, so every train/eval path exercises the
    real pipeline.

Channel normalization stats match the reference exactly
(mean=[125.3,123.0,113.9]/255, std=[63.0,62.1,66.7]/255 —
``/root/reference/src/Part 1/main.py:82-83``).
"""

from __future__ import annotations

import functools
import os
import pickle
from typing import NamedTuple, Tuple

import numpy as np

MEAN = np.array([125.3, 123.0, 113.9], np.float32) / 255.0
STD = np.array([63.0, 62.1, 66.7], np.float32) / 255.0

TRAIN_SIZE = 50_000
TEST_SIZE = 10_000
NUM_CLASSES = 10


class Split(NamedTuple):
    images: np.ndarray  # [N,32,32,3] uint8
    labels: np.ndarray  # [N] int32


def _load_pickle_batches(batch_dir: str, names) -> Split:
    imgs, labs = [], []
    for name in names:
        with open(os.path.join(batch_dir, name), "rb") as f:
            d = pickle.load(f, encoding="bytes")
        data = d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        imgs.append(np.ascontiguousarray(data, np.uint8))
        labs.append(np.asarray(d[b"labels"], np.int32))
    return Split(np.concatenate(imgs), np.concatenate(labs))


# Synthetic-task difficulty knobs, recalibrated (round 7) so the REFERENCE
# config (VGG-11, lr 0.1) shows a GRADED multi-epoch trajectory on the
# stand-in — neither the frozen-at-19.7% collapse round 5 measured (the old
# single-template/low-noise task pushed the first lr-0.1 step so far the net
# died at ln(10) loss) nor instant 100% (one epoch used to saturate, making
# a 3-epoch trajectory uninformative).  See BASELINE.md "Synthetic-task
# recalibration (round 7)" for the measured before/after trajectories.
_TEMPLATES_PER_CLASS = 3   # intra-class variety: one template is memorizable
_NOISE = 0.7               # per-pixel uniform noise fraction of the mix
_SHARED = 0.55             # inter-class template correlation (harder margins)
_CONTRAST = 0.5            # post-mix contrast toward mid-gray: shrinks the
#                            normalized input scale, which is THE knob that
#                            keeps the first lr-0.1 step from killing the
#                            net (measured on the CI tiny model: contrast
#                            1.0 -> frozen at exactly ln(10) loss even at
#                            full 50k scale; 0.5 -> stable graded learning)
_LABEL_NOISE = 0.1         # fraction of labels resampled uniformly: caps
#                            attainable accuracy below saturation


def _class_templates() -> np.ndarray:
    """Fixed low-frequency templates, shared by BOTH splits (so a model
    trained on the train split generalizes to the test split).

    [NUM_CLASSES, _TEMPLATES_PER_CLASS, 32, 32, 3]: every template is a
    blend of one GLOBAL base pattern (weight ``_SHARED`` — inter-class
    correlation, so classes are not linearly-separable blobs far apart),
    a per-class pattern, and a per-template variant (intra-class variety)."""
    rng = np.random.default_rng(42)
    base = rng.uniform(40, 215, size=(1, 1, 4, 4, 3)).astype(np.float32)
    cls = rng.uniform(40, 215,
                      size=(NUM_CLASSES, 1, 4, 4, 3)).astype(np.float32)
    var = rng.uniform(40, 215,
                      size=(NUM_CLASSES, _TEMPLATES_PER_CLASS, 4, 4, 3)
                      ).astype(np.float32)
    small = _SHARED * base + (1 - _SHARED) * (0.65 * cls + 0.35 * var)
    return np.repeat(np.repeat(small, 8, axis=2), 8, axis=3)


@functools.lru_cache(maxsize=8)
def _synthetic_split(n: int, seed: int) -> Split:
    """Class-templated noisy images: deterministic, learnable, NOT trivial.

    A sample draws one of its class's templates, mixes in ``_NOISE``
    uniform noise, pulls the result toward mid-gray by ``_CONTRAST``, and
    with probability ``_LABEL_NOISE`` carries a uniformly-resampled label.
    Calibrated (see knob comments above) so reference-config training
    rises epoch over epoch while staying between the 10% chance floor and
    saturation — the shape a convergence ORACLE needs to detect both a
    broken step (stuck at chance) and a degenerate task (instant 100%).

    Memoized: generating the full 50k split costs ~4 s of pure numpy, and
    multi-trainer processes (the elastic coordinator's shrink/resume
    ladder, the test suite) would otherwise pay it per Trainer.  The cached
    arrays are shared across callers and therefore read-only; consumers
    that need to mutate must copy."""
    rng = np.random.default_rng(seed)
    templates = _class_templates()
    labels = rng.integers(0, NUM_CLASSES, size=n).astype(np.int32)
    tidx = rng.integers(0, _TEMPLATES_PER_CLASS, size=n)
    noise = rng.uniform(0, 255, size=(n, 32, 32, 3)).astype(np.float32)
    images = (1 - _NOISE) * templates[labels, tidx] + _NOISE * noise
    images = 127.5 + _CONTRAST * (images - 127.5)
    if _LABEL_NOISE:
        flip = rng.random(n) < _LABEL_NOISE
        labels = np.where(flip, rng.integers(0, NUM_CLASSES, size=n),
                          labels).astype(np.int32)
    images = np.clip(images, 0, 255).astype(np.uint8)
    images.setflags(write=False)
    labels.setflags(write=False)
    return Split(images, labels)


def has_real_data(data_dir: str = "./data") -> bool:
    """Would ``load`` find the real python-pickle batches here?  The ONE
    check ``cli.py --require-real-data`` shares with the loader, so the
    flag can never disagree with what ``load`` actually does."""
    return os.path.isdir(os.path.join(data_dir, "cifar-10-batches-py"))


def load(data_dir: str = "./data") -> Tuple[Split, Split, bool]:
    """Return (train, test, is_real)."""
    batch_dir = os.path.join(data_dir, "cifar-10-batches-py")
    if os.path.isdir(batch_dir):
        train = _load_pickle_batches(
            batch_dir, [f"data_batch_{i}" for i in range(1, 6)])
        test = _load_pickle_batches(batch_dir, ["test_batch"])
        return train, test, True
    return (_synthetic_split(TRAIN_SIZE, seed=0),
            _synthetic_split(TEST_SIZE, seed=1), False)
