"""Token sequences for the decoder models: a stream for training, a fixed
held-out set for evaluation.

The image path stages ONE set of 50,000 images and reuses it every epoch
(``cifar10.Split``).  A language model's corpus is a stream: epoch ``e``
trains on its own ``per_epoch`` sequences and none comes back within a run.
``TokenSplit.epoch(e, n)`` hands them out; the trainer stages them on the
device an epoch at a time, as it stages the images.

Where ``<data_dir>/tokens/train.npy`` and ``heldout.npy`` exist ([N, L]
int32 arrays of token ids) they are the data (a file shorter than the run
wraps around); otherwise a deterministic synthetic stand-in: uniformly
random ids from numpy's generator, keyed by (seed, epoch).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

import numpy as np

SYNTHETIC_PER_EPOCH = 64
SYNTHETIC_HELDOUT = 16


@dataclasses.dataclass(frozen=True)
class TokenSplit:
    """`tokens` [N, L] int32 when the data is a file (or the held-out set);
    None for the synthetic stream, which draws epoch e from (seed, e).
    `len()` counts sequences, as `len(Split.labels)` counts images."""
    tokens: Optional[np.ndarray]
    seq_len: int
    vocab: int              # ids are drawn from [0, vocab)
    seed: int = 0

    def __len__(self) -> int:
        return SYNTHETIC_PER_EPOCH if self.tokens is None \
            else len(self.tokens)

    @property
    def images(self) -> Optional[np.ndarray]:
        """The rows, under the name the image splits give theirs."""
        return self.tokens

    @property
    def labels(self) -> np.ndarray:
        """One per sequence, as the image splits have one per image: a
        decoder's targets are its tokens, so these only count the rows (0;
        the eval staging pads with -1, the image path's convention)."""
        return np.zeros((len(self),), np.int32)

    def epoch(self, e: int, n: int) -> np.ndarray:
        """Sequences n*e .. n*e + n - 1 of the stream, [n, L] int32."""
        if self.tokens is None:
            rng = np.random.default_rng([self.seed, 7, e])
            return rng.integers(0, self.vocab, (n, self.seq_len),
                                dtype=np.int32)
        idx = (np.arange(n) + n * e) % len(self.tokens)
        return np.ascontiguousarray(self.tokens[idx])


def has_real_data(data_dir: str) -> bool:
    return os.path.isfile(os.path.join(data_dir, "tokens", "train.npy"))


def load(data_dir: str, seq_len: int, vocab: int, seed: int = 0
         ) -> Tuple[TokenSplit, TokenSplit, bool]:
    """(train, held-out, is_real).  `vocab` excludes the mask id."""
    if has_real_data(data_dir):
        d = os.path.join(data_dir, "tokens")
        train = np.load(os.path.join(d, "train.npy"), mmap_mode="r")
        held = np.load(os.path.join(d, "heldout.npy"))
        for name, a in (("train", train), ("heldout", held)):
            if a.ndim != 2 or a.shape[1] != seq_len or a.dtype != np.int32:
                raise ValueError(
                    f"{d}/{name}.npy: expected int32 [N, {seq_len}], got "
                    f"{a.dtype} {a.shape}")
        return (TokenSplit(train, seq_len, vocab),
                TokenSplit(np.asarray(held), seq_len, vocab), True)
    rng = np.random.default_rng([seed, 11])
    held = rng.integers(0, vocab, (SYNTHETIC_HELDOUT, seq_len),
                        dtype=np.int32)
    return (TokenSplit(None, seq_len, vocab, seed),
            TokenSplit(held, seq_len, vocab, seed), False)
