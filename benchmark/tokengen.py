"""Token sequences from `--seed`: the benchmark's generator for the
`train-tokens` traffic kind.  No JAX here.

A run's training data is a STREAM, not a set that recurs: unit `e` trains
on sequences `per_unit * e .. per_unit * (e + 1) - 1`, so no sequence is
seen twice in a run and a window's routing is that of all its sequences.
Ids are uniform over [0, vocab - 1): the last id is the mask id, which the
data never holds.  The same seed gives the same arrays.
"""

from __future__ import annotations

import numpy as np


def make_stream(seed: int, sequences: int, seq_len: int, vocab: int):
    rng = np.random.default_rng([int(seed), 0])
    return rng.integers(0, vocab - 1, (sequences, seq_len), dtype=np.int32)


def make_heldout(seed: int, sequences: int, seq_len: int, vocab: int):
    rng = np.random.default_rng([int(seed), 1])
    return rng.integers(0, vocab - 1, (sequences, seq_len), dtype=np.int32)
