"""Operations the window-and-full decoder's sliding-window kernels REQUIRE,
from the configuration's sizes: the products over the pairs the window
allows, not the tiles a kernel visits (`window_pairs` against the band's
tiles: twice as many scores at 1,024 x 1,024 tiles) and not the forward
pass a layer's recomputation runs again.  Beside `flops.py`, which counts
the whole step from the `layer_table`, and `hybrid_flops.py`, whose causal
count the full layers take.
"""

from __future__ import annotations


def window_pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs a window of `window` keys, the query's own among
    them, allows over `seq_len` positions: sum_i min(i + 1, window)."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def sliding_layers(config: dict) -> int:
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return sum(kind == "sliding_attention" for kind in kinds)


def window_attention_train_flops_per_sequence(config: dict) -> int:
    """Score and value products over the allowed pairs, all query heads,
    every sliding layer held: 2 products forward and 4 backward, 2 FLOPs a
    multiply-add."""
    width = config["num_attention_heads"] * config["head_dim"]
    return (6 * 2 * window_pairs(config["seq_len"], config["sliding_window"])
            * width * sliding_layers(config))
