"""Operations and bytes the decoder's two kernels REQUIRE, from the
configuration's sizes: what the algorithm needs, not what a kernel happened
to compute (masked tiles, recomputation and buffer rows do not count).
Beside `flops.py`, which counts the whole step from the `layer_table`.
"""

from __future__ import annotations


def allowed_pairs(seq_len: int, block: int) -> int:
    """(query, key) pairs the block-diffusion mask allows among the 2L
    positions of one sequence: L*B noisy->noisy, (L*L - L*B)/2 noisy->clean,
    (L*L + L*B)/2 clean->clean; a quarter of (2L)^2, and L*B."""
    return seq_len * block + seq_len * seq_len


def attention_train_flops_per_sequence(config: dict) -> int:
    """Score and value products over the allowed pairs, all heads, all
    layers held: 2 products forward and 4 backward (dV, dP, dQ, dK), 2
    FLOPs a multiply-add."""
    pairs = allowed_pairs(config["seq_len"], config["block_length"])
    width = config["num_attention_heads"] * config["head_dim"]
    return 6 * 2 * pairs * width * config["num_hidden_layers"]


def expert_train_flops_per_row(config: dict) -> int:
    """Gate, up and down products of one routed row, forward and backward
    (twice the forward)."""
    return 3 * 2 * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_weight_bytes_per_step(config: dict) -> int:
    """The least bytes the held experts' weights cost a step: read for the
    forward pass, read for the backward pass, their gradient written; every
    layer held, float32."""
    per_layer = (len(config["experts_held"]) * 3 * config["hidden_size"]
                 * config["moe_intermediate_size"] * 4)
    return 3 * per_layer * config["num_hidden_layers"]
