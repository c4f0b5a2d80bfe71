"""Operations and bytes the hybrid decoder's own kernels REQUIRE, from the
configuration's sizes: what the algorithm needs, not what a formulation
happened to compute (the chunked form's triangular solve, masked tiles and
recomputation do not count).  Beside `flops.py`, which counts the whole
step from the `layer_table`, and `lm_flops.py`, whose expert counts serve
this configuration unchanged.
"""

from __future__ import annotations


def layers_of_each_kind(config: dict) -> tuple:
    """(linear-attention layers, full-attention layers) held."""
    full = config["num_hidden_layers"] // config["full_attention_interval"]
    return config["num_hidden_layers"] - full, full


def _conv_channels(config: dict) -> int:
    return (2 * config["linear_num_key_heads"] * config["linear_key_head_dim"]
            + config["linear_num_value_heads"]
            * config["linear_value_head_dim"])


def gdn_train_flops_per_sequence(config: dict) -> int:
    """Convolution + recurrence, every linear layer held, forward and
    backward (twice the forward).  The recurrence as written, a value head
    a position: k^T S, the rank-one write, q^T S = 3 x dk x dv
    multiply-adds; the convolution `taps` multiply-adds a channel."""
    per_position = 2 * (
        3 * config["linear_num_value_heads"] * config["linear_key_head_dim"]
        * config["linear_value_head_dim"]
        + config["linear_conv_kernel_dim"] * _conv_channels(config))
    return 3 * per_position * config["seq_len"] * layers_of_each_kind(config)[0]


def gdn_train_bytes_per_sequence(config: dict) -> int:
    """The least bytes: the convolution reads and writes its channels; the
    recurrence reads q, k (a key head each), v, g, beta and writes o; once
    forward and once backward; float32."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    recurrence = 2 * hk * dk + hv * dv + 2 * hv + hv * dv
    per_position = 4 * (2 * _conv_channels(config) + recurrence)
    return 2 * per_position * config["seq_len"] * layers_of_each_kind(config)[0]


def causal_pairs(seq_len: int) -> int:
    """(query, key) pairs a causal mask allows."""
    return seq_len * (seq_len + 1) // 2


def causal_attention_train_flops_per_sequence(config: dict) -> int:
    """Score and value products over the allowed pairs, all heads, every
    full-attention layer held: 2 products forward and 4 backward, 2 FLOPs a
    multiply-add."""
    width = config["num_attention_heads"] * config["head_dim"]
    return (6 * 2 * causal_pairs(config["seq_len"]) * width
            * layers_of_each_kind(config)[1])
