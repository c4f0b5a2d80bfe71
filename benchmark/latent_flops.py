"""Operations and bytes the latent-attention decoder's own kernels REQUIRE,
from the configuration's sizes: what the algorithm needs, not what a
formulation happened to compute (masked tiles, recomputation and the passes
a compiler splits a mixer into do not count).  Beside `flops.py`, which
counts the whole step from the `layer_table`, and `lm_flops.py`, whose
expert counts serve this configuration unchanged.
"""

from __future__ import annotations

from benchmark.hybrid_flops import causal_pairs


def mla_attention_train_flops_per_sequence(config: dict) -> int:
    """The score and value products over the allowed pairs, all heads,
    every layer held, keys of nope + rope beside values of v: forward
    q k^T and p v; backward dV and dP (the value size each), dQ and dK
    (the key size each); 2 FLOPs a multiply-add."""
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    per_pair = 2 * (qk + v) + 2 * (v + v + qk + qk)
    return (per_pair * causal_pairs(config["seq_len"])
            * config["num_attention_heads"] * config["num_hidden_layers"])


def mhc_train_bytes_per_sequence(config: dict) -> int:
    """The least bytes of the hyper-connections, float32: a sublayer reads
    a sequence's streams once and writes them once forward, reads them
    twice (the streams and their cotangent) and writes once backward, and
    reads its Phi each way and writes its gradient; two sublayers a layer,
    every layer held."""
    n, width = config["hc_mult"], config["hidden_size"]
    streams = 4 * config["seq_len"] * n * width
    phi = 4 * n * width * (n * n + 2 * n)
    return (5 * streams + 3 * phi) * 2 * config["num_hidden_layers"]


def mhc_train_flops_per_sequence(config: dict) -> int:
    """The narrow product, the read and the write of every sublayer,
    forward and backward (twice the forward)."""
    n, width = config["hc_mult"], config["hidden_size"]
    per_position = n * width * (n * n + 2 * n) + n * width \
        + (n * n + n) * width
    return (3 * 2 * per_position * config["seq_len"]
            * 2 * config["num_hidden_layers"])
