"""Operations and bytes the dense hybrid decoder's linear-mixer kernels
REQUIRE, from the configuration's sizes at the published head sizes (keys of
96, values of 192): `hybrid_flops`' formulas, which `kernel.gdn_roofline`
reads, applied to this configuration, so that lanes a kernel pads a head
with show as a lower share and not as work.  The recurrence and the front
(convolution, silu, norms) apart, since each has kernels of its own here.
"""

from __future__ import annotations

from benchmark import hybrid_flops


def _view(config: dict, **over) -> dict:
    """The configuration as `hybrid_flops` reads a qwen3_next one: the
    period from `layer_types`."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return dict(config,
                full_attention_interval=kinds.index("full_attention") + 1,
                **over)


def linear_layers(config: dict) -> int:
    return hybrid_flops.layers_of_each_kind(_view(config))[0]


def front_train_bytes_per_sequence(config: dict) -> int:
    """`hybrid_flops`' convolution term: the front reads its channels and
    writes q, k, v, forward and backward, float32, every linear layer."""
    channels = (2 * config["linear_num_key_heads"]
                * config["linear_key_head_dim"]
                + config["linear_num_value_heads"]
                * config["linear_value_head_dim"])
    return 2 * 4 * 2 * channels * config["seq_len"] * linear_layers(config)


def recurrence_train_flops_per_sequence(config: dict) -> int:
    """`hybrid_flops.gdn_train_flops_per_sequence` without the convolution's
    taps: 3 x dk x dv multiply-adds a value head a position, forward and
    backward."""
    return hybrid_flops.gdn_train_flops_per_sequence(
        _view(config, linear_conv_kernel_dim=0))


def recurrence_train_bytes_per_sequence(config: dict) -> int:
    """`hybrid_flops.gdn_train_bytes_per_sequence` less the front's: q, k,
    v, g, beta read and o written, forward and backward."""
    return (hybrid_flops.gdn_train_bytes_per_sequence(_view(config))
            - front_train_bytes_per_sequence(config))
