"""Operations a configuration's forward and backward passes REQUIRE, from
its layer table: convolutions and the classifier, nothing the compiler
happened to emit (elementwise work, recomputation, layout changes), so the
count does not move when the program does.

A configuration file carries `layer_table`: rows
`[kind, out_h, out_w, c_in, c_out, k]` with kind "conv" (k x k kernel,
output out_h x out_w) or "fc" (out_h = out_w = k = 1).
"""

from __future__ import annotations


def forward_macs_per_image(layer_table) -> int:
    macs = 0
    for kind, oh, ow, cin, cout, k in layer_table:
        if kind not in ("conv", "fc"):
            raise ValueError(f"unknown layer kind {kind!r}")
        macs += oh * ow * cin * cout * k * k
    return macs


def train_flops_per_image(layer_table) -> int:
    """Forward + backward: 2 FLOPs per MAC, and the backward pass costs
    twice the forward (gradient w.r.t. input and w.r.t. weights)."""
    return 2 * forward_macs_per_image(layer_table) * 3


def eval_flops_per_image(layer_table) -> int:
    return 2 * forward_macs_per_image(layer_table)
