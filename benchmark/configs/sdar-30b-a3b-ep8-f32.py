"""Plain reference of `sdar-30b-a3b-ep8-f32`: one chip's share of
SDAR-30B-A3B-Chat (`sdar_moe`: grouped-query attention with a per-head
RMSNorm on q and k and rotary positions, 128 softmax-routed experts of
which this share holds 16, an untied head over an eighth of the vocabulary)
under the block-diffusion training objective.  Everything is in
`benchmark/reference/blockdiff.py`, which reads the widths and the share
from the configuration's file; a token configuration's reference is
followed by `follow(config, ...)` there, not by `reference/common.py`.
"""

from benchmark.reference import blockdiff

follow = blockdiff.follow
