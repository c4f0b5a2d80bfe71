"""Plain reference of `qwen3-next-80b-a3b-ep16-f32`: one chip's share of
Qwen3-Next-80B-A3B-Instruct (`qwen3_next`: three Gated-DeltaNet
linear-attention layers to one gated softmax-attention layer, 512
softmax-routed experts of which this share holds 32, one gated shared
expert, an untied head over an eighth of the vocabulary) under next-token
prediction.  Everything is in `benchmark/reference/hybrid_causal.py`, which
reads the widths and the share from the configuration's file; a token
configuration's reference is followed by `follow(config, ...)` there.
"""

from benchmark.reference import hybrid_causal

follow = hybrid_causal.follow
