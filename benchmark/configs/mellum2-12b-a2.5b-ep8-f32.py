"""Plain reference of `mellum2-12b-a2.5b-ep8-f32`: one chip's share of
Mellum2-12B-A2.5B (`mellum`: 28 layers in the pattern sliding, sliding,
sliding, full, the sliding ones under a window of 1,024 keys with the
default rotary, the full ones causal under YaRN's; 64 softmax-routed experts
in every layer, of which this share holds 8; an untied head over an eighth
of the vocabulary) under next-token prediction.  Everything is in
`benchmark/reference/window_causal.py`, which reads the widths and the share
from the configuration's file; a token configuration's reference is followed
by `follow(config, ...)` there.
"""

from benchmark.reference import window_causal

follow = window_causal.follow
