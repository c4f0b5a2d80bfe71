"""Plain reference of `xing4.0-29b-a4b-ep8-f32`: one chip's share of
Xing4.0-29B-A4B (`xing4_0`: latent attention with keys of 192 beside values
of 128, a residual of four streams under manifold-constrained
hyper-connections, one leading dense layer, then 64 sigmoid-routed experts
of which this share holds 8 beside one ungated shared expert, an untied
head over an eighth of the vocabulary) under next-token prediction.
Everything is in `benchmark/reference/latent_hc_causal.py`, which reads the
widths and the share from the configuration's file; a token configuration's
reference is followed by `follow(config, ...)` there.
"""

from benchmark.reference import latent_hc_causal

follow = latent_hc_causal.follow
