"""Plain reference of `olmo-hybrid-7b-pp8-f32`: one chip's share of
Olmo-Hybrid-7B (`olmo_hybrid`: 32 dense layers in the pattern linear,
linear, linear, full, post-norm; the linear ones Gated DeltaNet with keys of
96, values of 192 and beta = 2 sigmoid(b), so negative eigenvalues; the full
ones causal softmax attention without rotary over 30 heads of 128, q and k
normed over the whole width; an untied head over an eighth of the
vocabulary) under next-token prediction.  Everything is in
`benchmark/reference/dense_hybrid_causal.py`, which reads the widths and the
share from the configuration's file; a token configuration's reference is
followed by `follow(config, ...)` there.
"""

from benchmark.reference import dense_hybrid_causal

follow = dense_hybrid_causal.follow
